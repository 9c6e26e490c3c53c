"""Benchmark entrypoint — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (human-readable tables are
prefixed with ``#``).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table2 fig7
    PYTHONPATH=src python -m benchmarks.run --only engine,traffic --fast
    PYTHONPATH=src python -m benchmarks.run --list
    PYTHONPATH=src python -m benchmarks.run --fast --only traffic \
        --json-out BENCH_traffic.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _peak_rss_mb() -> float:
    """Peak resident set size of this process, MB (0.0 if unavailable)."""
    try:
        import resource
        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # Linux reports kilobytes, macOS bytes.
        if sys.platform == "darwin":                  # pragma: no cover
            rss_kb /= 1024.0
        return round(rss_kb / 1024.0, 1)
    except Exception:                                 # pragma: no cover
        return 0.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of benchmark names (space- or "
                         "comma-separated); see --list")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print available benchmark names and exit")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write structured results (benches that return "
                         "dicts) to this JSON file")
    ap.add_argument("--profile", action="store_true",
                    help="adds the first-call jit-compile time column to "
                         "the always-recorded per-bench wall time and "
                         "peak RSS (stdout and the --json-out payload "
                         "under '_profile')")
    args = ap.parse_args()

    compile_s = {"total": 0.0}
    if args.profile:
        # Sum jax's own compile-event durations (trace + lowering +
        # backend compile); the per-bench delta is the first-call
        # compilation cost that steady-state reruns would not pay.
        try:
            import jax

            def _on_event(key: str, value: float, **kw) -> None:
                if key.startswith("/jax/core/compile"):
                    compile_s["total"] += value

            jax.monitoring.register_event_duration_secs_listener(_on_event)
        except Exception as e:                      # pragma: no cover
            print(f"# profile: no jax compile events ({e})",
                  file=sys.stderr)

    from . import (bench_admission, bench_batching, bench_calibration,
                   bench_ctrl, bench_engine, bench_federation, bench_fig6,
                   bench_fig7, bench_fleet, bench_kernels, bench_linkstate,
                   bench_multi_expert, bench_obs, bench_placement,
                   bench_replan, bench_roofline, bench_table2,
                   bench_traffic)

    n_tok = 120 if args.fast else 400
    suite = {
        "engine": (bench_engine, lambda: bench_engine.run(
            n_tokens=200 if args.fast else 1000,
            n_plans=8 if args.fast else 16,
            n_slots=40 if args.fast else None)),
        "traffic": (bench_traffic,
                    lambda: bench_traffic.run(fast=args.fast)),
        "admission": (bench_admission,
                      lambda: bench_admission.run(fast=args.fast)),
        "batching": (bench_batching,
                     lambda: bench_batching.run(fast=args.fast)),
        "replan": (bench_replan,
                   lambda: bench_replan.run(fast=args.fast)),
        "ctrl": (bench_ctrl,
                 lambda: bench_ctrl.run(fast=args.fast)),
        "fleet": (bench_fleet,
                  lambda: bench_fleet.run(fast=args.fast)),
        "federation": (bench_federation,
                       lambda: bench_federation.run(fast=args.fast)),
        "table2": (bench_table2, lambda: bench_table2.run(
            n_tokens=n_tok, n_slots=60 if args.fast else None)),
        "fig6": (bench_fig6,
                 lambda: bench_fig6.run(n_tokens=150 if args.fast else 600)),
        "fig7": (bench_fig7,
                 lambda: bench_fig7.run(n_tokens=80 if args.fast else 250)),
        "multi_expert": (bench_multi_expert, lambda: bench_multi_expert.run(
            n_tokens=80 if args.fast else 250)),
        "placement": (bench_placement, bench_placement.run),
        "kernels": (bench_kernels, bench_kernels.run),
        "linkstate": (bench_linkstate, lambda: bench_linkstate.run(
            n_tokens=80 if args.fast else 250)),
        "roofline": (bench_roofline, bench_roofline.run),
        "calibration": (bench_calibration,
                        lambda: bench_calibration.run(fast=args.fast)),
        "obs": (bench_obs, lambda: bench_obs.run(fast=args.fast)),
    }
    if args.list:
        # One line per bench: name + the module docstring's summary line.
        width = max(len(n) for n in suite)
        for name, (module, _) in suite.items():
            summary = (module.__doc__ or "").strip().splitlines()
            print(f"{name:<{width}}  {summary[0] if summary else ''}")
        return

    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    selected = []
    for item in (args.only or list(suite)):
        selected += [s for s in item.split(",") if s]
    print("name,us_per_call,derived")
    t0 = time.time()
    structured: dict = {}
    profile: dict = {}
    for name in selected:
        if name not in suite:
            print(f"unknown bench {name!r} (see --list)", file=sys.stderr)
            raise SystemExit(2)
        t_bench, c_bench = time.time(), compile_s["total"]
        result = suite[name][1]()
        # Wall time and peak RSS are recorded for every bench
        # unconditionally — a --profile run that sees no jax compile
        # events still ships a non-empty profile payload.
        wall = time.time() - t_bench
        profile[name] = {"wall_s": round(wall, 3),
                         "peak_rss_mb": _peak_rss_mb()}
        if args.profile:
            comp = compile_s["total"] - c_bench
            profile[name]["compile_s"] = round(comp, 3)
            print(f"profile/{name},{wall * 1e6:.3f},"
                  f"compile_s={comp:.3f};steady_s={wall - comp:.3f}")
        if isinstance(result, dict):
            structured[name] = result
    structured["_profile"] = profile
    print(f"# total {time.time()-t0:.1f}s")
    if args.json_out:
        # Resolved service-model provenance: jax/backend the numbers were
        # produced on plus the content hash of every calibration table
        # loaded during the run — and the per-bench profile (wall, peak
        # RSS, compile time when measured) — so CI diffs compare like
        # with like and every artifact carries its own cost record.
        from repro.core import calibration
        structured["_provenance"] = dict(calibration.provenance(),
                                         profile=profile)
        with open(args.json_out, "w") as f:
            json.dump(structured, f, indent=2)
        print(f"# wrote {args.json_out}")


if __name__ == "__main__":
    main()
