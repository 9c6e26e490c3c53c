"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload deepseek-moe-16b.chat --seed 7 \
        --seconds 30 --trace 0

A cell is found by name in ``BENCHMARK.json``; its configuration, traffic
mix and limits are data files under ``bench/configs``, ``bench/traffic``
and ``bench/limits``; the traffic names the driver (``bench/drivers``) and
each per-layer metric has a reader in ``bench/metrics``.  With ``--trace 0``
the line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of a slice of the window.
The run needs the accelerator: without one, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import common  # noqa: E402


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict,
             limits: dict, seed: int, seconds: float, trace: bool,
             t0: float = T0) -> dict:
    """Drive one run of ``cell`` and build its result line (a dict)."""
    import jax
    counter = common.CompileCounter()
    driver = importlib.import_module("bench.drivers." + traffic["driver"])
    res = driver.run(config, traffic, limits, seed, seconds, trace, t0,
                     counter)
    name = cell["name"]
    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if common.applies(m, name, bench):
                v = common.load_reader(m["name"])(res["ctx"])
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if common.applies(m, name, bench):
                metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                      "unit": m["unit"]}
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    checks = res["checks"]
    line = {"correct": bool(checks) and all(
                c["value"] <= c["limit"] for c in checks.values()),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    red = res["ctx"].get("trace")
    if trace and red:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["info"] = res["info"]
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic, limits = common.resolve(args.workload)
    devices = common.require_chips(cell["chips"])
    print(f"bench: {len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform}), compile cache "
          f"{common.setup_compile_cache()}", file=sys.stderr)
    line = run_cell(bench, cell, config, traffic, limits, args.seed,
                    args.seconds, bool(args.trace))
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
