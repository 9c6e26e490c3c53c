"""Read the numbers a cell's limits are set from: sound runs, the control
and each planted fault (``bench/faults.py``), many runs in one process.

    python bench/control.py --workload deepseek-moe-16b.chat \
        --runs program:101,program:102,control:101,half_batch:101 \
        --out readings.json

Each run is ``run.run_cell`` as a chip run drives it, at the cell's own
size and load and with its committed limits, but with a short window
(``--seconds``, one second by default): the first batch runs to its end, so
a run serves one batch and checks the sample a run draws from it.  Each
run's line (``correct``, the numbers compared beside their limits) is
printed and written to ``--out``.  The benchmark's own runs do not run
this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import common, faults  # noqa: E402
from bench import run as bench_run  # noqa: E402


def reading(resolved, mode: str, seed: int, seconds: float) -> dict:
    """One run of the cell with ``mode`` planted; its line, trimmed."""
    bench, cell, config, traffic, limits = resolved
    t = time.perf_counter()
    with faults.planted(mode, config, traffic):
        line = bench_run.run_cell(bench, cell, config, traffic, limits,
                                  seed, seconds, False,
                                  t0=time.perf_counter())
    return {"mode": mode, "seed": seed, "correct": line["correct"],
            "checks": line["checks"], "info": line["info"],
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", required=True,
                    help="comma-separated mode:seed pairs")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    resolved = common.resolve(args.workload)
    common.require_chips(resolved[1]["chips"])
    common.setup_compile_cache()
    rows = []
    for item in args.runs.split(","):
        mode, seed = item.split(":")
        rows.append(reading(resolved, mode, int(seed), args.seconds))
        print(json.dumps(rows[-1]), flush=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
