"""Read the numbers a hybrid cell's limits are set from: sound runs and the
float8 control, many runs in one process.  This is ``bench/control_mla.py``
for the cells of ``bench/drivers/serve_hybrid.py``, whose judge neither
the control that ``bench/faults.py`` plants nor ``control_mla.py``'s
reaches.

    python bench/control_hybrid.py \
        --workload granite-4.0-h-small.reasoning-b128 \
        --runs program:101,program:102,control:101 --out readings.json

Modes: ``program`` (nothing planted) and ``control`` (at each position of
the served requests, the token the float8 reference puts first is scored
under the float32 reference instead of the served one, and the float8
reference's final SSM states are compared in place of the program's).  Each run is
``run.run_cell`` at the cell's own size and load with a short window
(``--seconds``, one second by default): the first batch runs to its end.
The benchmark's own runs do not run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import common, control  # noqa: E402
from bench.drivers import serve_hybrid  # noqa: E402

MODES = ("program", "control")


@contextlib.contextmanager
def planted(mode: str):
    """Run the body with ``mode`` planted under the hybrid serving driver."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
    good = serve_hybrid.judge
    if mode == "control":
        def judge(config, seed, prompts, served, states, quant=None):
            ctl = good(config, seed, prompts, served, states, quant="fp8")
            return {"program": ctl["control"],
                    "state_err": ctl["control_state_err"]}
        serve_hybrid.judge = judge
    try:
        yield
    finally:
        serve_hybrid.judge = good


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
        with planted(mode):
            row = control.reading(resolved, "program", int(seed),
                                  args.seconds)
        rows.append({**row, "mode": mode})
        print(json.dumps(rows[-1]), flush=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
