"""The trace reduction on hand-made events and on a recorded chip slice."""
import json
import os

import pytest

from bench import trace_reduce

DEV = "/device:TPU:0"
MS = 1_000_000


def _events():
    return [
        ("/host:CPU", "python", "bench.window", 0, 10 * MS),
        ("/host:CPU", "python", "bench.dispatch", 0, 1 * MS),
        ("/host:CPU", "python", "bench.fetch", 6 * MS, 3 * MS),
        (DEV, "XLA Modules", "jit_serve_step(3)", 1 * MS, 5 * MS),
        (DEV, "XLA Modules", "jit_serve_step(3)", 9 * MS, 2 * MS),
        (DEV, "XLA Ops", "fusion.1", 1 * MS, 2 * MS),
        (DEV, "XLA Ops", "fusion.2", 2 * MS, 2 * MS),     # overlaps fusion.1
        (DEV, "XLA Ops", "dot.3", 5 * MS, 1 * MS),
        (DEV, "XLA Ops", "fusion.1", 9 * MS, 2 * MS),     # runs past window
    ]


@pytest.mark.parametrize("n", [1, 2], ids=["one-slice", "two-slices"])
def test_busy_union_idle_share_and_programs(n):
    # Slices (profiler sessions) are summed: n copies read n times one.
    red = trace_reduce.reduce(*[_events()] * n)
    assert red["devices"] == [DEV]
    assert red["window_s"] == pytest.approx(0.010 * n)
    # Busy inside [0, 10 ms]: [1, 4] + [5, 6] + [9, 10] = 5 ms.
    assert red["busy_s"] == pytest.approx(0.005 * n)
    assert red["idle_share"] == pytest.approx(0.5)
    prog = trace_reduce.program(red, "serve_step")
    assert prog == {"seconds": pytest.approx(0.007 * n), "count": 2 * n}
    assert red["ops"][0] == ["fusion.1", pytest.approx(0.004 * n)]
    # Gaps: [0,1] under dispatch; [4,5] under no span; [6,9] under fetch.
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.fetch"] == pytest.approx(0.003 * n)
    assert gaps["bench.dispatch"] == pytest.approx(0.001 * n)
    assert gaps["host (no bench span)"] == pytest.approx(0.001 * n)


#: 40 ms of a traced chat run, measured on one TPU v5e chip: the start of a
#: prefill launch, its ops and async copies, with the host window span
#: cut to those 40 ms.
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_prefill_slice.json")


def test_recorded_chip_slice():
    with open(RECORDED) as f:
        events = [tuple(e) for e in json.load(f)]
    red = trace_reduce.reduce(events)
    assert red["devices"] == [DEV]
    assert red["window_s"] == pytest.approx(0.040)
    # Busy time against a 1-microsecond raster of the same op intervals.
    lo = min(s for p, ln, n, s, d in events if n == "bench.window")
    busy = set()
    for p, ln, n, s, d in events:
        if p == DEV and ln == "XLA Ops":
            a, b = max(s, lo) // 1000, min(s + d, lo + 40_000_000) // 1000
            busy.update(range(a, b))
    assert red["busy_s"] == pytest.approx(len(busy) * 1e-6, rel=1e-3)
    assert trace_reduce.program(red, "prefill_step")["count"] == 1
    assert not any(n.startswith("%while") for n, _ in red["ops"])

