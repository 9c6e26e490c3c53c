"""The MLA serving driver on the CPU at a tiny size: its decode loop, which
keeps ``IN_FLIGHT`` steps dispatched ahead of the token it fetches,
serves what ``serve.Server.batch``'s serial loop serves, and a run of a
cell through it passes where the program is sound and fails under the
float8 control."""
import time

import numpy as np
import pytest

from bench import common
from bench import run as bench_run
from bench.control_mla import planted
from bench.drivers import serve, serve_mla
from tiny_mla import MLA_LIKE

TRAFFIC = {
    "driver": "serve_mla", "loop": "closed", "batch": 4, "prompt_len": 8,
    "output_len": 24, "check_requests": 4,
    "trace": {"batch_index": 0,
              "slices": [{"prefill": True, "decode_steps": 3},
                         {"from_step": 6, "decode_steps": 2}]},
}

#: float32 program at this size: rounding only (as ``test_control.py``).
LIMITS = {"gap_max": {"limit": 1e-4}}


@pytest.fixture(scope="module")
def server():
    return serve_mla.Server(MLA_LIKE, TRAFFIC, 2**31 + 77)


def test_overlapped_loop_serves_the_serial_loops_tokens(server, tmp_path):
    prompt = server.draw(np.random.default_rng(5))
    serial = serve.Server.batch(server, prompt, np.inf)
    tracer = serve.Tracer(TRAFFIC["trace"]["slices"], str(tmp_path))
    for tr in (None, tracer):
        got = server.batch(prompt, np.inf, tr)
        np.testing.assert_array_equal(got["tokens"], serial["tokens"])
        assert got["tokens"].shape == (4, 24)
        assert len(got["times"]) == 24
        assert np.all(np.diff(got["times"]) >= 0)
        assert got["start"] <= got["times"][0]
    # Both slices were profiled, each in a session of its own.
    assert len(tracer.dirs) == 2 and not tracer.on


def test_overlapped_loop_starts_no_step_after_the_deadline(server):
    got = server.batch(server.draw(np.random.default_rng(6)),
                       deadline=-np.inf)
    assert got["tokens"].shape == (4, 1) and len(got["times"]) == 1


def test_overlapped_loop_keeps_in_flight_steps_ahead_of_its_fetches(
        server, monkeypatch):
    # Fewer steps in flight than the tiny batch's 23 decode steps, so that
    # the loop reaches the steady state where each dispatch waits a fetch.
    monkeypatch.setattr(serve_mla, "IN_FLIGHT", 5)
    dispatched = []
    step = server.step

    def counted(*args):
        dispatched.append(time.perf_counter())
        return step(*args)

    server.step = counted
    try:
        got = server.batch(server.draw(np.random.default_rng(7)), np.inf)
    finally:
        server.step = step
    n = TRAFFIC["output_len"]
    assert len(dispatched) == n - 1
    # Token s + 1 (decode step s) is fetched once step s + IN_FLIGHT has
    # been dispatched, and before the step after it.
    ahead = [int(np.searchsorted(dispatched, t)) for t in got["times"][1:]]
    assert ahead == [min(s + serve_mla.IN_FLIGHT + 1, n - 1)
                     for s in range(n - 1)]


@pytest.mark.parametrize("seed", [21, 2**31 + 22])
def test_float8_control_fails_where_program_passes(seed):
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["traffic"] == "reasoning-b128")
    lines = {}
    for mode in ("program", "control"):
        # The window closes at once: only the first batch is served (it
        # runs to its end), so the requests compared do not hang on the
        # speed of the host.
        with planted(mode):
            lines[mode] = bench_run.run_cell(bench, cell, MLA_LIKE, TRAFFIC,
                                             LIMITS, seed, 1e-3, False)
    assert lines["program"]["correct"], lines["program"]["checks"]
    assert lines["program"]["info"]["window_compiles"] == 0
    assert not lines["control"]["correct"], lines["control"]["checks"]
