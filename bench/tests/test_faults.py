"""A run with the timed path broken underneath must come out not correct.

Drives ``run.run_cell`` as a chip run does, minus the look for a chip, on
a tiny configuration on the CPU, once sound and once for each fault a
serving cell can have (``bench/faults.py``): a decode step that returns its
cache unchanged, half of the batch left out of the step, and a token
altered where it is produced.
"""
import pytest

from bench import common, faults
from bench import run as bench_run
from tiny import DEEPSEEK_LIKE, TRAFFIC

#: float32 at this size: a sound run's gaps are rounding (below 1e-5).
LIMITS = {"gap_max": {"limit": 1e-4}}
CONFIG = dict(DEEPSEEK_LIKE, torch_dtype="float32")
TRAFFIC_T = dict(TRAFFIC, output_len=12, check_requests=16)


def _run(mode, seed):
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}["deepseek-moe-16b.chat"]
    with faults.planted(mode, CONFIG, TRAFFIC_T):
        return bench_run.run_cell(bench, cell, CONFIG, TRAFFIC_T, LIMITS,
                                  seed, 1.0, False)


def test_sound_run_is_correct():
    line = _run("program", 2**32 + 3)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["info"]["window_compiles"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(fault):
    line = _run(fault, 2**32 + 3)
    assert not line["correct"], (fault, line["checks"])
