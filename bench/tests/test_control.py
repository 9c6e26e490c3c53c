"""The control: the reference in float8 in the program's place must fail
the comparison that sound runs pass.  At the cell's own size this is read
on the chip by ``bench/control.py``; here at a size a test run holds."""
import pytest

from bench import common, faults
from bench import run as bench_run
from tiny import DEEPSEEK_LIKE, GRANITE_LIKE, TRAFFIC

#: float32 program at this size: rounding only.
LIMITS = {"gap_max": {"limit": 1e-4}}


@pytest.mark.parametrize("config", [DEEPSEEK_LIKE, GRANITE_LIKE],
                         ids=["deepseek-like", "granite-like"])
@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_float8_control_fails_where_program_passes(config, seed):
    config = dict(config, torch_dtype="float32")
    traffic = dict(TRAFFIC, batch=8, prompt_len=16, output_len=32,
                   check_requests=8)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = bench["workloads"][0]
    lines = {}
    for mode in ("program", "control"):
        with faults.planted(mode, config, traffic):
            lines[mode] = bench_run.run_cell(bench, cell, config, traffic,
                                             LIMITS, seed, 1.0, False)
    assert lines["program"]["correct"], lines["program"]["checks"]
    assert not lines["control"]["correct"], lines["control"]["checks"]
