"""The hybrid Mamba-2 / attention cell's benchmark files on the CPU: its
dims against the published config keys, its work counts and readers against sums
worked out by hand on a toy model and hand-made run contexts and trace
records, and a run of a cell through its driver, which passes where the
program is sound and fails under the float8 control."""
import json
import os

import numpy as np
import pytest

from bench import common, ssm_scopes
from bench import run as bench_run
from bench.control_hybrid import planted
from bench.hybrid_dims import HybridDims, dims
from bench.peaks import PEAKS
from bench.work import hybrid_step
from tiny_hybrid import HYBRID_LIKE

TOY = HybridDims(d_model=4, n_layers=3,
                 layer_types=("mamba", "attention", "mamba"), n_experts=4,
                 n_held=2, top_k=2, d_ff_expert=3, n_shared=1, n_heads=2,
                 n_kv_heads=1, head_dim=2, ssm_heads=2, ssm_head_dim=4,
                 ssm_state=2, ssm_groups=1, conv_kernel=4, ssm_chunk=4,
                 vocab=10, norm_eps=1e-5, tied=True,
                 embedding_multiplier=12.0, attention_multiplier=0.5,
                 residual_multiplier=0.22, logits_scaling=16.0)
KIND = "TPU v5 lite"
HBM = PEAKS[KIND]["hbm_bytes_per_s"]
DEV = "/device:TPU:0"
MS = 1_000_000


def test_dims_read_the_published_keys():
    with open(os.path.join(common.ROOT, "bench", "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    d = dims(config)
    assert (d.d_model, d.n_layers, d.n_mamba, d.n_attn) == (4096, 10, 9, 1)
    assert d.layer_types.index("attention") == 5
    assert (d.ssm_heads, d.ssm_head_dim, d.ssm_state, d.ssm_groups) == (
        128, 64, 128, 1)
    assert (d.conv_kernel, d.ssm_chunk, d.d_inner, d.conv_dim) == (
        4, 256, 8192, 8448)
    assert (d.n_heads, d.n_kv_heads, d.head_dim) == (32, 8, 128)
    assert (d.n_experts, d.n_held, d.top_k) == (72, 9, 10)
    assert (d.d_ff_expert, d.n_shared, d.vocab, d.tied) == (768, 2, 100352,
                                                            True)
    assert (d.embedding_multiplier, d.residual_multiplier,
            d.attention_multiplier, d.logits_scaling) == (12, 0.22, 1 / 128,
                                                          16)
    assert set(config["reduced"]) == {"num_hidden_layers",
                                      "num_local_experts"}
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_local_experts": 72}
    with pytest.raises(ValueError, match="nope"):
        dims({**config, "position_embedding_type": "rope"})


def test_ssm_mixers_by_hand():
    # d_inner 8, conv_dim 8 + 2*2 = 12, heads 2.  Weights: in_proj 4*(8 +
    # 12 + 2) = 88 and out_proj 8*4 = 32 values, conv 5*12 = 60, all at 2
    # bytes, and 3*2 + 8 = 14 f32 values: 2*180 + 4*14 = 416 bytes.  State
    # a sequence: 2*4*2 + 3*12 = 52 values, read and written: 2*4*52 = 416
    # bytes.  Two layers, two sequences: 2 * (416 + 2*416) = 2496.
    # Operations a token: 2*88 + 2*32 + 2*4*12 + 5*16 = 416; two tokens,
    # two layers: 1664.
    assert hybrid_step.ssm_mixers(TOY, 2) == (pytest.approx(1664),
                                              pytest.approx(2496))


def test_attention_by_hand():
    # q 4, kv 2 wide: weights 4*8 + 4*4 = 48 values.  Contexts 3 and 5:
    # 2*48*2 + 4*4*8 = 320 operations; bytes 2*(48 + 2*2*(8 + 2)) = 176.
    assert hybrid_step.attention(TOY, [3, 5]) == (pytest.approx(320),
                                                  pytest.approx(176))


def test_hybrid_step_by_hand():
    # Mixers 1664 / 2496 and attention 320 / 176.  Per token the head
    # 2*40 = 80 and, per layer, the router 2*16 = 32 operations; experts
    # (``mla_step.experts``): per token and layer 1 routed + 1 shared of 36
    # values, 144 operations, so 2 tokens x 3 layers 864; bytes 1.5 held
    # experts hit + 1 shared: 2*36*2.5*3 = 540.  Operations 1664 + 320 +
    # 864 + 2*80 + 2*3*32 = 3200; bytes 2496 + 176 + 540 + 2*40 + 4*16*3
    # = 3484.
    assert hybrid_step.needed(TOY, [3, 5]) == (pytest.approx(3200),
                                               pytest.approx(3484))


def _ctx(count=2, **extra):
    return {"dims": TOY, "device_kind": KIND, "batch": 2, "prompt_len": 2,
            "traced_decode_contexts": [np.array([3, 5])] * count,
            "trace": {"programs": {
                "jit_serve_step": {"seconds": 1.0, "count": count},
                "jit_prefill_step": {"seconds": 1.0, "count": 1}}},
            **extra}


def _read(name, ctx):
    return common.load_reader(name)(ctx)


def test_hybrid_step_roofline_by_hand():
    # Floor 3484 bytes a step; two launches in eight floors' time: 25%.
    ctx = _ctx()
    ctx["trace"]["programs"]["jit_serve_step"]["seconds"] = 8 * 3484 / HBM
    assert _read("hybrid_step_roofline", ctx) == pytest.approx(25.0)


def test_ssm_decode_roofline_by_hand():
    # Floor 2496 bytes a step; two launches in 2.5 floors' time over every
    # SSM scope: 80%.
    t = 2.5 * 2496 / HBM
    ctx = _ctx(ssm_scopes={"serve_step": {"ssm.proj": t / 4,
                                          "ssm.state": t / 2,
                                          "ssm.out": t / 4},
                           "prefill_step": {"ssm.ssd": 1.0}})
    assert _read("ssm_decode_roofline", ctx) == pytest.approx(80.0)


@pytest.mark.parametrize("name", ["hybrid_step_roofline",
                                  "ssm_decode_roofline"])
def test_readers_are_silent_without_scopes(name):
    # A run whose trace has no decode program (as where the cell cannot
    # run), or whose program has no SSM scopes: no value, never 0 or 100.
    ctx = _ctx(ssm_scopes={"prefill_step": {"ssm.ssd": 1.0}})
    ctx["trace"]["programs"].pop("jit_serve_step")
    assert _read(name, ctx) is None
    if name == "ssm_decode_roofline":
        assert _read(name, _ctx(ssm_scopes={"serve_step": {}})) is None


def test_scopes_sum_by_path():
    """The scope of an op is a component of its path's first part; an op
    may be in no scope, and the loop op itself is not counted."""
    base = "jit(serve_step)/layers/while/body/closed_call"
    rec = [
        (DEV, "XLA Modules", "jit_serve_step(3)", 0, 10 * MS),
        (DEV, "XLA Ops", "%fusion.1 = ...", 0, 2 * MS,
         f"{base}/ssm.state/add"),
        (DEV, "XLA Ops", "%fusion.2 = ...", 2 * MS, 1 * MS,
         f"{base}/ssm.proj/dot_general;{base}/ssm.conv/mul"),
        (DEV, "XLA Ops", "%fusion.3 = ...", 3 * MS, 3 * MS,
         f"{base}/moe.experts/dot_general"),
        (DEV, "XLA Ops", "%while.4 = ...", 0, 9 * MS, f"{base}"),
    ]
    got = ssm_scopes.scopes(rec)
    assert got == {"serve_step": {"ssm.state": pytest.approx(2e-3),
                                  "ssm.proj": pytest.approx(1e-3)}}


TRAFFIC = {
    "driver": "serve_hybrid", "loop": "closed", "batch": 4, "prompt_len": 9,
    "output_len": 20, "check_requests": 4,
    "trace": {"batch_index": 0,
              "slices": [{"prefill": True, "decode_steps": 3}]},
}
#: float32 program at this size: rounding only (as ``test_control.py``).
LIMITS = {"gap_max": {"limit": 1e-6}, "state_err": {"limit": 1e-4}}


@pytest.mark.parametrize("seed", [31, 2**31 + 32])
def test_float8_control_fails_where_program_passes(seed):
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["traffic"] == "hybrid-reasoning-b128")
    lines = {}
    for mode in ("program", "control"):
        # The window closes at once: only the first batch is served (it
        # runs to its end).
        with planted(mode):
            lines[mode] = bench_run.run_cell(bench, cell, HYBRID_LIKE,
                                             TRAFFIC, LIMITS, seed, 1e-3,
                                             False)
    assert lines["program"]["correct"], lines["program"]["checks"]
    assert lines["program"]["info"]["window_compiles"] == 0
    # Each check fails under the control on its own.
    assert all(c["value"] > c["limit"]
               for c in lines["control"]["checks"].values()), lines["control"]
    assert not lines["control"]["correct"]
