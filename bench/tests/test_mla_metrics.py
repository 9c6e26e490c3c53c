"""The MLA work counts and readers, against sums worked out by hand on a
toy model and hand-made run contexts and trace records."""
import numpy as np
import pytest

from bench import common, mla_scopes
from bench.mla_dims import MlaDims
from bench.peaks import PEAKS
from bench.work import mla_attn, mla_step

TOY = MlaDims(d_model=4, n_layers=2, n_dense=1, d_ff_dense=6, n_experts=4,
              n_held=2, top_k=2, d_ff_expert=3, n_shared=1, n_heads=2,
              kv_lora_rank=2, qk_nope=2, qk_rope=2, v_head=2, vocab=10,
              rope_theta=1e4, yarn_factor=1.0, yarn_original_max=0,
              yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=1.0,
              yarn_mscale_all_dim=0.0, norm_eps=1e-6, tied=False,
              norm_topk=True)
KIND = "TPU v5 lite"
HBM = PEAKS[KIND]["hbm_bytes_per_s"]
DEV = "/device:TPU:0"
MS = 1_000_000


def test_latent_core_by_hand():
    # Rows of 2 + 2 = 4 values; contexts 3 and 5, two layers: scores over
    # the row (4) and the sum over the latent (2), 2 * 2 heads * 6 * 8 * 2
    # = 384 operations; 4 values * 2 bytes * 8 positions * 2 layers = 128.
    assert mla_attn.latent_core(TOY, [3, 5]) == (pytest.approx(384),
                                                 pytest.approx(128))


def test_mla_attention_by_hand():
    # Weights a layer: w_q 4*2*4 = 32, w_kv_a 4*4 = 16, w_kv_b 2*2*4 = 16,
    # w_o 2*2*4 = 16: 80 values, each applied once a token (absorbed), so
    # 2 tokens * 2 * 80 * 2 layers = 640 operations, plus the core's 384.
    # Bytes: weights 2 * 80 * 2 = 320, rows read 128, rows written
    # 4 * 2 * 2 tokens * 2 layers = 32.
    assert mla_attn.needed(TOY, [3, 5]) == (pytest.approx(1024),
                                            pytest.approx(480))


def test_mla_step_by_hand():
    # Attention 1024 / 480.  Per token: dense 2*72, head 2*40; per token
    # and MoE layer: router 2*4*4 = 32, routed top-k hits among the held
    # 2*2/4 = 1 expert plus 1 shared, 2*36*2 = 144.  Two tokens: 1024 +
    # 2*(144 + 80) + 2*(32 + 144) = 1824.  Bytes: two tokens hit
    # 2*(1 - 0.5^2) = 1.5 held experts; (72 + 40 + 36*2.5) * 2 = 404, the
    # f32 router 4*4*4 = 64.
    assert mla_step.held_hit(TOY, 2) == pytest.approx(1.5)
    assert mla_step.needed(TOY, [3, 5]) == (pytest.approx(1824),
                                            pytest.approx(480 + 404 + 64))


def test_held_experts_by_hand():
    # Each expert 3*4*3 = 36 values; per token top-k hits among the held
    # 2*2/4 = 1 plus 1 shared, 2*36*2 = 144 operations; two tokens 288.
    # Bytes: 1.5 held experts hit and 1 shared, 2 * 36 * 2.5 = 180.
    assert mla_step.experts(TOY, 2) == (pytest.approx(288),
                                        pytest.approx(180))


def _ctx(count=2, **extra):
    return {"dims": TOY, "device_kind": KIND, "batch": 2, "prompt_len": 2,
            "traced_decode_contexts": [np.array([3, 5])] * count,
            "trace": {"programs": {
                "jit_serve_step": {"seconds": 1.0, "count": count},
                "jit_prefill_step": {"seconds": 1.0, "count": 1}}},
            **extra}


def _read(name, ctx):
    return common.load_reader(name)(ctx)


def test_mla_step_roofline_by_hand():
    # Floor 948 bytes a step (1824 operations at peak take far less); two
    # launches in eight floors' time read 25%.
    ctx = _ctx()
    ctx["trace"]["programs"]["jit_serve_step"]["seconds"] = 8 * 948 / HBM
    assert _read("mla_step_roofline", ctx) == pytest.approx(25.0)


def test_mla_attn_roofline_by_hand():
    # Floor 480 bytes a step; two launches in four floors' time over the
    # attention stages and the scan's plumbing: 50%.
    t = 4 * 480 / HBM
    ctx = _ctx(stages={"serve_step": {
        "attn.qkv": t / 8, "attn.kv_write": t / 8, "attn.core": t / 2,
        "attn.out": t / 8, "layers": t / 16, "(none)": t / 16,
        "moe.experts": 1.0, "head": 1.0}})
    assert _read("mla_attn_roofline", ctx) == pytest.approx(50.0)


def test_mla_latent_roofline_by_hand():
    # Floor 128 bytes a step; two launches in 2.5 floors' time: 80%.
    t = 2.5 * 128 / HBM
    ctx = _ctx(mla_scopes={"serve_step": {"mla.latent": t,
                                          "mla.absorb": 1.0},
                           "prefill_step": {"mla.expand": 1.0}})
    assert _read("mla_latent_roofline", ctx) == pytest.approx(80.0)


def test_held_expert_ffn_roofline_by_hand():
    # Floor 180 bytes a step; two launches in four floors' time over the
    # expert stages: 50%.
    t = 4 * 180 / HBM
    ctx = _ctx(stages={"serve_step": {
        "moe.experts": 3 * t / 4, "moe.shared": t / 4, "attn.core": 1.0}})
    assert _read("held_expert_ffn_roofline", ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["mla_attn_roofline", "mla_latent_roofline",
                                  "held_expert_ffn_roofline"])
def test_readers_are_silent_without_scopes(name):
    # A program without the scopes (every op "(none)" or the scan): no
    # value, never 0 or 100.
    ctx = _ctx(stages={"serve_step": {"(none)": 2.0, "layers": 1.0}},
               mla_scopes={"serve_step": {}})
    assert _read(name, ctx) is None


def test_scopes_sum_by_path():
    """The scope of an op is a component of its path's first part; an op
    may be in no scope, and the loop op itself is not counted."""
    base = "jit(serve_step)/layers/while/body/closed_call"
    rec = [
        (DEV, "XLA Modules", "jit_serve_step(3)", 0, 10 * MS),
        (DEV, "XLA Ops", "%fusion.1 = ...", 0, 2 * MS,
         f"{base}/attn.core/mla.latent/dot_general"),
        (DEV, "XLA Ops", "%fusion.2 = ...", 2 * MS, 1 * MS,
         f"{base}/attn.core/mla.latent/reduce_max"),
        (DEV, "XLA Ops", "%fusion.3 = ...", 3 * MS, 1 * MS,
         f"{base}/attn.qkv/mla.absorb/dot_general;"
         f"{base}/attn.core/mla.latent/exp"),
        (DEV, "XLA Ops", "%fusion.4 = ...", 4 * MS, 3 * MS,
         f"{base}/moe.experts/dot_general"),
        (DEV, "XLA Ops", "%while.5 = ...", 0, 9 * MS, f"{base}"),
    ]
    got = mla_scopes.scopes(rec)
    assert got == {"serve_step": {"mla.latent": pytest.approx(3e-3),
                                  "mla.absorb": pytest.approx(1e-3)}}
