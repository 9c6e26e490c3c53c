"""The stage metrics and the work they count, against sums worked out by
hand on a toy model and a hand-made run context."""
import numpy as np
import pytest

from bench import common
from bench.model_dims import Dims
from bench.peaks import PEAKS
from bench.work import decode_attn, expert_ffn

TOY = Dims(d_model=4, n_layers=2, n_dense=1, d_ff_dense=6, n_experts=4,
           top_k=2, d_ff_expert=3, n_shared=1, n_heads=2, n_kv_heads=1,
           head_dim=2, vocab=10, rope_theta=1e4, norm_eps=1e-6, tied=False,
           norm_topk=True, embedding_multiplier=1.0,
           attention_multiplier=2 ** -0.5, residual_multiplier=1.0,
           logits_scaling=1.0)
KIND = "TPU v5 lite"
HBM, FLOPS = PEAKS[KIND]["hbm_bytes_per_s"], PEAKS[KIND]["flops"]


def test_expert_ffn_by_hand():
    # One MoE layer; an expert is 3 matrices of 4 x 3 = 36 values.  Two
    # tokens: 2 * 36 * (2 routed + 1 shared) * 2 = 432 operations; they
    # hit 4 * (1 - 0.5^2) = 3 experts, so 36 * (3 + 1) values, 288 bytes.
    assert expert_ffn.needed(TOY, 2) == (pytest.approx(432),
                                         pytest.approx(288))
    # Three tokens: 648 operations; 3.5 experts hit: 36 * 4.5 * 2 = 324.
    assert expert_ffn.needed(TOY, 3) == (pytest.approx(648),
                                         pytest.approx(324))


def test_decode_attention_by_hand():
    # Projections 2*4*(4 + 2*2) + 2*4*4 = 96 a token and layer; contexts 3
    # and 5: (2 * 96 + 4*2*2*8) * 2 layers = 640 operations.  Weights
    # (4*8 + 4*4) * 2 layers in bf16 = 192 bytes; KV 2*1*2*2*2 = 16 bytes
    # a position: 8 read, 2 written.
    assert decode_attn.needed(TOY, [3, 5]) == (pytest.approx(640),
                                               pytest.approx(192 + 128 + 32))


def _ctx(stages, count=2):
    return {"dims": TOY, "device_kind": KIND, "batch": 1, "prompt_len": 3,
            "traced_decode_contexts": [np.array([3, 5])] * count,
            "traced_prefill": True,
            "trace": {"programs": {
                "jit_serve_step": {"seconds": 1.0, "count": count},
                "jit_prefill_step": {"seconds": 1.0, "count": 1}}},
            "stages": stages}


def _read(name, ctx):
    return common.load_reader(name)(ctx)


def test_expert_ffn_roofline_by_hand():
    # Each step's floor is 288 bytes at the HBM's rate (432 operations at
    # peak take far less); two launches in four floors' time read 50%.
    t = 4 * 288 / HBM
    ctx = _ctx({"serve_step": {"moe.experts": 0.75 * t, "moe.shared": 0.25 * t,
                               "attn.core": 1.0}})
    assert _read("expert_ffn_roofline", ctx) == pytest.approx(50.0)


def test_decode_attn_roofline_by_hand():
    # Floor 352 bytes a step; two launches in eight floors' time, the
    # attention stages and the scan's plumbing together: 25%.
    t = 8 * 352 / HBM
    ctx = _ctx({"serve_step": {"attn.qkv": t / 8, "attn.kv_write": t / 8,
                               "attn.core": t / 4, "attn.out": t / 8,
                               "layers": t / 4, "(none)": t / 8,
                               "moe.experts": 1.0, "head": 1.0}})
    assert _read("decode_attn_roofline", ctx) == pytest.approx(25.0)


def test_decode_scan_share_by_hand():
    ctx = _ctx({"serve_step": {"moe.experts": 3.0, "attn.core": 5.0,
                               "layers": 1.5, "(none)": 0.5},
                "prefill_step": {"(none)": 100.0}})
    assert _read("decode_scan_share", ctx) == pytest.approx(20.0)


def test_prefill_expert_mfu_by_hand():
    # One prompt of three tokens: 648 operations, once, in four times
    # their time at peak: 25%.
    t = 4 * 648 / FLOPS
    ctx = _ctx({"prefill_step": {"moe.experts": t / 2, "moe.shared": t / 2,
                                 "layers": 9.0}})
    assert _read("prefill_expert_mfu", ctx) == pytest.approx(25.0)


READERS = ("expert_ffn_roofline", "decode_attn_roofline", "decode_scan_share",
           "prefill_expert_mfu")


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("stages", [
    {},
    {"serve_step": {"(none)": 2.0, "layers": 1.0},
     "prefill_step": {"(none)": 3.0}},
], ids=["no-paths", "no-scopes"])
def test_readers_are_silent_without_model_stages(name, stages):
    # No stage map (five-field records), or a program without the scopes
    # (every op "(none)" or the scan): no value, never 0 or 100.
    assert _read(name, _ctx(stages)) is None
