"""Needed operations and bytes against sums worked out by hand."""
import pytest

from bench.model_dims import Dims
from bench.work import decode_step, prefill

TOY = Dims(d_model=4, n_layers=2, n_dense=1, d_ff_dense=6, n_experts=4,
           top_k=2, d_ff_expert=3, n_shared=1, n_heads=2, n_kv_heads=1,
           head_dim=2, vocab=10, rope_theta=1e4, norm_eps=1e-6, tied=False,
           norm_topk=True, embedding_multiplier=1.0,
           attention_multiplier=2 ** -0.5, residual_multiplier=1.0,
           logits_scaling=1.0)


def test_decode_step_by_hand():
    # Per token: projections 2*4*(4+2*2) + 2*4*4 = 96 a layer, 192 in all;
    # dense FFN 6*4*6 = 144; MoE router 2*4*4 + experts 6*4*3*(2+1) = 248.
    # Two tokens: 2 * 584 = 1168; attention 4*2*2*(3+5)*2 = 256; head
    # 2*4*10*2 = 160.
    flops, nbytes = decode_step.needed(TOY, [3, 5])
    assert flops == pytest.approx(1168 + 256 + 160)
    # Two tokens hit 4*(1-0.5^2) = 3 experts.  Weights (bf16): attention
    # (4*8 + 4*4)*2 = 96, dense 3*4*6 = 72, experts 3*4*3*(3+1) = 144,
    # head 40 -> 2 * 352 = 704, router 4*4*4 = 64 bytes.  KV: 2*1*2*2*2 =
    # 16 bytes a position, 8 read and 2 written.
    assert nbytes == pytest.approx(704 + 64 + 16 * 8 + 16 * 2)


def test_prefill_by_hand():
    # One prompt of 3: 3 * 584 = 1752, causal attention 4*2*2*(1+2+3)*2 =
    # 192, head at the last position 80.
    flops, nbytes = prefill.needed(TOY, 1, 3)
    assert flops == pytest.approx(1752 + 192 + 80)
    # Three tokens hit 4*(1-0.5^3) = 3.5 experts: 3*4*3*4.5 = 162; weights
    # 2 * (96 + 72 + 162 + 40) = 740, router 64, KV written 3 * 16.
    assert nbytes == pytest.approx(740 + 64 + 48)


def test_routed_experts_count_top_k_not_buckets():
    dense_like = prefill.needed(TOY, 4, 16)[0]
    more_experts = prefill.needed(
        Dims(**{**TOY.__dict__, "n_experts": 64}), 4, 16)[0]
    # Only the router grows with the expert count (2*d per expert).
    assert more_experts - dense_like == pytest.approx(
        2 * 4 * 60 * 64 * TOY.n_moe)
