"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mla_decode import blocks
from repro.kernels.ops import (decode_attention, expert_ffn_hit,
                               expert_ffn_pallas, gmm, mla_decode_attention)
from repro.kernels.ref import decode_attention_ref, gmm_ref
from repro.models.mla import _latent_core
from repro.models.moe import expert_ffn, hit_experts


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------- #
# moe_gmm
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "e,c,k,n",
    [
        (4, 128, 256, 128),      # aligned
        (8, 96, 64, 48),         # needs padding on every axis
        pytest.param(1, 8, 512, 128,       # single expert, tall K
                     marks=pytest.mark.slow),
        pytest.param(16, 256, 128, 384,    # many experts
                     marks=pytest.mark.slow),
        (3, 130, 100, 36),       # awkward primes
    ],
)
def test_gmm_matches_ref(e, c, k, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (e, c, k), dtype)
    w = jax.random.normal(ks[1], (e, k, n), dtype)
    out = gmm(x, w, block_c=64, block_n=128, block_k=64, interpret=True)
    ref = gmm_ref(x, w)
    assert out.shape == (e, c, n) and out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_gmm_block_shape_invariance():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 128, 64), jnp.float32)
    ref = gmm_ref(x, w)
    for bc, bn, bk in [(8, 128, 128), (64, 128, 32), (32, 128, 64)]:
        out = gmm(x, w, block_c=bc, block_n=bn, block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_expert_ffn_pallas_matches_moe_layer():
    from repro.models.moe import expert_ffn
    e, c, d, f = 4, 32, 64, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {
        "w_gate": jax.random.normal(ks[0], (e, d, f), jnp.float32) * 0.1,
        "w_up": jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.1,
        "w_down": jax.random.normal(ks[2], (e, f, d), jnp.float32) * 0.1,
    }
    xs = jax.random.normal(ks[3], (e, c, d), jnp.float32)
    out = expert_ffn_pallas(params, xs, jnp.float32, interpret=True)
    ref = expert_ffn(params, xs, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------- #
# decode_attn
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hkv,g,s,hd,bs",
    [
        pytest.param(2, 2, 4, 1024, 128, 512,    # aligned
                     marks=pytest.mark.slow),
        (1, 1, 1, 333, 64, 128),      # MQA, ragged S
        pytest.param(4, 8, 12, 256, 128, 256,    # mistral-like grouping
                     marks=pytest.mark.slow),
        (2, 2, 3, 96, 64, 64),        # tiny G (sublane padding)
    ],
)
def test_decode_attn_matches_ref(b, hkv, g, s, hd, bs, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), dtype)
    pos = jax.random.randint(ks[3], (b,), 0, s)
    out = decode_attention(q, k, v, pos, block_s=bs, interpret=True)
    ref = decode_attention_ref(q, k, v, pos)
    assert out.shape == q.shape and out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_decode_attn_respects_mask_strictly():
    """Garbage beyond pos must not leak into the output."""
    b, hkv, g, s, hd = 1, 1, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), jnp.float32)
    pos = jnp.array([17], jnp.int32)
    out1 = decode_attention(q, k, v, pos, block_s=64, interpret=True)
    # poison everything past pos
    k2 = k.at[:, :, 18:].set(1e9)
    v2 = v.at[:, :, 18:].set(-1e9)
    out2 = decode_attention(q, k2, v2, pos, block_s=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_decode_attn_matches_model_attention():
    """Kernel agrees with the model's jnp decode-attention core."""
    from repro.models.attention import NEG_INF  # noqa: F401  (same mask rule)
    b, hkv, g, s, hd = 2, 4, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), jnp.float32)
    pos = jnp.array([13, 63], jnp.int32)
    out = decode_attention(q, k, v, pos, block_s=32, interpret=True)
    ref = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------- #
# mla_decode
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype,units,unit,pos,block_b,block_s", [
    # Positions at 0, at a block's edges (bs - 1, bs) and at S - 1, two
    # sequences a block, so that each block stops at another row block;
    # the other units of the stack hold other rows.
    (jnp.float32, 3, 1, (0, 15, 16, 63), 2, 16),
    (jnp.float32, 4, 3, (63, 16, 15, 0), 2, 16),
    (jnp.bfloat16, 3, 2, (0, 15, 16, 63), 2, 16),
    # One sequence a block, blocks from the shapes.
    (jnp.float32, 3, 0, (40, 7, 63, 16), 1, None),
    (jnp.float32, 3, 1, (5, 33, 48, 20), None, None),
    # The dense first layer's cache: a stack of one.
    (jnp.float32, 1, 0, (0, 15, 16, 63), 2, 16),
    (jnp.bfloat16, 1, 0, (31, 32, 47, 62), 4, 16),
])
def test_mla_decode_attn_matches_latent_core(dtype, units, unit, pos,
                                             block_b, block_s):
    """The kernel over layer ``unit`` of a stack equals the model's jnp
    latent core over that layer's slice: f32 to rounding, bf16 within
    2e-2 (its unnormalised weights are rounded to bf16 before the value
    product, the core's normalised ones)."""
    b, h, s, w, scale = 4, 4, 64, 128, 0.2
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    q = jax.random.normal(ks[0], (b, h, w), dtype)
    stack = jax.random.normal(ks[1], (units, b, s, w), dtype)
    pos = jnp.asarray(pos, jnp.int32)
    out = mla_decode_attention(q, stack, jnp.int32(unit), pos, scale=scale,
                               block_b=block_b, block_s=block_s,
                               interpret=True)
    ref = _latent_core(q, stack[unit], pos, scale, dtype)
    assert out.shape == (b, h, w) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **tol(dtype))


@pytest.mark.parametrize("b,s,w,want", [
    (128, 2048, 640, (8, 512)),      # deepseek-v2-lite's decode step
    (4, 64, 128, (4, 64)),
    (6, 333, 128, (6, 333)),         # no 16-row divisor: whole rows
    (3, 4096, 4096, (1, 512)),       # wide rows: one sequence a block
])
def test_mla_decode_blocks_from_shapes(b, s, w, want):
    assert blocks(b, s, w, 2) == want


# --------------------------------------------------------------------- #
# moe_decode
# --------------------------------------------------------------------- #


HIT_CASES = {
    # 3 tokens x top-2 hit 3 of 8 experts: 6 slots, the last 3 padded
    "three_hit": ([[6, 1], [4, 6], [1, 4]], [1, 4, 6, 6, 6, 6]),
    # every copy to one expert: 5 padded slots
    "one_hit": ([[3, 3], [3, 3], [3, 3]], [3, 3, 3, 3, 3, 3]),
    # 6 distinct experts: no padded slot, expert 7 (the last) hit
    "six_hit": ([[7, 0], [2, 5], [1, 4]], [0, 1, 2, 4, 5, 7]),
}


@pytest.mark.parametrize("case", list(HIT_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_ffn_hit_matches_expert_ffn(dtype, case):
    """Layer ``unit`` = 2 of a 3-unit stack, 8 experts, buckets of 4: the
    kernel gives ``expert_ffn``'s output for the experts that 3 tokens x
    top-2 hit and zero for the others, whose buckets hold data it must
    not read.  f32 within 1e-5 relative; in bf16 no farther from the f32
    computation on the same inputs than ``expert_ffn`` in bf16 is (the
    kernel keeps its gate and up products in f32, where the einsum
    rounds them to bf16)."""
    idx, want_ids = HIT_CASES[case]
    u, e, c, d, f = 3, 8, 4, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    stacks = {"w_gate": jax.random.normal(ks[0], (u, e, d, f)) * 0.1,
              "w_up": jax.random.normal(ks[1], (u, e, d, f)) * 0.1,
              "w_down": jax.random.normal(ks[2], (u, e, f, d)) * 0.1}
    stacks = {k: w.astype(dtype) for k, w in stacks.items()}
    xs = jax.random.normal(ks[3], (e, c, d), dtype)
    ids, n_hit = hit_experts(jnp.asarray(idx, jnp.int32), e)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    assert int(n_hit) == len(set(want_ids))
    out = expert_ffn_hit(xs, stacks["w_gate"], stacks["w_up"],
                         stacks["w_down"], jnp.int32(2), ids, n_hit,
                         interpret=True)
    ref = expert_ffn({k: w[2] for k, w in stacks.items()}, xs, dtype)
    assert out.shape == (e, c, d) and out.dtype == dtype
    hit = np.isin(np.arange(e), want_ids)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(out[hit], ref[hit], rtol=1e-5,
                                   atol=1e-5 * np.abs(ref).max())
    else:
        exact = np.asarray(expert_ffn(
            {k: w[2].astype(jnp.float32) for k, w in stacks.items()},
            xs.astype(jnp.float32), jnp.float32))
        einsum_err = np.abs(ref[hit] - exact[hit]).max()
        assert np.abs(out[hit] - exact[hit]).max() <= einsum_err
    assert not out[~hit].any()
