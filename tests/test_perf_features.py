"""Custom-VJP flash attention (``ModelConfig.flash_vjp``) must give the
gradients of the plain attention math, within fp tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import flash_attention
from repro.models.config import ModelConfig


def _naive(q, k, v, pos, sliding=0):
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    sco = jnp.einsum("bqngd,bknd->bnqgk", qg, k) * hd**-0.5
    mask = pos[:, None, :, None, None] >= pos[:, None, None, None, :]
    if sliding:
        mask &= (pos[:, None, :, None, None]
                 - pos[:, None, None, None, :]) < sliding
    sco = jnp.where(mask, sco, -1e30)
    p = jax.nn.softmax(sco, -1)
    return jnp.einsum("bnqgk,bknd->bqngd", p, v).reshape(b, s, hq, hd)


@pytest.mark.slow
@pytest.mark.parametrize("sw", [0, 8])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (8, 1)])
def test_flash_vjp_grads_match_naive(sw, hq, hkv):
    cfg = ModelConfig(
        name="t", n_layers=2, d_model=32, n_heads=hq, n_kv_heads=hkv,
        d_ff=64, vocab_size=128, attn_q_chunk=8, attn_kv_chunk=16,
        compute_dtype="float32", flash_vjp=True, sliding_window=sw,
    )
    b, s, hd = 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, hq, hd))
    k = jax.random.normal(ks[1], (b, s, hkv, hd))
    v = jax.random.normal(ks[2], (b, s, hkv, hd))
    tgt = jax.random.normal(ks[3], (b, s, hq, hd))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    g1 = jax.grad(
        lambda *a: jnp.sum((flash_attention(cfg, *a, pos, pos) - tgt) ** 2),
        (0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda *a: jnp.sum((_naive(*a, pos, sliding=sw) - tgt) ** 2), (0, 1, 2)
    )(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


@pytest.mark.slow
def test_flash_vjp_whole_model_grads():
    """End-to-end: training grads with flash_vjp == grads without."""
    from repro.models import init_params, loss_fn, random_batch
    base = dict(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=128, attn_q_chunk=8, attn_kv_chunk=8,
                compute_dtype="float32")
    cfg0 = ModelConfig(**base)
    cfg1 = ModelConfig(**base, flash_vjp=True)
    params = init_params(cfg0, jax.random.PRNGKey(0))
    batch = random_batch(cfg0, 2, 16, seed=1)
    g0 = jax.grad(lambda p: loss_fn(cfg0, p, batch)[0])(params)
    g1 = jax.grad(lambda p: loss_fn(cfg1, p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)

