"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ASSIGNED, SHAPES, get_config, list_archs,
                           shape_applies, smoke_config)
from repro.models import (decode_step, forward, init_params, loss_fn,
                          prefill, random_batch)

ALL_ARCHS = list_archs()


def test_registry_complete():
    assert len(ASSIGNED) == 10
    # + the paper's llama-moe-3.5b, deepseek-v2-lite (latent attention) and
    # granite-4.0-h-small (Mamba-2 beside attention)
    assert len(ALL_ARCHS) == 13


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_is_published_spec(arch):
    cfg = get_config(arch)
    # divisibility sanity on the published numbers
    assert cfg.n_heads % cfg.n_kv_heads == 0
    assert cfg.n_layers % len(cfg.pattern) == 0
    assert cfg.padded_vocab >= cfg.vocab_size
    counts = cfg.param_counts()
    assert counts["active"] <= counts["total"]


def test_param_counts_match_public_sizes():
    """Total params within tolerance of the published model sizes."""
    expect = {
        "granite-moe-3b-a800m": (3.3e9, 0.25),
        "deepseek-moe-16b": (16.4e9, 0.15),
        "jamba-1.5-large-398b": (398e9, 0.15),
        "llava-next-mistral-7b": (7.2e9, 0.15),
        "qwen2.5-3b": (3.1e9, 0.20),
        "minicpm-2b": (2.7e9, 0.25),
        "smollm-135m": (135e6, 0.20),
        "mistral-large-123b": (123e9, 0.10),
        "xlstm-350m": (350e6, 0.35),
        "llama-moe-3.5b": (6.7e9, 0.15),
        "deepseek-v2-lite": (15.7e9, 0.05),
        "granite-4.0-h-small": (32e9, 0.05),
    }
    for arch, (target, tol) in expect.items():
        total = get_config(arch).param_counts()["total"]
        assert abs(total - target) / target < tol, (arch, total, target)


def test_active_params():
    # MoE actives: granite ~800M-class, deepseek ~2.8-3B, llama-moe ~3.5B
    assert get_config("granite-moe-3b-a800m").param_counts()["active"] < 1.4e9
    a = get_config("deepseek-moe-16b").param_counts()["active"]
    assert 2.0e9 < a < 4.5e9
    a = get_config("llama-moe-3.5b").param_counts()["active"]
    assert 3.0e9 < a < 4.2e9
    # deepseek-v2-lite: 2.4B activated, with embedding and head 2.66B
    a = get_config("deepseek-v2-lite").param_counts()["active"]
    assert 2.4e9 < a < 2.8e9
    # granite-4.0-h-small: 32B-A9B
    a = get_config("granite-4.0-h-small").param_counts()["active"]
    assert 8.5e9 < a < 9.5e9


@pytest.mark.slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = random_batch(cfg, batch=2, seq_len=32, seed=1)

    logits, aux = forward(cfg, params, batch)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    assert bool(jnp.isfinite(aux)), arch

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch), has_aux=True
    )(params)
    assert bool(jnp.isfinite(loss)), arch
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch
    # one SGD step keeps everything finite
    new_params = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
    loss2, _ = loss_fn(cfg, new_params, batch)
    assert bool(jnp.isfinite(loss2)), arch


@pytest.mark.slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_decode_path(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = random_batch(cfg, batch=b, seq_len=s, seed=2)
    prompt = {k: v for k, v in batch.items() if k != "labels"}
    logits, cache = prefill(cfg, params, prompt, max_len=s + 4)
    assert logits.shape == (b, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    pos = jnp.full((b,), s, jnp.int32)
    if cfg.frontend == "audio":
        lg, _ = decode_step(cfg, params, cache, None, pos,
                            embeds=jnp.ones((b, 1, cfg.d_model), jnp.float32))
    else:
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        lg, _ = decode_step(cfg, params, cache, tok, pos)
    assert lg.shape == (b, cfg.padded_vocab)
    assert bool(jnp.isfinite(lg).all()), arch


def test_shape_matrix_counts():
    """40 assigned cells; long_500k runs only for jamba + xlstm."""
    total, runnable = 0, 0
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            total += 1
            ok, _ = shape_applies(cfg, shape)
            runnable += ok
    assert total == 40
    assert runnable == 32          # 8 full-attention archs skip long_500k
    for arch in ("jamba-1.5-large-398b", "xlstm-350m"):
        ok, _ = shape_applies(get_config(arch), SHAPES["long_500k"])
        assert ok
