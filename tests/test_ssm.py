"""The Mamba-2 mixer (``repro.models.ssm``) and granite-4.0-h-small's
hybrid model, which serves it beside NoPE attention with GraniteMoE's
multipliers, on the CPU at small sizes in float32."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import ssm_scopes
from bench.drivers.serve_hybrid import program_config, program_params
from bench.hybrid_dims import dims
from bench.reference import hybrid_moe_lm as reference
from bench.weights import seed_key
from repro.configs import get_config, smoke_config
from repro.launch import steps
from repro.models import (LayerSpec, ModelConfig, Parallel, decode_step,
                          forward, init_params, prefill)
from repro.models import ssm
from repro.models.layers import SSM_SCOPES, STAGES

F32 = jnp.float32


def _recurrence(x, dt, a, bm, cm, state):
    """The SSM one position at a time, in float64 on the host."""
    x, dt, a, bm, cm = (np.asarray(v, np.float64) for v in (x, dt, a, bm, cm))
    st = np.array(state, np.float64)
    rep = x.shape[2] // bm.shape[2]
    ys = []
    for t in range(x.shape[1]):
        bh = np.repeat(bm[:, t], rep, axis=1)                 # (B, H, N)
        ch = np.repeat(cm[:, t], rep, axis=1)
        st = (np.exp(dt[:, t] * a)[..., None, None] * st
              + (dt[:, t, :, None] * x[:, t])[..., None] * bh[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", st, ch))
    return np.stack(ys, axis=1), st


def _ssm_inputs(b, s, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), F32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), F32) - 2.0)
    a = -jax.random.uniform(ks[2], (h,), F32, 1.0, 16.0)
    bm = jax.random.normal(ks[3], (b, s, g, n), F32)
    cm = jax.random.normal(ks[4], (b, s, g, n), F32)
    return x, dt, a, bm, cm


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("length", [6, 21])
@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_ssd_matches_recurrence(chunk, length, groups):
    """The chunked SSD gives the per-token recurrence's outputs and final
    state for chunks that do not divide the length (and one longer than
    it) and B and C shared by 1 or 2 groups."""
    x, dt, a, bm, cm = _ssm_inputs(2, length, 4, 3, groups, 5)
    with jax.default_matmul_precision("highest"):
        y, st = ssm.ssd(x, dt, a, bm, cm, chunk)
    want_y, want_st = _recurrence(x, dt, a, bm, cm, np.zeros((2, 4, 3, 5)))
    # Float32 against float64: a few ulps of outputs of order 10.
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), want_st, rtol=1e-5, atol=1e-4)
    # One decode step from the final state is the recurrence's next one.
    nxt = _ssm_inputs(2, 1, 4, 3, groups, 5, seed=1)
    got_st, got_y = ssm.ssm_step(st, nxt[0][:, 0], nxt[1][:, 0], a,
                                 nxt[3][:, 0], nxt[4][:, 0])
    want_y1, want_st1 = _recurrence(nxt[0], nxt[1], a, nxt[3], nxt[4],
                                    want_st)
    np.testing.assert_allclose(np.asarray(got_y), want_y1[:, 0], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_st), want_st1, rtol=1e-5,
                               atol=1e-4)


def test_prefill_then_decode_matches_forward():
    """On granite-4.0-h-small's smoke config (Mamba-2 beside attention,
    every multiplier set, no position embedding): prefill and then decode
    steps through the stacked conv, SSM and K/V caches give the full
    forward's logits at each position (dropless routing, so that the
    forward's and prefill's buckets drop nothing)."""
    cfg = smoke_config("granite-4.0-h-small")
    cfg = dataclasses.replace(cfg, capacity_factor=cfg.n_experts / cfg.top_k)
    assert cfg.nope and cfg.logits_scaling == 16.0
    assert {s.mixer for s in cfg.pattern} == {"mamba2", "attn"}
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(0))
    b, s, k = 2, 19, 5                  # 19 = 2 chunks of 8 and 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s + k), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        full, _ = forward(cfg, params, {"tokens": tokens})
        got, cache = prefill(cfg, params, {"tokens": tokens[:, :s]},
                             max_len=s + k)
        scale = float(jnp.abs(full).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, s - 1]),
                                   atol=1e-5 * scale)
        step = jax.jit(lambda c, t, p: decode_step(cfg, params, c, t, p))
        pos = jnp.full((b,), s, jnp.int32)
        for i in range(k):
            got, cache = step(cache, tokens[:, s + i:s + i + 1], pos)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(full[:, s + i]),
                                       atol=1e-5 * scale)
            pos = pos + 1
    assert cache["units"]["b0"]["ssm"].dtype == jnp.float32
    assert cache["units"]["b0"]["conv"].dtype == jnp.float32


#: A tiny GraniteMoeHybrid config file: two Mamba-2 groups, a period of 2
#: (so two scan units), 4 of 8 experts held, every multiplier, NoPE.
HYBRID_LIKE = {
    "name": "tiny-hybrid", "kind": "hybrid_moe_lm",
    "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention"] * 3,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_bias": False, "position_embedding_type": "nope",
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 4,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 4, "published": {"num_local_experts": 8},
    "num_experts_per_tok": 3, "vocab_size": 500, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "attention_multiplier": 0.0078125, "residual_multiplier": 0.22,
    "logits_scaling": 16, "torch_dtype": "float32",
}


def test_program_matches_the_plain_reference():
    """The program's prefill and decode steps on the benchmark's seeded
    weights give the plain reference's logits, and leave in the cache the
    reference's final SSM states (chunked SSD and one-step decode against
    the per-token recurrence; the multipliers and NoPE set on both
    sides)."""
    seed = 2**31 + 4321                   # larger than 32 signed bits
    d, cfg = dims(HYBRID_LIKE), program_config(HYBRID_LIKE)
    assert cfg.n_units == 2 and cfg.held_share and cfg.nope
    structs = jax.eval_shape(functools.partial(init_params, cfg),
                             jax.random.PRNGKey(0))
    params = program_params(structs, seed, d)
    prompt, total = 11, 16
    tokens = np.random.default_rng(3).integers(0, d.vocab, (2, total),
                                               dtype=np.int32)
    got = []
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(cfg, params, {"tokens": tokens[:, :prompt]},
                                max_len=total)
        got.append(logits)
        pos = jnp.full((2,), prompt, jnp.int32)
        for i in range(prompt, total):
            logits, cache = decode_step(cfg, params, cache,
                                        tokens[:, i:i + 1], pos)
            got.append(logits)
            pos = pos + 1
    got = np.stack([np.asarray(g) for g in got], axis=1)
    want, want_states = reference.logits(d, seed_key(seed), F32, tokens,
                                         prompt - 1)
    np.testing.assert_array_equal(got[..., d.vocab:], 0.0)
    # Float32 at full precision on both sides, summed in another order:
    # a few ulps of the logits' scale (~5e-3, after the division by 16).
    scale = np.abs(want).max()
    np.testing.assert_allclose(got[..., :d.vocab], want, atol=1e-5 * scale)
    # The served tokens are not the input echoed.
    assert (want.argmax(-1) != tokens[:, prompt - 1:]).mean() > 0.5
    # Mamba-2 layers 0 and 2: block b0 of units 0 and 1.
    states = np.asarray(cache["units"]["b0"]["ssm"]).swapaxes(0, 1)
    assert want_states.shape == states.shape == (2, 2, 8, 16, 16)
    np.testing.assert_allclose(states, want_states, rtol=1e-4,
                               atol=1e-5 * np.abs(want_states).max())


def test_config_sizes_and_counts():
    cfg = get_config("granite-4.0-h-small")
    assert [s.mixer for s in cfg.pattern].index("attn") == 5
    assert cfg.n_layers == 40 and cfg.n_units == 4
    assert cfg.d_inner == 8192 and cfg.mamba_conv_dim == 8448
    assert cfg.supports_long_context and not cfg.is_recurrent
    only_ssm = dataclasses.replace(cfg, pattern=(LayerSpec("mamba2", "moe"),))
    assert only_ssm.is_recurrent
    # One Mamba-2 mixer: in_proj 4096 x 16768, out_proj 8192 x 4096, the
    # convolution 8448 x (4 + 1), 3 x 128 scalars and the norm's 8192.
    mixer = 4096 * 16768 + 8192 * 4096 + 8448 * 5 + 3 * 128 + 8192
    dense = dataclasses.replace(cfg, n_layers=10, pattern=tuple(
        LayerSpec("mamba2", "none") for _ in range(10)))
    emb = cfg.padded_vocab * 4096
    assert dense.param_counts()["total"] == emb + 10 * mixer
    with pytest.raises(ValueError, match="mamba2"):
        ModelConfig(name="bad", n_layers=1, d_model=64, n_heads=4,
                    n_kv_heads=4, d_ff=64, vocab_size=128,
                    pattern=(LayerSpec("mamba2", "none"),), mamba_n_heads=6,
                    mamba_head_dim=16)


# --------------------------------------------------------------------- #
# Scopes in the compiled programs
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def programs() -> dict[str, str]:
    """Optimized HLO text of both serving programs of the smoke model."""
    cfg = smoke_config("granite-4.0-h-small")
    par, b, prompt, max_len = Parallel(), 2, 8, 12
    params = steps.param_structs(cfg)
    pre = jax.jit(steps.make_prefill_step(cfg, par, max_len)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((b, prompt), jnp.int32)})
    serve = jax.jit(steps.make_serve_step(cfg, par), donate_argnums=(1,)).lower(
        params, steps.cache_structs(cfg, b, max_len),
        jax.ShapeDtypeStruct((b, 1), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32), None)
    return {"prefill_step": pre.compile().as_text(),
            "serve_step": serve.compile().as_text()}


def test_bench_keeps_the_program_ssm_scopes():
    assert ssm_scopes.SSM_SCOPES == SSM_SCOPES
    assert not set(SSM_SCOPES) & set(STAGES)


@pytest.mark.parametrize("program,scopes", [
    ("prefill_step", {"ssm.proj", "ssm.conv", "ssm.ssd", "ssm.out"}),
    ("serve_step", {"ssm.proj", "ssm.conv", "ssm.state", "ssm.out"}),
])
def test_ssm_scopes_reach_the_op_metadata(programs, program, scopes):
    paths = re.findall(r'op_name="([^"]*)"', programs[program])
    assert any(p.startswith(f"jit({program})") for p in paths)
    found = {s for p in paths for s in SSM_SCOPES if s in p.split("/")}
    assert found == scopes
