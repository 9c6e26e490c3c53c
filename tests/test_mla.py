"""Latent attention (``repro.models.mla``) and the held share of a MoE
layer's experts, on the CPU at small sizes in float32."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import mla_scopes, trace_stages
from repro.configs import smoke_config
from repro.launch import steps
from repro.launch.serve import plan_and_apply_placement
from repro.models import LayerSpec, ModelConfig, Parallel, init_params
from repro.models import mla
from repro.models.attention import flash_attention
from repro.models.config import YarnScaling
from repro.models.layers import MLA_SCOPES, STAGES, apply_rope, apply_rope_pairs
from repro.models.moe import moe_apply_local, moe_init

F32 = jnp.float32

#: A dense first layer, then latent attention + MoE with a shared expert
#: and a held share of the experts; YaRN over a short original length, so
#: that positions past it are scaled.
CFG = ModelConfig(
    name="tiny-mla", n_layers=3, d_model=32, n_heads=4, n_kv_heads=4,
    d_ff=16, vocab_size=128, pattern=(LayerSpec("mla", "moe"),),
    n_experts=8, n_experts_held=4, top_k=2, n_shared_experts=1,
    d_ff_expert=16, first_layer_dense=True, first_dense_d_ff=48,
    capacity_factor=4.0, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8,
    rope_scaling=YarnScaling(factor=4.0, original_max_position_embeddings=8,
                             mscale=0.707, mscale_all_dim=0.707),
    compute_dtype="float32", attn_q_chunk=8, attn_kv_chunk=8)


def _mixer_params(cfg, key=0):
    return mla.mla_init(jax.random.PRNGKey(key), cfg, F32)


def test_absorbed_decode_matches_expanded_form():
    """Each position's absorbed decode over the latent cache equals the
    expanded causal attention at that position."""
    params = _mixer_params(CFG)
    b, s = 3, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, CFG.d_model), F32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.default_matmul_precision("highest"):
        want = mla.mla_forward(CFG, params, x, pos, F32)
        cache = mla.init_latent_cache(CFG, b, s, F32)
        got = []
        for i in range(s):
            y, cache = mla.mla_decode(CFG, params, x[:, i:i + 1],
                                      jnp.full((b,), i, jnp.int32), cache,
                                      F32)
            got.append(y)
    # Float32 at full precision; the two forms sum in another order.
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


def test_prefill_writes_the_rows_decode_would():
    params = _mixer_params(CFG)
    b, s = 2, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, CFG.d_model), F32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    _, pre = mla.mla_prefill(CFG, params, x, pos,
                             mla.init_latent_cache(CFG, b, s + 2, F32), F32)
    cache = mla.init_latent_cache(CFG, b, s + 2, F32)
    for i in range(s):
        _, cache = mla.mla_decode(CFG, params, x[:, i:i + 1],
                                  jnp.full((b,), i, jnp.int32), cache, F32)
    assert pre["latent"].shape == (b, s + 2, mla.cache_width(CFG))
    np.testing.assert_allclose(np.asarray(pre["latent"]),
                               np.asarray(cache["latent"]), atol=1e-6)


def test_flash_attention_with_narrow_values():
    """qk width 12, v width 8, an explicit scale: flash equals the plain
    softmax, and its custom VJP the plain gradients."""
    cfg = dataclasses.replace(CFG, attn_q_chunk=4, attn_kv_chunk=8)
    b, s, h = 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, s, h, 12), F32)
    k = jax.random.normal(ks[1], (b, s, h, 12), F32)
    v = jax.random.normal(ks[2], (b, s, h, 8), F32)
    tgt = jax.random.normal(ks[3], (b, s, h, 8), F32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def plain(q, k, v):
        sco = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.37
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sco, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def loss(fn):
        return lambda *a: jnp.sum((fn(*a) - tgt) ** 2)

    with jax.default_matmul_precision("highest"):
        want = plain(q, k, v)
        for c in (cfg, dataclasses.replace(cfg, flash_vjp=True)):
            def fl(q, k, v, c=c):
                return flash_attention(c, q, k, v, pos, pos, scale=0.37)
            np.testing.assert_allclose(np.asarray(fl(q, k, v)),
                                       np.asarray(want), atol=1e-5)
            for a, w in zip(jax.grad(loss(fl), (0, 1, 2))(q, k, v),
                            jax.grad(loss(plain), (0, 1, 2))(q, k, v)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                           atol=1e-4)


def test_pairs_rope_is_the_half_rope_of_deinterleaved_dims():
    """At a YaRN factor of 1 (no scaling), DeepSeek-V2's layout is the
    usual rotation applied to the even dims followed by the odd ones."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 3, 8), F32)
    pos = jnp.broadcast_to(jnp.arange(5)[None] * 7, (2, 5))
    deint = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    unscaled = YarnScaling(factor=1.0, original_max_position_embeddings=16)
    np.testing.assert_allclose(
        np.asarray(apply_rope_pairs(x, pos, 1e4, unscaled)),
        np.asarray(apply_rope(deint, pos, 1e4)), atol=1e-6)


# --------------------------------------------------------------------- #
# Held share of the experts
# --------------------------------------------------------------------- #


def test_held_shares_add_up_to_the_whole_layer():
    """A layer of 8 experts held 2 at a time by 4 chips: with the shared
    expert, which every chip computes alike, counted once, the 4 shares'
    outputs add up to the uncut layer's."""
    whole = dataclasses.replace(CFG, n_experts_held=0)
    held = dataclasses.replace(CFG, n_experts_held=2)
    params = moe_init(jax.random.PRNGKey(5), whole, F32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 10, CFG.d_model), F32)
    with jax.default_matmul_precision("highest"):
        want, _ = moe_apply_local(whole, params, x, F32)
        parts = []
        for g in range(4):
            # Chip g holds experts 2g, 2g+1: as the program's held share is
            # the first experts of the router, its columns are rotated so
            # that those come first (the routing itself does not change).
            share = dict(params,
                         router=jnp.roll(params["router"], -2 * g, axis=1),
                         **{n: params[n][2 * g:2 * g + 2]
                            for n in ("w_gate", "w_up", "w_down")})
            y, aux = moe_apply_local(held, share, x, F32)
            parts.append(y)
        sh = params["shared"]
        xt = x.reshape(-1, CFG.d_model)
        shared = ((jax.nn.silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"]))
                  @ sh["w_down"]).reshape(x.shape)
    got, want = np.asarray(sum(parts) - 3 * shared), np.asarray(want)
    scale = np.abs(want).max()
    # Float32 at full precision: the shares and the whole layer sum the
    # same products in another order, to a few ulps of the output's scale.
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    assert aux["expert_counts"].shape == (8,)
    # No single share is the whole layer.
    assert np.abs(np.asarray(parts[0]) - want).max() > 0.1 * scale


def test_held_share_stacks_and_router():
    params = init_params(CFG, jax.random.PRNGKey(0))
    ffn = params["units"]["b0"]["ffn"]
    assert ffn["router"].shape[-1] == 8
    assert ffn["w_gate"].shape[1] == 4 and ffn["w_down"].shape[1] == 4
    counts = CFG.param_counts()
    whole = dataclasses.replace(CFG, n_experts_held=0).param_counts()
    expert = 3 * CFG.d_model * CFG.d_ff_expert
    assert whole["total"] - counts["total"] == 4 * expert * 2   # 2 MoE layers
    assert whole["active"] - counts["active"] == pytest.approx(
        2 * (CFG.top_k - CFG.top_k * 4 / 8) * expert)


def test_placement_refuses_a_held_share():
    params = init_params(CFG, jax.random.PRNGKey(0))
    counts = np.ones((2, 8))
    with pytest.raises(ValueError, match="held share"):
        plan_and_apply_placement(CFG, params, counts, ep_ring=8)


def test_smoke_config_keeps_latent_attention():
    cfg = smoke_config("deepseek-v2-lite")
    assert cfg.pattern[0].mixer == "mla" and cfg.rope_scaling is not None
    assert cfg.latent_dim == cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert cfg.kv_lora_rank < 512


# --------------------------------------------------------------------- #
# Scopes in the compiled programs
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def programs() -> dict[str, str]:
    """Optimized HLO text of both serving programs of the tiny model."""
    par, b, prompt, max_len = Parallel(), 2, 8, 12
    params = steps.param_structs(CFG)
    prefill = jax.jit(steps.make_prefill_step(CFG, par, max_len)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((b, prompt), jnp.int32)})
    serve = jax.jit(steps.make_serve_step(CFG, par), donate_argnums=(1,)).lower(
        params, steps.cache_structs(CFG, b, max_len),
        jax.ShapeDtypeStruct((b, 1), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32), None)
    return {"prefill_step": prefill.compile().as_text(),
            "serve_step": serve.compile().as_text()}


def test_bench_keeps_the_program_mla_scopes():
    assert mla_scopes.MLA_SCOPES == MLA_SCOPES
    assert not set(MLA_SCOPES) & set(STAGES)


@pytest.mark.parametrize("program,scopes", [
    ("prefill_step", {"mla.expand"}),
    ("serve_step", {"mla.absorb", "mla.latent"}),
])
def test_mla_scopes_reach_the_op_metadata(programs, program, scopes):
    paths = re.findall(r'op_name="([^"]*)"', programs[program])
    assert any(p.startswith(f"jit({program})") for p in paths)
    found = {s for p in paths for s in MLA_SCOPES if s in p.split("/")}
    assert found == scopes
    # Each scope lies inside an attention stage, where the stage metrics
    # read it.
    for p in paths:
        parts = p.split(";", 1)[0].split("/")
        if scopes & set(parts):
            assert trace_stages.stage_of(p).startswith("attn."), p
