"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) and its
latent cache.

Parameters, with no query compression (``q_lora_rank`` null): ``w_q``
(d, H (nope + rope)), ``w_kv_a`` (d, r + rope) with ``kv_norm`` over the
r-wide latent, ``w_kv_b`` (r, H (nope + v)) and ``w_o`` (H v, d).  Each
head's columns are contiguous, nope before rope and nope before v, as the
published checkpoints lay them out.

Two paths compute the same attention:

- train and prefill, expanded: K and V per head from the latent, the one
  rotary key appended to every head's K, and causal flash attention with
  qk width nope + rope and v width v;
- decode, absorbed: the query's nope part goes into latent space through
  W_uk (the K half of ``w_kv_b``) and is scored against the cached latents
  directly; the weighted sum of latents comes out through W_uv (the V
  half) and ``w_o``.  K and V are never expanded.

The cache holds one row per token and layer, shared by every head: the
normed latent and the rotated key, r + rope wide (576 for DeepSeek-V2),
padded with zeros to a multiple of the TPU's 128 lanes (640).  It is one
array, so a decode step writes one row per sequence
(``attention._write_rows``), in place in the layer scan's carried stack.
The compiler keeps an array whose minor dimension is short of a lane
multiple with another dimension minor where that one needs no padding: an
unpadded 576-wide stack is kept with the positions minor, so that every
row written is a column across 72 tiles, and so is a 64-wide array of the
rotary keys alone.  Timed at the cell's shapes on a v5e (128 sequences of
2,048 positions, 10 layers), the padded rows gave 18.68 ms a decode step,
the unpadded 19.56 ms, and a 512 + 64 split of the row into two arrays
18.18 ms in some runs and 10% more in others, where the padded rows were
the same in every run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import NEG_INF, _write_rows, flash_attention
from .config import ModelConfig
from .layers import (apply_rope_pairs, normal_init, out_proj_init,
                     rmsnorm_init, yarn_mscale)

#: The TPU's lane width, to which a latent-cache row is padded.
LANES = 128


def mla_init(key, cfg: ModelConfig, dtype) -> dict:
    kq, ka, kb, ko = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "w_q": normal_init(
            kq, (d, h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)), dtype),
        "w_kv_a": normal_init(ka, (d, cfg.latent_dim), dtype),
        "kv_norm": rmsnorm_init(r, jnp.float32),
        "w_kv_b": normal_init(
            kb, (r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dtype),
        "w_o": out_proj_init(ko, (h * cfg.v_head_dim, d), dtype, cfg.n_layers),
    }


def cache_width(cfg: ModelConfig) -> int:
    """Width of a latent-cache row: ``latent_dim`` padded to the lanes."""
    return -(-cfg.latent_dim // LANES) * LANES


def init_latent_cache(cfg: ModelConfig, batch: int, max_len: int,
                      dtype) -> dict:
    return {"latent": jnp.zeros((batch, max_len, cache_width(cfg)), dtype)}


def _pad_row(x, cfg: ModelConfig):
    """Zero columns that pad a latent-width ``x`` to ``cache_width``."""
    pad = cache_width(cfg) - cfg.latent_dim
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-0.5, times YaRN's mscale squared where the config
    scales the rotary embedding (DeepSeek-V2's ``softmax_scale``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = cfg.rope_scaling
    if sc.mscale_all_dim:
        m = yarn_mscale(sc.factor, sc.mscale_all_dim)
        scale *= m * m
    return scale


@jax.named_scope("attn.qkv")
def _project(cfg: ModelConfig, params, x, positions, compute_dtype):
    """x (B, S, d) -> q_nope (B, S, H, nope), rotated q_rope (B, S, H, rope)
    and the latent-cache rows (B, S, ``cache_width``): normed latent,
    rotated key, zeros."""
    b, s, _ = x.shape
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    x = x.astype(compute_dtype)
    q = (x @ params["w_q"].astype(compute_dtype)).reshape(b, s, h, -1)
    q_rope = apply_rope_pairs(q[..., nope:], positions, cfg.rope_theta,
                              cfg.rope_scaling)
    kv = x @ params["w_kv_a"].astype(compute_dtype)
    c = kv[..., :r].astype(jnp.float32)
    c = c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True)
                          + cfg.norm_eps) * params["kv_norm"]["scale"]
    k_rope = apply_rope_pairs(kv[..., None, r:], positions, cfg.rope_theta,
                              cfg.rope_scaling)[..., 0, :]
    rows = jnp.concatenate([c.astype(compute_dtype), k_rope], axis=-1)
    return q[..., :nope], q_rope, _pad_row(rows, cfg)


def _attend_expanded(cfg: ModelConfig, params, q_nope, q_rope, rows,
                     positions, compute_dtype):
    """Causal attention with K and V expanded from the latent rows; returns
    the output projection (B, S, d)."""
    b, s = rows.shape[:2]
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with jax.named_scope("attn.qkv"), jax.named_scope("mla.expand"):
        kv = (rows[..., :r] @ params["w_kv_b"].astype(compute_dtype)
              ).reshape(b, s, h, -1)
        k_rope = jnp.broadcast_to(rows[..., None, r:cfg.latent_dim],
                                  (b, s, h, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        v = kv[..., nope:]
    out = flash_attention(cfg, q, k, v, positions, positions,
                          scale=softmax_scale(cfg))
    with jax.named_scope("attn.out"):
        return out.reshape(b, s, h * cfg.v_head_dim) \
            @ params["w_o"].astype(compute_dtype)


def mla_forward(cfg: ModelConfig, params: dict, x: jnp.ndarray,
                positions: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    """Training self-attention, expanded form (no cache)."""
    q_nope, q_rope, rows = _project(cfg, params, x, positions, compute_dtype)
    return _attend_expanded(cfg, params, q_nope, q_rope, rows, positions,
                            compute_dtype)


def mla_prefill(cfg: ModelConfig, params: dict, x: jnp.ndarray,
                positions: jnp.ndarray, cache: dict,
                compute_dtype) -> tuple[jnp.ndarray, dict]:
    """Prefill: expanded attention, and the latent rows written at [0, S)."""
    q_nope, q_rope, rows = _project(cfg, params, x, positions, compute_dtype)
    y = _attend_expanded(cfg, params, q_nope, q_rope, rows, positions,
                         compute_dtype)
    with jax.named_scope("attn.kv_write"):
        latent = jax.lax.dynamic_update_slice(
            cache["latent"], rows.astype(cache["latent"].dtype), (0, 0, 0))
    return y, {"latent": latent}


def mla_decode(cfg: ModelConfig, params: dict, x: jnp.ndarray,
               pos: jnp.ndarray, cache: dict, compute_dtype,
               unit: jnp.ndarray | None = None) -> tuple[jnp.ndarray, dict]:
    """One-token decode in the absorbed form: x (B, 1, d), pos (B,).

    ``cache["latent"]`` is this layer's (B, S, ``cache_width``), or with
    ``unit`` the stack over the layer scan's units and this layer's index
    in it.  Writes the B new rows at ``[unit,] b, pos[b]`` and attends
    over this layer's rows 0..pos; only those rows of the cache change.
    """
    b = x.shape[0]
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_rope, rows = _project(cfg, params, x, pos[:, None],
                                    compute_dtype)
    w_kv_b = params["w_kv_b"].astype(compute_dtype).reshape(r, h, -1)
    with jax.named_scope("attn.qkv"), jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kv_b[..., :nope],
                           preferred_element_type=jnp.float32)
        q = _pad_row(jnp.concatenate([q_lat.astype(compute_dtype),
                                      q_rope[:, 0]], axis=-1), cfg)
    with jax.named_scope("attn.kv_write"):
        stack = _write_rows(cache["latent"], rows, pos, unit)
    layer = stack if unit is None else jax.lax.dynamic_index_in_dim(
        stack, unit, keepdims=False)
    o_lat = _latent_core(cfg, q, layer, pos, compute_dtype)
    with jax.named_scope("attn.out"):
        with jax.named_scope("mla.absorb"):
            o = jnp.einsum("bhr,rhv->bhv", o_lat[..., :r].astype(compute_dtype),
                           w_kv_b[..., nope:],
                           preferred_element_type=jnp.float32)
        y = o.reshape(b, 1, h * cfg.v_head_dim).astype(compute_dtype) \
            @ params["w_o"].astype(compute_dtype)
    return y, {"latent": stack}


@jax.named_scope("attn.core")
def _latent_core(cfg: ModelConfig, q, latent, pos, compute_dtype):
    """One latent query per head over the sequence's cached rows:
    q (B, H, W), latent (B, S, W) -> the softmax-weighted sum of rows
    (B, H, W), f32; only its first r columns are used."""
    with jax.named_scope("mla.latent"):
        lat = latent.astype(compute_dtype)
        sco = jnp.einsum("bhc,bsc->bhs", q, lat,
                         preferred_element_type=jnp.float32) \
            * softmax_scale(cfg)
        mask = jnp.arange(lat.shape[1])[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(mask[:, None, :], sco, NEG_INF), axis=-1)
        return jnp.einsum("bhs,bsc->bhc", p.astype(compute_dtype), lat,
                          preferred_element_type=jnp.float32)
