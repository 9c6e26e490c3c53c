"""Shared primitives: norms, initializers, rotary embeddings, FFN."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: ``jax.named_scope`` names of the model's stages, as they appear in each
#: compiled op's ``op_name`` metadata (and so in a profiler trace).  They
#: are metadata only: the optimized program is the same without them.
STAGES = (
    "embed", "norm",
    "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
    "ffn.dense", "head", "layers",
)
#: Scopes nested inside the attention stages of the latent-attention mixer
#: (``repro.models.mla``): K/V expanded from the latent (prefill), queries
#: taken into and outputs out of latent space (decode), and the scores and
#: weighted sum over the latent cache (decode).
MLA_SCOPES = ("mla.expand", "mla.absorb", "mla.latent")


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# --------------------------------------------------------------------- #
# Initializers
# --------------------------------------------------------------------- #


def normal_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def out_proj_init(key, shape, dtype, n_layers: int, scale: float = 0.02):
    """GPT-2 style residual-branch scaling."""
    return (scale / np.sqrt(2 * n_layers) * jax.random.normal(key, shape)).astype(dtype)


# --------------------------------------------------------------------- #
# RMSNorm
# --------------------------------------------------------------------- #


def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype)}


@jax.named_scope("norm")
def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(orig)


# --------------------------------------------------------------------- #
# Rotary position embeddings
# --------------------------------------------------------------------- #


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate q/k.  x: (..., seq, n_heads, head_dim); positions: (..., seq)."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)                  # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                           # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, 0.1 m ln(s) + 1 (1 for s <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, scaling) -> jnp.ndarray:
    """YaRN inverse frequencies, shape (dim // 2,), float32: wavelengths
    below the correction range keep their frequency, those above it are
    divided by ``factor``, with a linear ramp between (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``; arXiv:2309.00071)."""
    def correction_dim(rotations):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(dim, theta)
    inter = extra / scaling.factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                     scaling) -> jnp.ndarray:
    """YaRN rotary embedding in DeepSeek-V2's layout: rotated pairs are
    adjacent (dims 2i, 2i+1), and the result comes out de-interleaved (the
    even dims rotated, then the odd), as the published
    ``apply_rotary_pos_emb`` does; the frequencies and the cos/sin factor
    are YaRN's.  x: (..., seq, n_heads, dim); positions: (..., seq)."""
    dim = x.shape[-1]
    freqs = yarn_frequencies(dim, theta, scaling)
    m = (yarn_mscale(scaling.factor, scaling.mscale)
         / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


# --------------------------------------------------------------------- #
# Gated FFN (SwiGLU)
# --------------------------------------------------------------------- #


def ffn_init(key, d_model: int, d_ff: int, n_layers: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": normal_init(k1, (d_model, d_ff), dtype),
        "w_up": normal_init(k2, (d_model, d_ff), dtype),
        "w_down": out_proj_init(k3, (d_ff, d_model), dtype, n_layers),
    }


@jax.named_scope("ffn.dense")
def ffn_apply(params: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    x = x.astype(compute_dtype)
    gate = jax.nn.silu(x @ params["w_gate"].astype(compute_dtype))
    up = x @ params["w_up"].astype(compute_dtype)
    return (gate * up) @ params["w_down"].astype(compute_dtype)


# --------------------------------------------------------------------- #
# Embedding / LM head
# --------------------------------------------------------------------- #


def embedding_init(key, vocab: int, d_model: int, dtype) -> jnp.ndarray:
    return normal_init(key, (vocab, d_model), dtype)


@jax.named_scope("embed")
def embed(table: jnp.ndarray, tokens: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0).astype(compute_dtype)


@jax.named_scope("head")
def lm_head(table_or_w: jnp.ndarray, x: jnp.ndarray, tied: bool) -> jnp.ndarray:
    w = table_or_w.astype(x.dtype)
    return x @ (w.T if tied else w)
