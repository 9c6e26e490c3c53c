"""Plain float32 forward pass of a mixture-of-experts decoder.

The reference against which served tokens are judged.  It follows the
published description of the architecture (pre-norm decoder, RMSNorm,
rotary embeddings with the half-split rotation, grouped-query causal
attention, SwiGLU feed-forward, softmax router with top-k experts and
optional shared experts and leading dense layers), with the departures the
config file lists.  It imports nothing of the program under test: it makes
its own weights from the seed (``bench/weights.py``), one layer at a time,
computes every expert for every token and weights the outputs by the
router, and uses no cache, kernel or batching.

``quant="fp8"`` computes every weight matrix product with both operands
rounded to float8 e4m3 (per-matrix scale for weights, per-token scale for
activations): the control that decides whether a comparison can fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model_dims import Dims
from bench.weights import make_leaf

F32 = jnp.float32
_FP8_MAX = 448.0


def _fp8(x, axes):
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant, spec="...d,df->...f"):
    """Weight matrix product; ``w`` may carry a leading expert axis."""
    if quant == "fp8":
        x = _fp8(x, (-1,))
        w = _fp8(w, (-2, -1))
    return jnp.einsum(spec, x, w)


def layer_shapes(d: Dims, layer: int) -> dict:
    """Canonical name -> (shape, stored dtype) of one layer's weights."""
    dm, hd = d.d_model, d.head_dim
    out = {
        "norm.attn": ((dm,), F32),
        "norm.ffn": ((dm,), F32),
        "attn.q": ((dm, d.n_heads * hd), None),
        "attn.k": ((dm, d.n_kv_heads * hd), None),
        "attn.v": ((dm, d.n_kv_heads * hd), None),
        "attn.o": ((d.n_heads * hd, dm), None),
    }
    if layer < d.n_dense:
        out.update({"ffn.gate": ((dm, d.d_ff_dense), None),
                    "ffn.up": ((dm, d.d_ff_dense), None),
                    "ffn.down": ((d.d_ff_dense, dm), None)})
        return out
    e, f = d.n_experts, d.d_ff_expert
    out.update({"moe.router": ((dm, e), F32),
                "moe.gate": ((e, dm, f), None),
                "moe.up": ((e, dm, f), None),
                "moe.down": ((e, f, dm), None)})
    if d.n_shared:
        fs = f * d.n_shared
        out.update({"shared.gate": ((dm, fs), None),
                    "shared.up": ((dm, fs), None),
                    "shared.down": ((fs, dm), None)})
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def make_layer(key, d: Dims, dense: bool, layer, dtype) -> dict:
    """One layer's weights as stored (``dtype`` for the matrices), in f32."""
    shapes = layer_shapes(d, 0 if dense else d.n_dense)
    return {name: make_leaf(key, name, layer, shape, dt or dtype,
                            d.n_layers).astype(F32)
            for name, (shape, dt) in shapes.items()}


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_globals(key, d: Dims, dtype) -> dict:
    g = {"embed": make_leaf(key, "embed", -1, (d.vocab, d.d_model),
                            dtype, d.n_layers, tied=d.tied).astype(F32),
         "norm.final": make_leaf(key, "norm.final", -1, (d.d_model,),
                                 F32, d.n_layers)}
    if not d.tied:
        g["head"] = make_leaf(key, "head", -1, (d.d_model, d.vocab),
                              dtype, d.n_layers).astype(F32)
    return g


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1; rotate halves."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(d: Dims, w, h, quant):
    s = h.shape[0]
    q = _mm(h, w["attn.q"], quant).reshape(s, d.n_heads, d.head_dim)
    k = _mm(h, w["attn.k"], quant).reshape(s, d.n_kv_heads, d.head_dim)
    v = _mm(h, w["attn.v"], quant).reshape(s, d.n_kv_heads, d.head_dim)
    q, k = _rope(q, d.rope_theta), _rope(k, d.rope_theta)
    group = d.n_heads // d.n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * d.attention_multiplier
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1)
    return _mm(o, w["attn.o"], quant)


def _swiglu(h, g, u, dn, quant):
    a = jax.nn.silu(_mm(h, g, quant)) * _mm(h, u, quant)
    return _mm(a, dn, quant)


def _moe(d: Dims, w, h, quant):
    probs = jax.nn.softmax(h @ w["moe.router"], axis=-1)         # (S, E)
    top_p, top_i = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    comb = jnp.zeros_like(probs).at[rows, top_i].set(top_p)      # (S, E)
    # Every expert on every token, weighted by the router (0 if unchosen).
    a = (jax.nn.silu(_mm(h, w["moe.gate"], quant, "sd,edf->esf"))
         * _mm(h, w["moe.up"], quant, "sd,edf->esf"))
    y = _mm(a, w["moe.down"], quant, "esf,efd->esd")
    out = jnp.einsum("se,esd->sd", comb, y)
    if d.n_shared:
        out = out + _swiglu(h, w["shared.gate"], w["shared.up"],
                            w["shared.down"], quant)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(d: Dims, dense: bool, w, x, quant):
    h = _rms(x, w["norm.attn"], d.norm_eps)
    x = x + d.residual_multiplier * _attention(d, w, h, quant)
    h = _rms(x, w["norm.ffn"], d.norm_eps)
    f = (_swiglu(h, w["ffn.gate"], w["ffn.up"], w["ffn.down"], quant)
         if dense else _moe(d, w, h, quant))
    return x + d.residual_multiplier * f


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(d: Dims, g, x, quant):
    x = _rms(x, g["norm.final"], d.norm_eps)
    table = g["embed"].T if d.tied else g["head"]
    return _mm(x, table, quant) / d.logits_scaling


def logits(d: Dims, seed_key, dtype, seqs: np.ndarray, out_from: int,
           quant: str | None = None) -> np.ndarray:
    """Logits at positions ``out_from..S-1`` of each sequence.

    seqs: (n, S) token ids, each a prompt followed by the served tokens
    fed back.  Returns (n, S - out_from, vocab) float32 on the host.  The
    layers run one at a time over all sequences, so that only one layer's
    weights are on the device.
    """
    with jax.default_matmul_precision("highest"):
        g = make_globals(seed_key, d, dtype)
        x = [g["embed"][jnp.asarray(s)] * d.embedding_multiplier
             for s in seqs]
        for layer in range(d.n_layers):
            dense = layer < d.n_dense
            w = make_layer(seed_key, d, dense, layer, dtype)
            x = [_layer(d, dense, w, xi, quant) for xi in x]
            del w
        return np.stack([np.asarray(_head(d, g, xi[out_from:], quant))
                         for xi in x])
