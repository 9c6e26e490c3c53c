"""Plain float32 forward pass of a latent-attention (MLA) mixture-of-experts
decoder, DeepSeek-V2's architecture, on one chip's share of its experts.

The reference against which an MLA cell's served tokens are judged.  It
follows the published description (arXiv:2405.04434 and the published
``modeling_deepseek.py``) in its expanded form: per-head keys and values
from the normed latent, the one rotary key appended to every head's key,
YaRN rotary frequencies on the de-interleaved rotary dims, causal
softmax attention, and no absorption, cache, kernel or batching.  The MoE
layer routes over all published experts and adds the part of the routed
sum that the held experts give (ids ``0..n_held-1``, every held expert
computed for every token and weighted by the router) and the shared
experts.  The departures the config file lists are applied by
``bench/mla_dims.py``.

It imports nothing of the program under test; it shares the GQA
reference's matrix product (with its float8 control), norms, SwiGLU,
globals and head, and makes its weights from the seed one layer at a time
(``bench/weights.make_leaf``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.mla_dims import MlaDims
from bench.reference.moe_lm import F32, _head, _mm, _rms, _swiglu, make_globals
from bench.weights import make_leaf


def layer_shapes(d: MlaDims, layer: int) -> dict:
    """Canonical name -> (shape, stored dtype) of one layer's weights."""
    dm, h, r = d.d_model, d.n_heads, d.kv_lora_rank
    out = {
        "norm.attn": ((dm,), F32),
        "norm.ffn": ((dm,), F32),
        "attn.q": ((dm, h * (d.qk_nope + d.qk_rope)), None),
        "attn.kv_a": ((dm, r + d.qk_rope), None),
        "norm.kv": ((r,), F32),
        "attn.kv_b": ((r, h * (d.qk_nope + d.v_head)), None),
        "attn.o": ((h * d.v_head, dm), None),
    }
    if layer < d.n_dense:
        out.update({"ffn.gate": ((dm, d.d_ff_dense), None),
                    "ffn.up": ((dm, d.d_ff_dense), None),
                    "ffn.down": ((d.d_ff_dense, dm), None)})
        return out
    e, f = d.n_held, d.d_ff_expert
    out.update({"moe.router": ((dm, d.n_experts), F32),
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
def make_layer(key, d: MlaDims, dense: bool, layer, dtype) -> dict:
    """One layer's weights as stored (``dtype`` for the matrices), in f32."""
    shapes = layer_shapes(d, 0 if dense else d.n_dense)
    return {name: make_leaf(key, name, layer, shape, dt or dtype,
                            d.n_layers).astype(F32)
            for name, (shape, dt) in shapes.items()}


# --------------------------------------------------------------------- #
# YaRN (arXiv:2309.00071), as DeepSeek-V2's DeepseekV2YarnRotaryEmbedding
# --------------------------------------------------------------------- #


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(d: MlaDims) -> jnp.ndarray:
    """Inverse frequencies of the rotary dims, (qk_rope // 2,) float32."""
    dim, base = d.qk_rope, d.rope_theta
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    freq_extra = 1.0 / pos_freqs
    freq_inter = 1.0 / (d.yarn_factor * pos_freqs)

    def correction_dim(num_rotations):
        return (dim * math.log(d.yarn_original_max
                               / (num_rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(d.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(d.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def softmax_scale(d: MlaDims) -> float:
    scale = (d.qk_nope + d.qk_rope) ** -0.5
    if d.yarn_mscale_all_dim:
        m = _yarn_get_mscale(d.yarn_factor, d.yarn_mscale_all_dim)
        scale = scale * m * m
    return scale


def _rope(d: MlaDims, x):
    """x: (S, heads, qk_rope) at positions 0..S-1, the published way: view
    the dims as pairs, put the pairs' first and second halves apart, then
    ``x cos + rotate_half(x) sin`` with cos/sin of the doubled angles."""
    s, heads, dim = x.shape
    x = x.reshape(s, heads, dim // 2, 2).swapaxes(-1, -2).reshape(s, heads,
                                                                 dim)
    freqs = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(d)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = (_yarn_get_mscale(d.yarn_factor, d.yarn_mscale)
         / _yarn_get_mscale(d.yarn_factor, d.yarn_mscale_all_dim))
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    rotated = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rotated * sin


# --------------------------------------------------------------------- #
# Layers
# --------------------------------------------------------------------- #


def _attention(d: MlaDims, w, h, quant):
    s = h.shape[0]
    nh, r, nope = d.n_heads, d.kv_lora_rank, d.qk_nope
    q = _mm(h, w["attn.q"], quant).reshape(s, nh, nope + d.qk_rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    compressed = _mm(h, w["attn.kv_a"], quant)
    c, k_pe = compressed[:, :r], compressed[:, r:]
    kv = _mm(_rms(c, w["norm.kv"], d.norm_eps), w["attn.kv_b"], quant)
    kv = kv.reshape(s, nh, nope + d.v_head)
    k_nope, value = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(d, q_pe)
    k_pe = _rope(d, k_pe[:, None, :])
    query = jnp.concatenate([q_nope, q_pe], -1)
    key = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, q_pe.shape)], -1)
    sc = jnp.einsum("qhd,khd->hqk", query, key) * softmax_scale(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, value).reshape(s, -1)
    return _mm(o, w["attn.o"], quant)


def _moe(d: MlaDims, w, h, quant):
    """The router over all experts; the held experts' part of the routed
    sum, plus the shared experts."""
    probs = jax.nn.softmax(h @ w["moe.router"], axis=-1)         # (S, E)
    top_p, top_i = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    comb = jnp.zeros_like(probs).at[rows, top_i].set(top_p)      # (S, E)
    # Every held expert on every token, weighted by the router (0 if
    # unchosen); the experts held elsewhere are not in the sum.
    a = (jax.nn.silu(_mm(h, w["moe.gate"], quant, "sd,edf->esf"))
         * _mm(h, w["moe.up"], quant, "sd,edf->esf"))
    y = _mm(a, w["moe.down"], quant, "esf,efd->esd")
    out = jnp.einsum("se,esd->sd", comb[:, :d.n_held], y)
    if d.n_shared:
        out = out + _swiglu(h, w["shared.gate"], w["shared.up"],
                            w["shared.down"], quant)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(d: MlaDims, dense: bool, w, x, quant):
    h = _rms(x, w["norm.attn"], d.norm_eps)
    x = x + _attention(d, w, h, quant)
    h = _rms(x, w["norm.ffn"], d.norm_eps)
    f = (_swiglu(h, w["ffn.gate"], w["ffn.up"], w["ffn.down"], quant)
         if dense else _moe(d, w, h, quant))
    return x + f


def logits(d: MlaDims, seed_key, dtype, seqs: np.ndarray, out_from: int,
           quant: str | None = None) -> np.ndarray:
    """Logits at positions ``out_from..S-1`` of each sequence.

    seqs: (n, S) token ids, each a prompt followed by the served tokens
    fed back.  Returns (n, S - out_from, vocab) float32 on the host.  The
    layers run one at a time over all sequences, so that only one layer's
    weights are on the device.
    """
    with jax.default_matmul_precision("highest"):
        g = make_globals(seed_key, d, dtype)
        x = [g["embed"][jnp.asarray(s)] for s in seqs]
        for layer in range(d.n_layers):
            dense = layer < d.n_dense
            w = make_layer(seed_key, d, dense, layer, dtype)
            x = [_layer(d, dense, w, xi, quant) for xi in x]
            del w
        return np.stack([np.asarray(_head(d, g, xi[out_from:], quant))
                         for xi in x])
