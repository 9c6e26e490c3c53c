"""Plain float32 forward pass of a hybrid Mamba-2 / attention
mixture-of-experts decoder, GraniteMoeHybrid's architecture, on one
chip's share of its experts.

The reference against which a hybrid cell's served tokens are judged.  It
follows the published description (GraniteMoeHybrid and Bamba in
``transformers``; Mamba-2, arXiv:2405.21060):

- the embedding times ``embedding_multiplier``; each layer adds
  ``residual_multiplier`` times its mixer's output to the residual
  stream, then ``residual_multiplier`` times its MoE's and shared
  expert's; the logits are divided by ``logits_scaling``;
- a Mamba-2 layer: ``[z | xBC | dt] = u W_in``; a causal depthwise
  convolution of width ``mamba_d_conv`` with bias over xBC, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head h of
  group g, ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T`` and ``y_t =
  S_t C_t + D_h x_t``; then ``rmsnorm(y * silu(z)) W_out``.  The state is
  computed by the plain recurrence, one position at a time, not by the
  chunked algorithm the program runs;
- an attention layer: causal grouped-query attention with no position
  embedding and the softmax scale ``attention_multiplier``;
- the MoE: the router over all published experts, the part of the routed
  sum that the held experts give (every held expert computed for every
  token, ``mla_moe_lm._moe``) and the shared experts.

It imports nothing of the program under test; it shares the GQA
reference's matrix product (with its float8 control), norms and head, and
makes its weights from the seed one layer at a time (``make_leaf``:
``bench/weights.make_leaf``, the embedding's scale and the SSM's scalars
below).  The config must tie its embeddings.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.hybrid_dims import HybridDims
from bench.reference.mla_moe_lm import _moe
from bench.reference.moe_lm import F32, _head, _mm, _rms

#: Half-width of the values ``weights.make_leaf`` makes for a plain name:
#: dividing by it gives uniform values in [-1, 1).
_UNIT = weights.STD * math.sqrt(3.0)


def make_leaf(key, name: str, layer, shape, dtype, n_layers: int,
              vocab: int | None = None, tied: bool = False,
              embedding_multiplier: float = 1.0):
    """One weight, as ``weights.make_leaf``, except the embedding and the
    Mamba-2 leaves that the model's own initialisation sets (assumed in
    the config file).  ``embed`` is made at ``weights.make_leaf``'s std
    over ``embedding_multiplier``, so that the first layer sees
    embeddings of that std: at 12 times it, the input token's own row
    outweighs the layers' outputs in the residual stream and greedy
    decoding only echoes it.  ``ssm.a_log`` is the log of A uniform in
    [1, 16], ``ssm.dt_bias`` the inverse softplus of dt log-uniform in
    [0.001, 0.1], ``ssm.d`` ones, the convolution's weight and bias
    uniform in [-0.5, 0.5] (PyTorch's default for a kernel of 4 on one
    channel) and ``ssm.out`` at the output projections' std."""
    if name == "embed":
        x = weights.make_leaf(key, name, layer, shape, F32, n_layers, vocab,
                              tied)
        return (x / embedding_multiplier).astype(dtype)
    if not name.startswith("ssm.") or name == "ssm.in":
        return weights.make_leaf(key, name, layer, shape, dtype, n_layers,
                                 vocab, tied)
    if name == "ssm.d":
        return jnp.ones(shape, dtype)
    u = weights.make_leaf(key, name, layer, shape, F32, n_layers) / _UNIT
    if name == "ssm.a_log":
        x = jnp.log(8.5 + 7.5 * u)
    elif name == "ssm.dt_bias":
        lo, hi = math.log(1e-3), math.log(0.1)
        dt = jnp.maximum(jnp.exp((lo + hi) / 2 + (hi - lo) / 2 * u), 1e-4)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name in ("ssm.conv", "ssm.conv_bias"):
        x = 0.5 * u
    elif name == "ssm.out":
        x = u * (_UNIT / math.sqrt(2 * n_layers))
    else:
        raise KeyError(f"no initialisation for {name}")
    return x.astype(dtype)


def layer_shapes(d: HybridDims, kind: str) -> dict:
    """Canonical name -> (shape, stored dtype) of one layer's weights
    (``None``: the model's dtype)."""
    dm, e, f = d.d_model, d.n_held, d.d_ff_expert
    out = {"norm.attn": ((dm,), F32),          # before the mixer
           "norm.ffn": ((dm,), F32),
           "moe.router": ((dm, d.n_experts), F32),
           "moe.gate": ((e, dm, f), None),
           "moe.up": ((e, dm, f), None),
           "moe.down": ((e, f, dm), None)}
    if d.n_shared:
        fs = f * d.n_shared
        out.update({"shared.gate": ((dm, fs), None),
                    "shared.up": ((dm, fs), None),
                    "shared.down": ((fs, dm), None)})
    if kind == "attention":
        q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
        out.update({"attn.q": ((dm, q), None), "attn.k": ((dm, kv), None),
                    "attn.v": ((dm, kv), None), "attn.o": ((q, dm), None)})
        return out
    di, cd, h = d.d_inner, d.conv_dim, d.ssm_heads
    out.update({"ssm.in": ((dm, di + cd + h), None),
                "ssm.conv": ((d.conv_kernel, cd), None),
                "ssm.conv_bias": ((cd,), None),
                "ssm.dt_bias": ((h,), F32),
                "ssm.a_log": ((h,), F32),
                "ssm.d": ((h,), F32),
                "norm.ssm": ((di,), F32),
                "ssm.out": ((di, dm), None)})
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_globals(key, d: HybridDims, dtype) -> dict:
    """The tied embedding (the head too) and the final norm, in f32."""
    return {"embed": make_leaf(key, "embed", -1, (d.vocab, d.d_model),
                               dtype, d.n_layers, tied=d.tied,
                               embedding_multiplier=d.embedding_multiplier
                               ).astype(F32),
            "norm.final": make_leaf(key, "norm.final", -1, (d.d_model,),
                                    F32, d.n_layers)}


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def make_layer(key, d: HybridDims, kind: str, layer, dtype) -> dict:
    """One layer's weights as stored (``dtype`` for the matrices), in f32."""
    return {name: make_leaf(key, name, layer, shape, dt or dtype,
                            d.n_layers).astype(F32)
            for name, (shape, dt) in layer_shapes(d, kind).items()}


# --------------------------------------------------------------------- #
# Mixers
# --------------------------------------------------------------------- #


def ssm_recurrence(x, dt, a, bm, cm, state=None):
    """The SSM one position at a time: x (S, H, P), dt (S, H), a (H,),
    bm and cm (S, G, N); ``state`` (H, P, N) at the start, zeros by
    default.  Returns y (S, H, P) without the D skip, and the final state."""
    s, h, p = x.shape
    rep = h // bm.shape[1]

    def step(st, inp):
        xt, dtt, bt, ct = inp
        bh, ch = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        st = (jnp.exp(dtt * a)[:, None, None] * st
              + dtt[:, None, None] * xt[:, :, None] * bh[:, None, :])
        return st, jnp.einsum("hpn,hn->hp", st, ch)

    st = jnp.zeros((h, p, bm.shape[2]), F32) if state is None else state
    st, y = jax.lax.scan(step, st, (x, dt, bm, cm))
    return y, st


def _mamba(d: HybridDims, w, u, quant):
    """Mamba-2 over one sequence u (S, d) from position 0; returns its
    output and the SSM state after the last position."""
    s = u.shape[0]
    di, cd, g, n = d.d_inner, d.conv_dim, d.ssm_groups, d.ssm_state
    zxbcdt = _mm(u, w["ssm.in"], quant)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cd], zxbcdt[:, di + cd:]
    k = d.conv_kernel
    past = jnp.concatenate([jnp.zeros((k - 1, cd), F32), xbc], axis=0)
    conv = sum(past[i:i + s] * w["ssm.conv"][i] for i in range(k))
    xbc = jax.nn.silu(conv + w["ssm.conv_bias"])
    x = xbc[:, :di].reshape(s, d.ssm_heads, d.ssm_head_dim)
    bm = xbc[:, di:di + g * n].reshape(s, g, n)
    cm = xbc[:, di + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + w["ssm.dt_bias"])
    y, state = ssm_recurrence(x, dt, -jnp.exp(w["ssm.a_log"]), bm, cm)
    y = (y + w["ssm.d"][:, None] * x).reshape(s, di) * jax.nn.silu(z)
    return _mm(_rms(y, w["norm.ssm"], d.norm_eps), w["ssm.out"], quant), state


def _attention(d: HybridDims, w, h, quant):
    """Causal grouped-query attention, no position embedding."""
    s = h.shape[0]
    q = _mm(h, w["attn.q"], quant).reshape(s, d.n_heads, d.head_dim)
    k = _mm(h, w["attn.k"], quant).reshape(s, d.n_kv_heads, d.head_dim)
    v = _mm(h, w["attn.v"], quant).reshape(s, d.n_kv_heads, d.head_dim)
    group = d.n_heads // d.n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * d.attention_multiplier
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1)
    return _mm(o, w["attn.o"], quant), None


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(d: HybridDims, kind: str, w, x, quant):
    """One layer over one sequence: the new residual stream, and the SSM
    state after the last position (None for attention)."""
    h = _rms(x, w["norm.attn"], d.norm_eps)
    mix, state = (_attention if kind == "attention" else _mamba)(d, w, h,
                                                                 quant)
    x = x + d.residual_multiplier * mix
    h = _rms(x, w["norm.ffn"], d.norm_eps)
    return x + d.residual_multiplier * _moe(d, w, h, quant), state


def logits(d: HybridDims, seed_key, dtype, seqs: np.ndarray, out_from: int,
           quant: str | None = None):
    """Logits at positions ``out_from..S-1`` of each sequence, and each
    sequence's SSM states after its last position.

    seqs: (n, S) token ids, each a prompt followed by the served tokens
    fed back.  Returns (n, S - out_from, vocab) and (n, Mamba-2 layers,
    heads, head_dim, d_state), float32 on the host.  The layers run one at
    a time over all sequences, so that only one layer's weights are on the
    device.
    """
    states = []
    with jax.default_matmul_precision("highest"):
        g = make_globals(seed_key, d, dtype)
        x = [g["embed"][jnp.asarray(s)] * d.embedding_multiplier
             for s in seqs]
        for layer, kind in enumerate(d.layer_types):
            w = make_layer(seed_key, d, kind, layer, dtype)
            x, st = zip(*[_layer(d, kind, w, xi, quant) for xi in x])
            if kind == "mamba":
                states.append(np.stack([np.asarray(s) for s in st]))
            del w
        out = np.stack([np.asarray(_head(d, g, xi[out_from:], quant))
                        for xi in x])
    return out, np.stack(states, axis=1)
