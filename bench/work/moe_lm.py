"""Work a mixture-of-experts decoder needs, counted from its shapes.

These are the operations and bytes the model itself requires, not what the
compiled program happens to do: routed experts count for the tokens routed
to them (top-k), not for the padded dropless buckets; attention counts each
sequence's real context; the head counts the published vocabulary.  A
program that stops doing wasted work therefore does not lower them.

A multiply-add is two operations.  Weights are counted at the served width
(``wbytes`` per value); the router is kept in float32 (4 bytes).
"""
from __future__ import annotations

import numpy as np

from bench.model_dims import Dims


def _proj_flops(d: Dims) -> float:
    """Attention projections per token and layer."""
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    return 2.0 * d.d_model * (q + 2 * kv) + 2.0 * q * d.d_model


def _ffn_flops(d: Dims) -> float:
    """Feed-forward work per token, summed over the served layers."""
    dense = 6.0 * d.d_model * d.d_ff_dense * d.n_dense
    moe = (2.0 * d.d_model * d.n_experts
           + 6.0 * d.d_model * d.d_ff_expert * (d.top_k + d.n_shared))
    return dense + moe * d.n_moe


def token_flops(d: Dims, context: np.ndarray) -> float:
    """Operations for tokens that each attend over ``context`` keys
    (their own included), without the head."""
    ctx = np.asarray(context, dtype=np.float64)
    attn = 4.0 * d.n_heads * d.head_dim * ctx.sum() * d.n_layers
    return float(ctx.size * (d.n_layers * _proj_flops(d) + _ffn_flops(d))
                 + attn)


def head_flops(d: Dims, n_tokens: int) -> float:
    return 2.0 * d.d_model * d.vocab * n_tokens


def weight_bytes(d: Dims, experts_read: float, wbytes: int = 2) -> float:
    """Bytes of the served weights with ``experts_read`` routed experts
    of each MoE layer read (the embedding is read as the head only when
    tied; its gathered rows are negligible)."""
    dm, q = d.d_model, d.n_heads * d.head_dim
    kv = d.n_kv_heads * d.head_dim
    attn = (dm * (q + 2 * kv) + q * dm) * d.n_layers
    dense = 3 * dm * d.d_ff_dense * d.n_dense
    moe = (3 * dm * d.d_ff_expert * (experts_read + d.n_shared)) * d.n_moe
    head = dm * d.vocab
    router = 4 * dm * d.n_experts * d.n_moe
    return float(wbytes * (attn + dense + moe + head) + router)


def experts_hit(d: Dims, n_tokens: int) -> float:
    """Expected routed experts of one layer that ``n_tokens`` tokens
    select, each choosing top-k of E uniformly."""
    return d.n_experts * (1.0 - (1.0 - d.top_k / d.n_experts) ** n_tokens)


def kv_bytes(d: Dims, n_positions: float, wbytes: int = 2) -> float:
    return float(2 * d.n_kv_heads * d.head_dim * wbytes * d.n_layers
                 * n_positions)
