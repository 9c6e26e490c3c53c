"""Needed work of the attention stages of one decode step: the
projections (RoPE included) and output projection, each sequence's new
key and value row written to the cache, and attention over its keys and
values up to its position."""
from __future__ import annotations

import numpy as np

from bench.model_dims import Dims
from bench.work import moe_lm


def needed(d: Dims, contexts, wbytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of a step whose sequences attend over
    ``contexts`` keys each (the new token included): every layer's
    attention weights read once, the keys and values up to each position
    read and the new row written."""
    ctx = np.asarray(contexts, dtype=np.float64)
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    proj = 2.0 * d.d_model * (q + 2 * kv) + 2.0 * q * d.d_model
    flops = (ctx.size * proj
             + 4.0 * d.n_heads * d.head_dim * ctx.sum()) * d.n_layers
    weights = wbytes * (d.d_model * (q + 2 * kv) + q * d.d_model) * d.n_layers
    nbytes = (weights + moe_lm.kv_bytes(d, ctx.sum(), wbytes)
              + moe_lm.kv_bytes(d, ctx.size, wbytes))
    return float(flops), float(nbytes)
