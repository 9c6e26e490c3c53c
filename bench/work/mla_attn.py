"""Needed work of the latent-attention stages of one decode step, in the
absorbed form: the projections (query, latent and rotary key, the query
taken into latent space, the output taken out of it and projected), each
sequence's new latent row written, and its scores and weighted sum over
its latent rows up to its position.  The latent norm's scale is left out
(512 values a layer)."""
from __future__ import annotations

import numpy as np

from bench.mla_dims import MlaDims


def weight_values(d: MlaDims) -> int:
    """Attention weights of one layer, in values."""
    h, r = d.n_heads, d.kv_lora_rank
    return (d.d_model * h * (d.qk_nope + d.qk_rope)      # w_q
            + d.d_model * d.latent_dim                   # w_kv_a
            + r * h * (d.qk_nope + d.v_head)             # w_kv_b
            + h * d.v_head * d.d_model)                  # w_o


def latent_core(d: MlaDims, contexts, wbytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the scores and the weighted sum over the
    latent cache alone: each sequence's rows up to its position read once,
    scores over the whole row (latent and rotary key) and the sum over
    the latent."""
    ctx = np.asarray(contexts, dtype=np.float64)
    flops = (2.0 * d.n_heads * (d.latent_dim + d.kv_lora_rank) * ctx.sum()
             * d.n_layers)
    return float(flops), float(wbytes * d.latent_dim * ctx.sum() * d.n_layers)


def needed(d: MlaDims, contexts, wbytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of a step whose sequences attend over
    ``contexts`` positions each (the new token included): every layer's
    attention weights read once, the latent rows up to each position read
    and the new row written."""
    ctx = np.asarray(contexts, dtype=np.float64)
    # Each weight is applied once a token: w_kv_b's K half takes the query
    # into latent space and its V half takes the weighted latent out.
    core_flops, core_bytes = latent_core(d, ctx, wbytes)
    flops = ctx.size * 2.0 * weight_values(d) * d.n_layers + core_flops
    nbytes = (wbytes * weight_values(d) * d.n_layers + core_bytes
              + wbytes * d.latent_dim * ctx.size * d.n_layers)
    return float(flops), float(nbytes)
