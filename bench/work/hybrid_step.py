"""Needed work of one decode step of a hybrid Mamba-2 / attention MoE
model on its chip's share of the experts: one new token per sequence.

A Mamba-2 layer reads its weights once and each sequence's SSM state
(heads x head_dim x d_state, float32) and last d_conv - 1 convolution
inputs, and writes both back; its arithmetic is the projections, the
convolution and, per state value, the decay, the input's outer product
and the read-out (five operations).  An attention layer reads its
weights and each sequence's K/V rows up to its position and writes the
new row.  The MoE block is counted as on the MLA cells' chips
(``bench/work/mla_step.experts``: the held experts the batch's routing
hits, and the shared ones)."""
from __future__ import annotations

import numpy as np

from bench.hybrid_dims import HybridDims
from bench.work import mla_step

F32 = 4


def ssm_weight_bytes(d: HybridDims, wbytes: int = 2) -> int:
    """One Mamba-2 layer's weights: the projections and the convolution at
    the served width, its per-head scalars and the norm's scale in f32."""
    di, cd, h = d.d_inner, d.conv_dim, d.ssm_heads
    matrices = d.d_model * (di + cd + h) + di * d.d_model
    return wbytes * (matrices + (d.conv_kernel + 1) * cd) + F32 * (3 * h + di)


def ssm_state_values(d: HybridDims) -> int:
    """One sequence's decode state in one Mamba-2 layer, in values."""
    return (d.ssm_heads * d.ssm_head_dim * d.ssm_state
            + (d.conv_kernel - 1) * d.conv_dim)


def ssm_mixers(d: HybridDims, batch: int, wbytes: int = 2
               ) -> tuple[float, float]:
    """(operations, bytes) of every Mamba-2 mixer of a step of ``batch``
    sequences: each layer's weights read once, its states read and
    written."""
    di, cd, h = d.d_inner, d.conv_dim, d.ssm_heads
    per_token = (2.0 * d.d_model * (di + cd + h) + 2.0 * di * d.d_model
                 + 2.0 * d.conv_kernel * cd
                 + 5.0 * h * d.ssm_head_dim * d.ssm_state)
    flops = per_token * batch * d.n_mamba
    nbytes = (ssm_weight_bytes(d, wbytes)
              + 2 * F32 * ssm_state_values(d) * batch) * d.n_mamba
    return float(flops), float(nbytes)


def attention(d: HybridDims, contexts, wbytes: int = 2
              ) -> tuple[float, float]:
    """(operations, bytes) of every attention layer of a step whose
    sequences attend over ``contexts`` positions each (the new token
    included): weights read once, K/V rows up to each position read and
    the new row written."""
    ctx = np.asarray(contexts, dtype=np.float64)
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    weights = d.d_model * (q + 2 * kv) + q * d.d_model
    flops = (2.0 * weights * ctx.size + 4.0 * q * ctx.sum()) * d.n_attn
    nbytes = wbytes * (weights + 2 * kv * (ctx.sum() + ctx.size)) * d.n_attn
    return float(flops), float(nbytes)


def needed(d: HybridDims, contexts, wbytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of a step whose sequences attend over
    ``contexts`` positions each: the Mamba-2 mixers (``ssm_mixers``), the
    attention layers (``attention``), the router over all E experts (kept
    in float32), the held and shared experts and the head over the
    published vocabulary."""
    ctx = np.asarray(contexts, dtype=np.float64)
    b = ctx.size
    s_flops, s_bytes = ssm_mixers(d, b, wbytes)
    a_flops, a_bytes = attention(d, ctx, wbytes)
    e_flops, e_bytes = mla_step.experts(d, b, wbytes)
    dm = d.d_model
    flops = (s_flops + a_flops + e_flops + b * 2.0 * dm * d.vocab
             + b * d.n_moe * 2.0 * dm * d.n_experts)
    nbytes = (s_bytes + a_bytes + e_bytes + wbytes * dm * d.vocab
              + F32 * dm * d.n_experts * d.n_moe)
    return float(flops), float(nbytes)
