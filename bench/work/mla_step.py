"""Needed work of one decode step of a latent-attention MoE model on its
chip's share of the experts: one new token per sequence."""
from __future__ import annotations

import numpy as np

from bench.mla_dims import MlaDims
from bench.work import mla_attn


def held_hit(d: MlaDims, n_tokens: int) -> float:
    """Expected held experts of one layer that ``n_tokens`` tokens select,
    each choosing top-k of all E uniformly: n_held (1 - (1 - k/E)^T)."""
    return d.n_held * (1.0 - (1.0 - d.top_k / d.n_experts) ** n_tokens)


def experts(d: MlaDims, n_tokens: int, wbytes: int = 2
            ) -> tuple[float, float]:
    """(operations, bytes) of ``n_tokens`` tokens through every MoE layer's
    held and shared experts (SwiGLU, three matrices): each token's top-k
    hits among the held experts (k n_held / E on average) and the shared
    experts computed, and the weights of the held experts the tokens hit
    (``held_hit``) and of the shared ones read once."""
    expert = 3 * d.d_model * d.d_ff_expert
    routed = d.top_k * d.n_held / d.n_experts
    flops = 2.0 * expert * (routed + d.n_shared) * n_tokens * d.n_moe
    nbytes = wbytes * expert * (held_hit(d, n_tokens) + d.n_shared) * d.n_moe
    return float(flops), float(nbytes)


def needed(d: MlaDims, contexts, wbytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of a step whose sequences attend over
    ``contexts`` positions each (the new token included): latent attention
    (``mla_attn``), the dense layer, the router over all E experts (kept in
    float32), the held and shared experts (``experts``) and the head over
    the published vocabulary."""
    ctx = np.asarray(contexts, dtype=np.float64)
    b = ctx.size
    a_flops, a_bytes = mla_attn.needed(d, ctx, wbytes)
    e_flops, e_bytes = experts(d, b, wbytes)
    dm = d.d_model
    dense = 3 * dm * d.d_ff_dense * d.n_dense
    flops = (a_flops + e_flops + b * (2.0 * dense + 2.0 * dm * d.vocab)
             + b * d.n_moe * 2.0 * dm * d.n_experts)
    nbytes = (a_bytes + e_bytes + wbytes * (dense + dm * d.vocab)
              + 4 * dm * d.n_experts * d.n_moe)
    return float(flops), float(nbytes)
