"""Needed work of the expert FFN stage: the routed experts each token
selects (top-k) and the shared experts, over the MoE layers.  The router,
the dispatch into buckets and the combine are other stages."""
from __future__ import annotations

from bench.model_dims import Dims
from bench.work import moe_lm


def needed(d: Dims, n_tokens: int, wbytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ``n_tokens`` tokens through every MoE layer's
    experts: each token's top-k routed and the shared experts computed
    (SwiGLU, three matrices), and the weights of the routed experts the
    tokens hit, E(1 - (1 - k/E)^T) a layer, and of the shared ones read
    once."""
    per_expert = 3 * d.d_model * d.d_ff_expert
    flops = 2.0 * per_expert * (d.top_k + d.n_shared) * n_tokens * d.n_moe
    nbytes = (wbytes * per_expert * d.n_moe
              * (moe_lm.experts_hit(d, n_tokens) + d.n_shared))
    return flops, float(nbytes)
