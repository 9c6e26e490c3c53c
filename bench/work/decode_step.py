"""Needed work of one decode step: one new token per sequence."""
from __future__ import annotations

import numpy as np

from bench.model_dims import Dims
from bench.work import moe_lm


def needed(d: Dims, contexts) -> tuple[float, float]:
    """(operations, bytes) of a step whose sequences attend over
    ``contexts`` keys each (the new token included)."""
    ctx = np.asarray(contexts, dtype=np.float64)
    b = ctx.size
    flops = moe_lm.token_flops(d, ctx) + moe_lm.head_flops(d, b)
    nbytes = (moe_lm.weight_bytes(d, moe_lm.experts_hit(d, b))
              + moe_lm.kv_bytes(d, ctx.sum())      # keys and values read
              + moe_lm.kv_bytes(d, b))             # the new row written
    return flops, nbytes
