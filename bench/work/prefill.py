"""Needed work of prefilling a batch of equal-length prompts."""
from __future__ import annotations

import numpy as np

from bench.model_dims import Dims
from bench.work import moe_lm


def needed(d: Dims, batch: int, prompt_len: int) -> tuple[float, float]:
    """(operations, bytes): causal attention over each prompt, logits of
    the last position only, every weight read once and the cache written."""
    ctx = np.tile(np.arange(1, prompt_len + 1, dtype=np.float64), batch)
    flops = moe_lm.token_flops(d, ctx) + moe_lm.head_flops(d, batch)
    nbytes = (moe_lm.weight_bytes(d, moe_lm.experts_hit(d, ctx.size))
              + moe_lm.kv_bytes(d, ctx.size))
    return flops, nbytes
