"""Decode latent attention and its cache against their roofline: the least
time the chip could take for each step's attention (``bench/work/
mla_attn.py``: every layer's attention weights, each sequence's latent
rows up to its position read and its new row written; or the operations
at peak, whichever is longer) over the device time per launch of the
decode program's ops that move or use those operands.

The op set is ``decode_attn_roofline``'s: the attention stages
(``attn.qkv``, ``attn.kv_write``, ``attn.core``, ``attn.out``, which hold
the MLA scopes) and the layer scan's plumbing (``layers`` and
``(none)``), which slices each layer's weights out of the stacks."""
import numpy as np

from bench import trace_stages
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import mla_attn

STAGES = ("attn.qkv", "attn.kv_write", "attn.core", "attn.out", "layers",
          trace_stages.NONE)


def read(ctx):
    steps = ctx.get("traced_decode_contexts")
    st = trace_stages.program_stages(ctx, "serve_step") if steps else None
    if st is None:
        return None
    secs = sum(st.get(s, 0.0) for s in STAGES)
    if not secs:
        return None
    pk = peaks(ctx["device_kind"])
    floors = [max(b / pk["hbm_bytes_per_s"], f / pk["flops"])
              for f, b in (mla_attn.needed(ctx["dims"], c) for c in steps)]
    count = program(ctx["trace"], "serve_step")["count"]
    return 100.0 * float(np.mean(floors)) * count / secs
