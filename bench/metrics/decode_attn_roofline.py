"""Decode attention and its KV cache against their roofline: the least
time the chip could take for each step's attention (every layer's
attention weights, each sequence's keys and values up to its position
read and its new row written; or the operations at peak, whichever is
longer) over the device time per launch of the decode program's ops that
move or use those operands (profiler trace).

Those ops are the attention stages (``attn.qkv``, ``attn.kv_write``,
``attn.core``, ``attn.out``) and the layer scan's plumbing (``layers``
and ``(none)``): the scan slices each layer's attention weights and cache
out of the stacked arrays and stacks the new cache, and the compiler's
copies of the whole cache have no stage.  The attention ops alone read
those operands from the scan's slices and from copies made before them,
so their time leaves that movement out."""
import numpy as np

from bench import trace_stages
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import decode_attn

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
              for f, b in (decode_attn.needed(ctx["dims"], c)
                           for c in steps)]
    count = program(ctx["trace"], "serve_step")["count"]
    return 100.0 * float(np.mean(floors)) * count / secs
