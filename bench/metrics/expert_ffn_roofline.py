"""The decode step's expert FFN against its roofline: the least time the
chip could take for the experts each step needs (the routed experts the
batch's routing hits, E(1 - (1 - k/E)^B) of each MoE layer, and the shared
experts, read once; or their operations at peak, whichever is longer) over
the device time of the decode program's ops in the ``moe.experts`` and
``moe.shared`` stages per launch (profiler trace)."""
import numpy as np

from bench import trace_stages
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import expert_ffn


def read(ctx):
    steps = ctx.get("traced_decode_contexts")
    st = trace_stages.program_stages(ctx, "serve_step") if steps else None
    if st is None:
        return None
    secs = st.get("moe.experts", 0.0) + st.get("moe.shared", 0.0)
    if not secs:
        return None
    pk = peaks(ctx["device_kind"])
    floors = [max(b / pk["hbm_bytes_per_s"], f / pk["flops"])
              for f, b in (expert_ffn.needed(ctx["dims"], len(c))
                           for c in steps)]
    count = program(ctx["trace"], "serve_step")["count"]
    return 100.0 * float(np.mean(floors)) * count / secs
