"""The decode-step program of a latent-attention cell against its
roofline: the least time the chip could take for the step's needed work
(``bench/work/mla_step.py``: latent attention over each sequence's rows up
to its position, the dense layer, the router, the held experts the
batch's routing hits and the shared ones, the head; or its operations at
peak, whichever is longer) over the program's device time per launch in
the trace."""
import numpy as np

from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import mla_step


def read(ctx):
    t, steps = ctx.get("trace"), ctx.get("traced_decode_contexts")
    if not t or not steps:
        return None
    prog = program(t, "serve_step")
    if prog is None or not prog["seconds"]:
        return None
    pk = peaks(ctx["device_kind"])
    floors = [max(b / pk["hbm_bytes_per_s"], f / pk["flops"])
              for f, b in (mla_step.needed(ctx["dims"], c) for c in steps)]
    return 100.0 * float(np.mean(floors)) * prog["count"] / prog["seconds"]
