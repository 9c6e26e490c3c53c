"""The decode-step program's share of its roofline: the least time the
chip could take for the step's needed work (every weight the batch's
routing needs read once, each sequence's keys and values up to its
position; or its operations at peak, whichever is longer) over the
program's device time per launch in the trace."""
import numpy as np

from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import decode_step


def read(ctx):
    t, steps = ctx.get("trace"), ctx.get("traced_decode_contexts")
    if not t or not steps:
        return None
    prog = program(t, "serve_step")
    if prog is None or not prog["seconds"]:
        return None
    pk = peaks(ctx["device_kind"])
    floors = [max(b / pk["hbm_bytes_per_s"], f / pk["flops"])
              for f, b in (decode_step.needed(ctx["dims"], c) for c in steps)]
    return 100.0 * float(np.mean(floors)) * prog["count"] / prog["seconds"]
