"""The decode step's Mamba-2 mixers against their roofline: the least
time the chip could take to read their weights and each sequence's SSM
and convolution states, write the states back and do the mixers'
arithmetic (``bench/work/hybrid_step.ssm_mixers``; or those operations at
peak, whichever is longer) over the device time per launch of the decode
program's ops whose path holds an ``ssm.*`` scope (profiler trace,
``bench/ssm_scopes.py``)."""
import numpy as np

from bench import ssm_scopes
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import hybrid_step


def read(ctx):
    steps = ctx.get("traced_decode_contexts")
    sc = ssm_scopes.program_scopes(ctx, "serve_step") if steps else None
    if sc is None:
        return None
    pk = peaks(ctx["device_kind"])
    floors = [max(b / pk["hbm_bytes_per_s"], f / pk["flops"])
              for f, b in (hybrid_step.ssm_mixers(ctx["dims"], len(c))
                           for c in steps)]
    count = program(ctx["trace"], "serve_step")["count"]
    return 100.0 * float(np.mean(floors)) * count / sum(sc.values())
