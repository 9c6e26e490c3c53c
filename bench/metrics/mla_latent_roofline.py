"""The decode step's latent-attention core against its roofline: the
least time the chip could take to read each sequence's latent rows up to
its position and compute the absorbed scores and weighted sum over them
(``bench/work/mla_attn.latent_core``; or those operations at peak,
whichever is longer) over the device time per launch of the decode
program's ops whose path holds the ``mla.latent`` scope (profiler
trace, ``bench/mla_scopes.py``)."""
import numpy as np

from bench import mla_scopes
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import mla_attn


def read(ctx):
    steps = ctx.get("traced_decode_contexts")
    sc = mla_scopes.program_scopes(ctx, "serve_step") if steps else None
    if sc is None or not sc.get("mla.latent"):
        return None
    pk = peaks(ctx["device_kind"])
    floors = [max(b / pk["hbm_bytes_per_s"], f / pk["flops"])
              for f, b in (mla_attn.latent_core(ctx["dims"], c)
                           for c in steps)]
    count = program(ctx["trace"], "serve_step")["count"]
    return 100.0 * float(np.mean(floors)) * count / sc["mla.latent"]
