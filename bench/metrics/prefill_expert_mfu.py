"""The prefill program's expert FFN as a share of the chip's peak: the
operations its tokens need (each token's top-k routed experts and the
shared experts, not the padded dropless buckets) over the device time of
the prefill program's ops in the ``moe.experts`` and ``moe.shared``
stages, times the peak FLOP/s (profiler trace)."""
from bench import trace_stages
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import expert_ffn


def read(ctx):
    if not ctx.get("traced_prefill"):
        return None
    st = trace_stages.program_stages(ctx, "prefill_step")
    if st is None:
        return None
    secs = st.get("moe.experts", 0.0) + st.get("moe.shared", 0.0)
    if not secs:
        return None
    flops = expert_ffn.needed(ctx["dims"], ctx["batch"] * ctx["prompt_len"])[0]
    count = program(ctx["trace"], "prefill_step")["count"]
    return 100.0 * flops * count / (secs * peaks(ctx["device_kind"])["flops"])
