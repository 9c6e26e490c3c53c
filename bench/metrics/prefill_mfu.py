"""The prefill program's share of the chip's peak: the useful operations
of a prefill (routed top-k and shared experts, dense layer, causal
attention, the head at the last position) over the program's device time
in the trace, times the peak FLOP/s."""
from bench.peaks import peaks
from bench.trace_reduce import program
from bench.work import prefill


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("traced_prefill"):
        return None
    prog = program(t, "prefill_step")
    if prog is None or not prog["seconds"]:
        return None
    flops = prefill.needed(ctx["dims"], ctx["batch"], ctx["prompt_len"])[0]
    return 100.0 * flops * prog["count"] / (prog["seconds"]
                                            * peaks(ctx["device_kind"])["flops"])
