"""Whole decode step's share of the chip's peak: the useful operations of
every decode step in the window (top-k routed and shared experts, dense
layer, attention over each sequence's context, head) over the host-clock
time those steps took, token to token, times the peak FLOP/s."""
from bench.peaks import peaks
from bench.work import decode_step


def read(ctx):
    steps, secs = ctx.get("decode_contexts"), ctx.get("decode_host_s")
    if not steps or not secs:
        return None
    flops = sum(decode_step.needed(ctx["dims"], c)[0] for c in steps)
    return 100.0 * flops / (secs * peaks(ctx["device_kind"])["flops"])
