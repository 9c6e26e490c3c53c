"""Share of the decode program's device time in ops of no model stage:
those whose innermost stage is the scan over layers (``layers``: slicing
each layer's weights and cache out of the stacked arrays and stacking the
new cache) or that have none (``(none)``: copies the compiler puts around
the scan), over the device time of all the program's ops (profiler
trace)."""
from bench import trace_stages


def read(ctx):
    st = trace_stages.program_stages(ctx, "serve_step")
    if st is None:
        return None
    total = sum(st.values())
    model = sum(st.get(s, 0.0) for s in trace_stages.MODEL_STAGES)
    return 100.0 * (total - model) / total
