"""Share of the traced serving slice in which no operation ran on the
device: 1 - device busy / traced window (profiler trace)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["devices"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
