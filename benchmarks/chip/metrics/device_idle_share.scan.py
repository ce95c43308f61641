"""Device: 1 - (union of device op intervals) / window, mean over the
cell's chips, from the profiler trace of the window."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
