"""Kernel: the fused cascade kernel's least time on the chip, from the
benchmark's own count of its work, over the summed device time of the
kernel's events in the trace.  The count comes from the plan's shapes
(``counts.cascade_flops`` / ``counts.cascade_bytes``), not from the
kernel's padded lanes.  At F=64 with a few linear proxies the bound is
memory (``counts.least_time_s`` says which)."""


def read(ctx):
    t = ctx.trace
    if t is None or t["kernel_events"] == 0 or t["kernel_s"] <= 0:
        return None
    least, _bound = ctx.cascade_least_time()
    return 100.0 * least / t["kernel_s"]
