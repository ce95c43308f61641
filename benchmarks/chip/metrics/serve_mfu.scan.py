"""Whole step: model FLOPs the executed plan required in the window (the
proxy cascade on every scored record, each UDF on the unpadded records
that reached it) over window x chips x the chip's bf16 peak."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.peaks is None:
        return None
    flops = ctx.cascade_work()[0] + ctx.udf_flops()
    if flops <= 0:
        return None
    peak = ctx.peaks["bf16_flop_per_s"]
    return 100.0 * flops / (ctx.window_s * ctx.chips * peak)
