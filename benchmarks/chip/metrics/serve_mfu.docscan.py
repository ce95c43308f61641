"""Whole step: model FLOPs the executed plan required in the window (the
proxy cascade on every scored record, each UDF on the valid tokens of
the records that reached it: the family's per-call counts of the calls
made inside ``bench.window``) over window x chips x the chip's bf16
peak."""


def read(ctx):
    fam = ctx.model.family
    win = [(a, b) for name, a, b in ctx.probe.spans if name == "bench.window"]
    if ctx.window_s <= 0 or ctx.peaks is None or not hasattr(fam, "calls") \
            or not win:
        return None
    flops = ctx.cascade_work()[0] + fam.flops(fam.calls(*win[0]), padded=False)
    if flops <= 0:
        return None
    peak = ctx.peaks["bf16_flop_per_s"]
    return 100.0 * flops / (ctx.window_s * ctx.chips * peak)
