"""Fused scorer: share of the window inside ``CascadeScorer`` calls
(harness span ``bench.score``)."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * ctx.seconds_in("bench.score") / ctx.window_s
