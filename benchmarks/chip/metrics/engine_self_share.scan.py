"""Engine and scheduler: share of the window spent in neither a UDF call
nor a fused-scorer call (harness spans ``bench.udf``, ``bench.score``)."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    rest = ctx.window_s - ctx.seconds_in("bench.udf") - ctx.seconds_in("bench.score")
    return 100.0 * rest / ctx.window_s
