"""UDF: share of the window inside the benchmark-owned UDF callables
(harness span ``bench.udf``)."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * ctx.seconds_in("bench.udf") / ctx.window_s
