"""UDF: rows the engine handed to the UDF callables in the window (the
harness's ``udf_rows`` count) over the window's UDF calls (its
``bench.udf`` spans) times the engine's tile.  100% means every UDF call
got a full tile; the gate drops rows from each stage batch before the
call."""


def read(ctx):
    calls = sum(1 for name, _a, _b in ctx.probe.spans if name == "bench.udf")
    if calls == 0:
        return None
    return 100.0 * ctx.counter("udf_rows") / (calls * int(ctx.cfg["tile"]))
