"""Optimizer: UDF rows the proxy builder labeled
(``plan.meta["stats"]["udf_calls"]``), summed over the cell's queries."""


def read(ctx):
    total = 0
    for p in ctx.plans:
        stats = p.meta.get("stats")
        if stats is None:
            return None
        total += sum(int(v) for v in stats["udf_calls"].values())
    return float(total)
