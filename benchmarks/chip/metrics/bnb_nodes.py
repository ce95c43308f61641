"""Optimizer: branch-and-bound nodes visited (``plan.meta["trace"]``),
summed over the cell's queries, in the timed optimize."""


def read(ctx):
    traces = [p.meta.get("trace") for p in ctx.plans]
    if not traces or any(t is None for t in traces):
        return None
    return float(sum(int(t["nodes_visited"]) for t in traces))
