"""Optimizer: proxy training (``plan.meta["stats"]["training_ms"]``)
over the plan build's wall time (``plan.meta["wall_ms"]``), summed over
the cell's queries, in the timed optimize.  Both are the builder's
advisory timers."""


def read(ctx):
    part = wall = 0.0
    for p in ctx.plans:
        stats, ms = p.meta.get("stats"), p.meta.get("wall_ms")
        if stats is None or ms is None:
            return None
        part += float(stats["training_ms"])
        wall += float(ms)
    if wall <= 0:
        return None
    return 100.0 * part / wall
