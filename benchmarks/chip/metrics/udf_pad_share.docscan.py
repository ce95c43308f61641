"""UDF: share of the token slots the window's UDF calls computed that
carry no valid token: the rows a call is padded with to its record bucket
plus each record's right padding (the family's per-call counts of the
calls made inside ``bench.window``)."""


def read(ctx):
    fam = ctx.model.family
    win = [(a, b) for name, a, b in ctx.probe.spans if name == "bench.window"]
    if not hasattr(fam, "calls") or not win:
        return None
    calls = fam.calls(*win[0])
    slots = sum(float(st["token_slots"]) for _n, _s, st in calls)
    if slots <= 0:
        return None
    valid = sum(float(st["valid_tokens"]) for _n, _s, st in calls)
    return 100.0 * (1.0 - valid / slots)
