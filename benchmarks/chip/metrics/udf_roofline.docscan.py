"""UDF forward: the least time of the window's padded UDF work on the
chip (``counts_moonlight``: every token slot the calls computed, the
held experts' assignments, the weights read once a call; the larger of
FLOPs over the bf16 peak and bytes over the HBM bandwidth, call by call)
over the device seconds of the forward's program (``device_ops``)."""

PROGRAM = "jit_classifier_forward"


def read(ctx):
    t, fam = ctx.trace, ctx.model.family
    if t is None or ctx.peaks is None or not hasattr(fam, "least_time_s"):
        return None
    device_s = sum(s for name, s in t["device_ops"] if name == PROGRAM)
    win = [(a, b) for name, a, b in ctx.probe.spans if name == "bench.window"]
    if device_s <= 0 or not win:
        return None
    return 100.0 * fam.least_time_s(fam.calls(*win[0]), ctx.peaks) / device_s
