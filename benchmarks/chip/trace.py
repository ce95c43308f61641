"""Reduction of a profiler trace to device busy time, kernel time, the
busiest device programs and the idle gaps named by harness spans.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps the few event streams the reduction needs, as plain lists:

* per device plane (``/device:TPU:k``): the op events of its
  ``XLA Ops`` line and the program events of its ``XLA Modules`` line;
* the host spans the harness recorded with ``TraceAnnotation`` (names
  starting with ``bench.``).

Host and device events share the trace's clock (nanoseconds from the
start of the trace).  ``reduce`` works on that plain form, so a small
recorded trace kept as JSON checks it.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def extract(pb_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(pb_path)
    devices: Dict[str, dict] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if re.match(r"^/device:TPU:\d+$", plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.name, float(e.start_ns), float(e.duration_ns))
                               for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    return {"devices": devices, "spans": spans}


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


NO_SPAN = "outside harness spans"


def _segments(spans: List[Tuple[str, float, float]]
              ) -> List[Tuple[float, float, str]]:
    """Flatten nested spans of one thread into non-overlapping
    (start, end, innermost span name) segments."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []
    t = None

    def emit(a, b, label):
        if b > a:
            segs.append((a, b, label))

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            label, end = stack.pop()
            emit(t, end, label)
            t = max(t, end)
        if stack:
            emit(t, a, stack[-1][0])
        t = a if t is None else max(t, a)
        stack.append((name, b))
    while stack:
        label, end = stack.pop()
        emit(t, end, label)
        t = max(t, end)
    return segs


def _split(segs, starts, a: float, b: float):
    """(innermost span name, ns) for each part of the interval [a, b]."""
    import bisect

    out = []
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    t = a
    while t < b and i < len(segs):
        sa, sb, label = segs[i]
        if sb <= t:
            i += 1
            continue
        if sa > t:
            out.append((NO_SPAN, min(sa, b) - t))
            t = min(sa, b)
            continue
        out.append((label, min(sb, b) - t))
        t = min(sb, b)
        i += 1
    if t < b:
        out.append((NO_SPAN, b - t))
    return out


def module_name(event_name: str) -> str:
    """``jit_cascade_score(1234)`` -> ``jit_cascade_score``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def window_of(trace: dict) -> Tuple[float, float]:
    wins = [(a, b) for n, a, b in trace["spans"] if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return wins[0]


def reduce(trace: dict, *, kernel_op: str, devices: Optional[List[str]] = None,
           top: int = 10) -> dict:
    """Window-clipped reduction.  ``kernel_op`` is a regular expression
    matched against op event names (the kernel's custom call).

    Returns ``window_s``; ``busy_s`` (union of op intervals, mean over the
    chosen device planes); ``kernel_s`` (summed kernel op durations, over
    all chosen devices); ``device_ops`` (device seconds per program, the
    ``top`` largest, summed over devices); ``idle_gaps`` (idle device
    seconds, each part of a gap attributed to the innermost harness span
    covering it, the ``top`` largest, mean over devices)."""
    lo, hi = window_of(trace)
    names = devices if devices is not None else sorted(trace["devices"])
    if not names:
        raise ValueError("trace holds no device plane")
    pat = re.compile(kernel_op)
    segs = _segments([s for s in trace["spans"] if s[0] != WINDOW_SPAN])
    starts = [s[0] for s in segs]
    busy_total = 0.0
    kernel_ns = 0.0
    kernel_events = 0
    per_module: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for dev in names:
        d = trace["devices"][dev]
        iv = []
        for name, start, dur in d["ops"]:
            c = _clip(start, start + dur, lo, hi)
            if c is None:
                continue
            iv.append(c)
            if pat.search(name):
                kernel_ns += c[1] - c[0]
                kernel_events += 1
        merged = _merge(iv)
        busy_total += sum(b - a for a, b in merged)
        for name, start, dur in d["modules"]:
            c = _clip(start, start + dur, lo, hi)
            if c is not None:
                key = module_name(name)
                per_module[key] = per_module.get(key, 0.0) + (c[1] - c[0])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for i in range(0, len(edges), 2):
            for label, ns in _split(segs, starts, edges[i], edges[i + 1]):
                gaps[label] = gaps.get(label, 0.0) + ns / len(names)
    window_ns = hi - lo
    ops_sorted = sorted(per_module.items(), key=lambda kv: -kv[1])[:top]
    gaps_sorted = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_total / len(names) * 1e-9,
        "kernel_s": kernel_ns * 1e-9,
        "kernel_events": kernel_events,
        "device_ops": [[k, v * 1e-9] for k, v in ops_sorted],
        "idle_gaps": [[k, v * 1e-9] for k, v in gaps_sorted],
        "devices": names,
    }
