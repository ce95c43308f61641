"""Program spans: named host intervals at the boundaries of the serving
engine and the fused scorer.

Off by default.  While off, ``span(name)`` returns one shared no-op
object: no clock read, no allocation, no profiler call.  ``enable()``
turns recording on; each span is then

* written into the profiler trace as a ``jax.profiler.TraceAnnotation``,
  so a profile puts it on the device trace's clock, and
* aggregated in memory per name: calls, total seconds, and self seconds
  (the duration minus what its child spans cover).  Each thread keeps
  its own span stack; the aggregates stay bounded however long
  recording stays on.  Raw spans live only in the profiler trace.

Names are prefixed by their layer: ``engine.``, ``scorer.``.  A
function-wide span is the decorator ``spanned(name)``, which calls
straight through while off.  A span gives no timing back to its caller, and
``snapshot()`` is for reports only: wall time never feeds a decision
(DESIGN.md §2).  Like ``advisory_wall_ms``, this module may read the
clock; corelint's ``wall-clock-decision`` rule flags a ``snapshot()``
read in a decision-path module.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Tuple

_clock = time.perf_counter
_on = False
_annotation = None            # jax.profiler.TraceAnnotation, bound by enable()
_lock = threading.Lock()
_totals: Dict[str, list] = {}  # name -> [calls, total_s, self_s]
_local = threading.local()


class _Off:
    """The shared span of a recorder that is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "ann", "stack", "child", "t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.stack = _stack()
        self.stack.append(self)
        self.child = 0.0
        self.t0 = _clock()
        return None

    def __exit__(self, *exc):
        dt = _clock() - self.t0
        self.stack.pop()
        if self.stack:
            self.stack[-1].child += dt
        self.ann.__exit__(*exc)
        with _lock:
            agg = _totals.get(self.name)
            if agg is None:
                agg = _totals[self.name] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dt
            agg[2] += dt - self.child
        return False


def span(name: str):
    """Context manager marking one named interval; a no-op while off."""
    if not _on:
        return _OFF
    return _Span(name)


def spanned(name: str):
    """Decorator: each call of the function is span ``name``.  While off,
    the wrapper tests one flag and calls straight through."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if not _on:
                return fn(*args, **kwargs)
            with _Span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def enable() -> None:
    """Start recording (spans entered from now on)."""
    global _on, _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Stop recording; spans already open still close into the totals."""
    global _on
    _on = False


def reset() -> None:
    """Forget every aggregate."""
    with _lock:
        _totals.clear()


def snapshot() -> Dict[str, Tuple[int, float, float]]:
    """``{name: (calls, total_s, self_s)}`` of every span closed while
    recording, since the last ``reset``."""
    with _lock:
        return {k: (int(c), float(t), float(s))
                for k, (c, t, s) in _totals.items()}
