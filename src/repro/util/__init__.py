"""Small cross-cutting runtime helpers.

Two invariant-enforcing utilities live here, each distilled from a bug
class this repo actually shipped (see ``analysis/corelint.py`` and
DESIGN.md §9 for the rule catalog they anchor):

* ``advisory_wall_ms`` — THE sanctioned wall-clock read for decision-path
  modules (``serving/``, ``core/``, ``distributed/``).  Everything those
  modules decide (scheduling, degrade ladders, swap escalation) runs on
  the deterministic cost-model clock; wall-clock is advisory reporting
  only.  Funneling every read through one explicitly-named helper makes
  the corelint allowlist a single function instead of a module list —
  a raw ``time.perf_counter()`` in a decision module is a lint error.
  ``repro.util.spans`` (program spans, off unless enabled) reads the
  clock on the same footing; its totals are for reports only.
* ``atomic_write_text`` / ``atomic_write_bytes`` — same-directory temp
  file + ``os.replace`` publish, the pattern ``kernels/autotune.py``
  hardened in PR 7 after a concurrent writer tore its disk cache.  Any
  shared-path ``open(path, "w")`` outside this pattern is a lint error.

``enable_compile_cache`` turns on JAX's persistent compilation cache for
the entry points (never at import).
"""
from __future__ import annotations

import os
import time
from pathlib import Path


def advisory_wall_ms() -> float:
    """Milliseconds from a monotonic wall clock — ADVISORY ONLY.

    The returned value may feed stats fields, log lines, and advisory
    bench columns; it must never feed a scheduling, shedding, degrade,
    or swap decision (those run on the cost-model clock so results are
    bit-reproducible and gateable — DESIGN.md §2/§7).  corelint rule
    ``wall-clock-decision`` enforces that decision-path modules read
    wall time only through this helper.
    """
    return time.perf_counter() * 1e3


def atomic_write_bytes(path, data: bytes) -> None:
    """Publish ``data`` at ``path`` atomically: write a same-directory
    temp file, then ``os.replace``.  Readers see the old content or the
    new content, never a torn prefix; a concurrent writer loses the race
    wholesale instead of interleaving.  The temp name carries the pid so
    two processes publishing the same path cannot collide on it."""
    path = os.fspath(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_text(path, text: str, encoding: str = "utf-8") -> None:
    """``atomic_write_bytes`` for text content."""
    atomic_write_bytes(path, text.encode(encoding))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Called by entry points only.  A ``JAX_COMPILATION_CACHE_DIR`` set in
    the environment is left to JAX; otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` — the path is part of what a later
    run has to find again, so it never carries a temporary name, pid or
    time.  Every program is cached, however quick its compile: a cold
    process on the chip compiles all of them again otherwise."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
