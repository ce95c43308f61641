"""Roofline-driven tile autotuner for the fused cascade scorer.

Replaces the static ~8 MB VMEM heuristic in ``CascadeScorer.__init__``
with a swept cost model: for each candidate ``block_m`` (and weight
dtype) it computes the bytes the kernel actually moves per launch — the
bucket-padded x tile, the stacked packed weights at their storage width
(fp32 = 4 B, int8/fp8 codes = 1 B), and the mask/compaction outputs —
plus the GEMM FLOPs, and scores the cell with a two-knee roofline

    t = LAUNCH + nb * STEP + max(bytes / HBM_BW, flops / PEAK)

The sweep is deliberately a MODEL, not a wall-clock timer: in this
container Pallas runs in interpret mode, where per-cell timings measure
the Python interpreter, not the memory system.  The model's byte counts
are exact (they are the operand nbytes the compiled kernel streams), so
the ranking is the bandwidth-bound ranking a TPU would see; wall-clock
stays an advisory column (``measure_cell``) for runs on real hardware.

Feasibility reuses the PREVIOUS static heuristic's bound — per-row VMEM
footprint ``4*(F + HPp) + 9*Pp`` bytes against an 8 MB budget — so with
the default full-tile row hint the tuner picks exactly the block the old
heuristic picked (no disruption to compiled-program caches), and only
diverges where the old rule was wrong: small serving chunks, where a
full-budget block pads 8-16x the rows actually scored.

Winning configs are cached keyed by (F, HP-bucket, P-bucket, dtype,
backend, hint-bucket, max_tile); set ``CORE_AUTOTUNE_CACHE=/path.json``
to persist the table across processes so repeat serving runs skip the
sweep entirely.  On a TPU the backend is the chip's ``device_kind`` and
its envelope comes from ``DEVICE_PEAKS``; a TPU missing from that table
is an error, never the nominal default.
"""
from __future__ import annotations

import json
import os
from typing import NamedTuple, Optional, Tuple

import numpy as np

# Nominal envelope for backends without published peaks (the CPU and the
# "model" sweep backend).  Only RATIOS of modeled times ever gate
# anything, so the absolute calibration is free to be nominal; the byte
# counts feeding them are exact.  A backend can override these with
# MEASURED constants via ``calibrate_backend`` / ``set_backend_constants``.
HBM_BYTES_PER_S = 1.2e12
PEAK_FLOPS = 7.0e13
LAUNCH_OVERHEAD_S = 5.0e-6
GRID_STEP_OVERHEAD_S = 1.5e-6
VMEM_BLOCK_BUDGET = 8 << 20  # same budget the old static heuristic used
WEIGHT_RESIDENT_BYTES = 4 << 20  # weights this small stay pinned in VMEM


class BackendConstants(NamedTuple):
    """Roofline envelope for one backend.  ``source`` records where the
    numbers came from: "default" (the baked nominal constants),
    "published" (a chip's datasheet peaks, ``DEVICE_PEAKS``) or
    "measured" (``calibrate_backend`` fitted them from wall-clock)."""

    hbm_bytes_per_s: float = HBM_BYTES_PER_S
    peak_flops: float = PEAK_FLOPS
    launch_overhead_s: float = LAUNCH_OVERHEAD_S
    grid_step_overhead_s: float = GRID_STEP_OVERHEAD_S
    source: str = "default"


_DEFAULT_CONSTANTS = BackendConstants()
_BACKEND_CONSTANTS: dict = {}  # backend name -> BackendConstants

# Published per-chip peaks keyed by ``jax.Device.device_kind`` (Google
# Cloud documentation, "TPU v5e": 819 GB/s HBM, 197 TFLOP/s bf16).  The
# overhead terms stay nominal: nothing published prices them.
DEVICE_PEAKS = {
    "TPU v5 lite": BackendConstants(hbm_bytes_per_s=819e9,
                                    peak_flops=197e12, source="published"),
}


def resolve_backend() -> str:
    """The autotune backend key of the running JAX backend: the platform
    name, except on a TPU, where it is the chip's ``device_kind`` — which
    must have an entry in ``DEVICE_PEAKS``."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        return backend
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for TPU device_kind {kind!r}: add it to "
            f"kernels/autotune.DEVICE_PEAKS")
    return kind


def backend_constants(backend: Optional[str] = None) -> BackendConstants:
    """Constants for ``backend`` — the calibrated set if one was
    registered, else the chip's published peaks, else the nominal
    defaults (so the CPU path is numerically identical to the
    pre-calibration tuner)."""
    key = str(backend)
    if key in _BACKEND_CONSTANTS:
        return _BACKEND_CONSTANTS[key]
    return DEVICE_PEAKS.get(key, _DEFAULT_CONSTANTS)


def set_backend_constants(backend: str, constants: BackendConstants) -> None:
    """Register measured constants for ``backend`` and invalidate every
    cached sweep winner keyed to it — a winner picked under the nominal
    envelope may not survive the measured one."""
    _BACKEND_CONSTANTS[str(backend)] = constants
    for key in [k for k in _CACHE if k[4] == str(backend)]:
        del _CACHE[key]


def reset_backend_constants() -> None:
    _BACKEND_CONSTANTS.clear()


def _ceil128(n: int) -> int:
    return -(-int(n) // 128) * 128


def static_heuristic_block_m(n_features: int, hp: int, n_proxies: int,
                             max_tile: int = 8192) -> int:
    """The pre-autotune rule, verbatim: largest power-of-two block >= 256
    whose per-row footprint fits the 8 MB budget.  Kept callable so the
    sweep can report "chosen vs static" and tests can pin equivalence."""
    hpp = _ceil128(hp)
    pp = _ceil128(n_proxies)
    per_row = 4 * (int(n_features) + hpp) + 9 * pp
    budget_rows = VMEM_BLOCK_BUDGET // per_row
    block_m = 256
    while block_m * 2 <= min(budget_rows, max_tile):
        block_m *= 2
    return min(block_m, max_tile)


class CellModel(NamedTuple):
    """Roofline model of one (block_m, dtype) sweep cell."""

    block_m: int
    dtype: str
    n_rows: int
    npad: int          # bucket-padded rows the launch actually scores
    nb: int            # grid steps
    bytes_moved: int   # exact operand bytes streamed per launch
    flops: int
    t_model_s: float
    mbu: float         # model bandwidth utilization: useful bytes / (t*BW)
    feasible: bool     # per-block footprint within the VMEM budget


class TunedConfig(NamedTuple):
    block_m: int
    dtype: str
    t_model_s: float
    bytes_moved: int
    mbu: float
    static_block_m: int  # what the old heuristic would have picked
    source: str          # "sweep" | "cache"


def _weight_bytes(n_features: int, hp: int, n_proxies: int, dtype: str) -> int:
    from repro.core.proxy_family import QUANT_WEIGHT_BYTES

    wb = QUANT_WEIGHT_BYTES[dtype]
    hpp = _ceil128(hp)
    pp = _ceil128(n_proxies)
    # w1 (F, HPp) + w2 (HPp, Pp) at storage width; b1/b2/thr/out_scale f32
    return (int(n_features) * hpp * wb + hpp * pp * wb
            + hpp * 4 + 3 * pp * 4)


def padded_rows(n_rows: int, block_m: int, max_tile: int) -> int:
    """The scorer's bucket ladder: block_m * 2^k, capped at max_tile."""
    size = block_m
    while size < min(n_rows, max_tile):
        size *= 2
    return min(size, max_tile)


def cell_model(n_features: int, hp: int, n_proxies: int, dtype: str,
               block_m: int, n_rows: int, *,
               max_tile: int = 8192,
               backend: Optional[str] = None) -> CellModel:
    """Roofline-score one sweep cell for a chunk of ``n_rows`` records.

    ``backend`` selects the bandwidth/flops/overhead envelope: a backend
    with registered measured constants (``calibrate_backend``) is scored
    under those; anything else — including the default ``None`` — uses
    the nominal module constants, bit-identically to before."""
    bc = backend_constants(backend)
    hpp = _ceil128(hp)
    pp = _ceil128(n_proxies)
    npad = padded_rows(n_rows, block_m, max_tile)
    nb = -(-npad // block_m)
    wbytes = _weight_bytes(n_features, hp, n_proxies, dtype)
    refetch = 1 if wbytes <= WEIGHT_RESIDENT_BYTES else nb
    x_bytes = npad * n_features * 4
    out_bytes = npad * pp * (1 + 4)  # keep mask + compacted survivor ids
    bytes_moved = x_bytes + out_bytes + wbytes * refetch
    flops = 2 * npad * (n_features * hpp + hpp * pp)
    t_mem = bytes_moved / bc.hbm_bytes_per_s
    t_flop = flops / bc.peak_flops
    t = bc.launch_overhead_s + nb * bc.grid_step_overhead_s + max(t_mem, t_flop)
    # useful bytes: the unpadded rows' traffic + one copy of the weights
    useful = n_rows * (n_features * 4 + pp * 5) + wbytes
    mbu = useful / (t * bc.hbm_bytes_per_s)
    per_row = 4 * (n_features + hpp) + 9 * pp
    feasible = per_row * block_m <= VMEM_BLOCK_BUDGET
    return CellModel(block_m=int(block_m), dtype=dtype, n_rows=int(n_rows),
                     npad=int(npad), nb=int(nb),
                     bytes_moved=int(bytes_moved), flops=int(flops),
                     t_model_s=float(t), mbu=float(mbu), feasible=feasible)


def _candidates(max_tile: int) -> Tuple[int, ...]:
    out, c = [], 128
    while c <= max_tile:
        out.append(c)
        c *= 2
    return tuple(out) or (max_tile,)


# ----------------------------------------------------------------- cache
_CACHE: dict = {}
_STATS = {"sweeps": 0, "hits": 0}
_DISK_LOADED = False


def autotune_stats() -> dict:
    return dict(_STATS)


def reset_autotune_stats() -> None:
    _STATS["sweeps"] = 0
    _STATS["hits"] = 0


def clear_autotune_cache() -> None:
    global _DISK_LOADED
    _CACHE.clear()
    _DISK_LOADED = False


def _hint_bucket(n_rows_hint: int, max_tile: int) -> int:
    return padded_rows(min(int(n_rows_hint), max_tile), 128, max_tile)


def _cache_key(n_features, hp, n_proxies, dtype, backend, hint_b, max_tile):
    return (int(n_features), _ceil128(hp), _ceil128(n_proxies), str(dtype),
            str(backend), int(hint_b), int(max_tile))


def _disk_path() -> Optional[str]:
    return os.environ.get("CORE_AUTOTUNE_CACHE") or None


def _read_disk_table(path: str) -> dict:
    """Parse the on-disk table into {key tuple: TunedConfig}.  A corrupt,
    partial, or wrong-schema file (a concurrent writer died mid-write
    before the save path became atomic, or the user pointed
    ``CORE_AUTOTUNE_CACHE`` at an unrelated file) yields {} with a
    warning — the sweep is cheap, silently-poisoned configs are not."""
    table: dict = {}
    if not os.path.exists(path):
        return table
    try:
        with open(path) as f:
            raw = json.load(f)
        for key_s, cfg in raw.items():
            table[tuple(json.loads(key_s))] = TunedConfig(
                block_m=int(cfg["block_m"]), dtype=str(cfg["dtype"]),
                t_model_s=float(cfg["t_model_s"]),
                bytes_moved=int(cfg["bytes_moved"]), mbu=float(cfg["mbu"]),
                static_block_m=int(cfg["static_block_m"]), source="cache")
    except (OSError, ValueError, KeyError, TypeError):
        import warnings

        warnings.warn(
            f"CORE_AUTOTUNE_CACHE at {path!r} is corrupt or partial; "
            f"ignoring it and falling back to a fresh sweep",
            RuntimeWarning, stacklevel=3)
        return {}
    return table


def _load_disk_cache() -> None:
    global _DISK_LOADED
    _DISK_LOADED = True
    path = _disk_path()
    if not path:
        return
    for key, cfg in _read_disk_table(path).items():
        # disk entries were swept under nominal or published envelopes; a
        # backend running calibrated constants must re-sweep, not inherit
        if backend_constants(key[4]).source == "measured":
            continue
        _CACHE.setdefault(key, cfg)


def _save_disk_cache() -> None:
    """Persist the in-memory table: merge-on-save + atomic replace.

    K subprocess hosts all point at one cache file, so the naive
    ``open(path, "w")`` had two failure modes: interleaved writes could
    corrupt the JSON, and a host that swept shape A would clobber the
    entries a peer had just saved for shape B (last writer wins on the
    WHOLE table).  Re-reading the file immediately before writing keeps
    peers' fresh entries (our in-memory values win only for keys we hold
    — both sides swept the same deterministic model, so ties are
    identical anyway), and writing via a same-directory temp file +
    ``os.replace`` makes the publish atomic: readers see the old table
    or the new one, never a torn prefix."""
    path = _disk_path()
    if not path:
        return
    merged = _read_disk_table(path)
    # never publish winners swept under MEASURED constants: they price
    # this machine's silicon, and the shared table is read by peers whose
    # calibration (or lack of one) differs
    merged.update({k: v for k, v in _CACHE.items()
                   if backend_constants(k[4]).source != "measured"})
    table = {
        json.dumps(list(k)): {
            "block_m": v.block_m, "dtype": v.dtype,
            "t_model_s": v.t_model_s, "bytes_moved": v.bytes_moved,
            "mbu": v.mbu, "static_block_m": v.static_block_m,
        }
        for k, v in merged.items()
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(table, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def choose_block_m(n_features: int, hp: int, n_proxies: int,
                   dtype: str = "float32", *,
                   n_rows_hint: Optional[int] = None,
                   max_tile: int = 8192,
                   backend: Optional[str] = None) -> TunedConfig:
    """Pick ``block_m`` for the fused scorer by roofline sweep.

    ``n_rows_hint`` is the expected serving chunk size; None means "full
    tiles" (n_rows_hint = max_tile), under which the winner coincides
    with the old static heuristic by construction (same feasibility
    bound; equal bytes at every feasible block, so fewer grid steps win).
    """
    if backend is None:
        backend = resolve_backend()
    if not _DISK_LOADED:
        _load_disk_cache()
    hint = max_tile if n_rows_hint is None else int(n_rows_hint)
    hint_b = _hint_bucket(max(hint, 1), max_tile)
    key = _cache_key(n_features, hp, n_proxies, dtype, backend, hint_b,
                     max_tile)
    hit = _CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        return hit._replace(source="cache")
    _STATS["sweeps"] += 1
    static_bm = static_heuristic_block_m(n_features, hp, n_proxies, max_tile)
    cells = [cell_model(n_features, hp, n_proxies, dtype, bm, hint_b,
                        max_tile=max_tile, backend=backend)
             for bm in _candidates(max_tile)]
    feasible = [c for c in cells if c.feasible]
    if not feasible:
        # degenerate shape: even the old heuristic's floor blows the
        # budget — keep its pick so behavior is unchanged
        feasible = [c for c in cells if c.block_m == static_bm] or cells[:1]
    best = min(feasible, key=lambda c: (c.t_model_s, -c.block_m))
    cfg = TunedConfig(block_m=best.block_m, dtype=dtype,
                      t_model_s=best.t_model_s,
                      bytes_moved=best.bytes_moved, mbu=best.mbu,
                      static_block_m=static_bm, source="sweep")
    _CACHE[key] = cfg
    # calibrated winners are this process's measurement — persisting them
    # would poison peers running under the nominal (or their own
    # measured) envelope, since the disk key does not carry constants
    if backend_constants(backend).source != "measured":
        _save_disk_cache()
    return cfg


# ----------------------------------------------------------------- sweep
def sweep_table(shapes, dtypes=("float32", "int8"), *,
                n_rows_hints=(256, 1024, 8192), max_tile: int = 8192):
    """Full sweep over workload shapes x dtypes x chunk hints; the rows
    behind ``benchmarks/roofline.py`` and the nightly CI artifact.

    ``shapes``: iterable of (name, F, HP, P).  Returns a list of dicts,
    one per (shape, dtype, hint): the winning cell, the static
    heuristic's cell at the same hint, and whether the tuner's pick
    strictly beats it under the model.
    """
    rows = []
    for name, f, hp, p in shapes:
        static_bm = static_heuristic_block_m(f, hp, p, max_tile)
        for dtype in dtypes:
            for hint in n_rows_hints:
                cfg = choose_block_m(f, hp, p, dtype, n_rows_hint=hint,
                                     max_tile=max_tile, backend="model")
                stat = cell_model(f, hp, p, dtype, static_bm, hint,
                                  max_tile=max_tile)
                rows.append({
                    "shape": name, "F": int(f), "HP": int(hp), "P": int(p),
                    "dtype": dtype, "n_rows": int(hint),
                    "block_m": cfg.block_m, "static_block_m": static_bm,
                    "t_model_us": cfg.t_model_s * 1e6,
                    "t_static_us": stat.t_model_s * 1e6,
                    "bytes_moved": cfg.bytes_moved,
                    "bytes_static": stat.bytes_moved,
                    "mbu": cfg.mbu,
                    "beats_static": cfg.t_model_s < stat.t_model_s,
                    "source": cfg.source,
                })
    return rows


def calibrate_backend(scorer, *, backend: Optional[str] = None,
                      rows: Tuple[int, int] = (256, 8192),
                      repeats: int = 3,
                      register: bool = True) -> BackendConstants:
    """Fit the roofline constants for THIS backend from measured
    wall-clock instead of the baked TPU-ish defaults.

    Two ``measure_cell`` points bracket the chunk-size axis: the byte
    delta between them over the time delta is the achieved streaming
    bandwidth (the fixed launch/overhead terms cancel in the
    difference), the small-point residual after memory time prices the
    launch overhead, and peak FLOPs scale with the fitted bandwidth
    ratio (the model only ever compares cells on one backend, so the
    compute roof needs the right ORDER, not the right absolute).  Every
    fitted constant is clamped positive; a degenerate measurement (zero
    or negative deltas — e.g. interpret mode noise) falls back to the
    nominal default for that constant rather than registering garbage.

    ``register=True`` installs the result via ``set_backend_constants``
    so subsequent ``choose_block_m`` sweeps for this backend score under
    the measured envelope.  Runs that never call this keep the default
    constants and pick byte-identical blocks to the pre-calibration
    tuner."""
    if backend is None:
        backend = resolve_backend()
    f = int(scorer.n_features)
    hp = int(scorer.w1.shape[1])
    p = int(scorer.n_proxies)
    dtype = str(scorer.dtype)
    bm = int(scorer.block_m)
    mt = int(scorer.max_tile)
    r_small, r_large = int(min(rows)), int(max(rows))
    t_small = measure_cell(scorer, r_small, repeats=repeats)
    t_large = measure_cell(scorer, r_large, repeats=repeats)
    cm_small = cell_model(f, hp, p, dtype, bm, r_small, max_tile=mt)
    cm_large = cell_model(f, hp, p, dtype, bm, r_large, max_tile=mt)
    d_bytes = cm_large.bytes_moved - cm_small.bytes_moved
    d_t = t_large - t_small
    if d_bytes > 0 and d_t > 1e-9:
        bw = float(d_bytes) / float(d_t)
    else:
        bw = _DEFAULT_CONSTANTS.hbm_bytes_per_s
    # the compute roof scales with the memory roof: only the RATIO of
    # the two roofs (the knee position) affects any ranking on a single
    # backend, and preserving the default ratio keeps it where exact
    # byte/flop counts put it
    peak = _DEFAULT_CONSTANTS.peak_flops * (
        bw / _DEFAULT_CONSTANTS.hbm_bytes_per_s)
    launch = t_small - cm_small.bytes_moved / bw \
        - cm_small.nb * _DEFAULT_CONSTANTS.grid_step_overhead_s
    if launch <= 0:
        launch = _DEFAULT_CONSTANTS.launch_overhead_s
    bc = BackendConstants(
        hbm_bytes_per_s=bw, peak_flops=peak,
        launch_overhead_s=float(launch),
        grid_step_overhead_s=_DEFAULT_CONSTANTS.grid_step_overhead_s,
        source="measured")
    if register:
        set_backend_constants(str(backend), bc)
    return bc


def measure_cell(scorer, n_rows: int, *, repeats: int = 3) -> float:
    """Advisory wall-clock: seconds per ``score_masks`` call on a random
    chunk.  Meaningful on compiled backends only; in interpret mode it
    times Python, so callers must treat it as a non-gating column."""
    import time

    rng = np.random.RandomState(0)
    x = rng.randn(n_rows, scorer.n_features).astype(np.float32)
    scorer.score_masks(x)  # warm the jit cache
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        scorer.score_masks(x)
        best = min(best, time.perf_counter() - t0)
    return best
