"""Jit'd wrappers around the Pallas kernels.

On a TPU backend the kernels run compiled (interpret=False); on the CPU
they run in Pallas interpret mode, which executes the kernel body for
correctness validation only.  Interpret mode does not check Mosaic's
lowering or tiling rules: ``tests/test_tpu_compile.py`` compiles the
kernels for a described TPU v5e to cover that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune
from repro.kernels.flash_attention import flash_attention
from repro.kernels.proxy_score import cascade_score, interpret_default
from repro.kernels.ssd_scan import ssd_chunk
from repro.util.spans import span, spanned


# ----------------------------------------------------------- proxy scoring
def fold_standardizer(params):
    """Fold (x - mean)/scale into (w, b): the kernel then applies a single
    affine map.  params: LinearParams.  (Kept as the linear parity oracle's
    fold; execution paths go through the family packers.)"""
    w = np.asarray(params.w) / np.asarray(params.scale)
    b = float(params.b) - float(np.asarray(params.mean) @ w)
    return w.astype(np.float32), np.float32(b)


# Packing (standardizer fold + lowering to the depth-1 MLP form) is pure
# per parameter set, so memoize by object identity.  The cache holds a
# strong reference to the params, which keeps each id() valid for the
# lifetime of its entry; size-bounded FIFO eviction caps memory.
_PACK_CACHE: dict = {}
_PACK_CACHE_MAX = 512


def pack_proxy_cached(params):
    """Memoized ``family_of(params).pack``: repeated scoring of the same
    proxy (every microbatch of every stage) packs once."""
    from repro.core.proxy_family import family_of

    # id() is safe HERE only because the entry holds a strong ref to params
    # and the hit path re-checks `hit[0] is params` before trusting the key.
    key = id(params)  # corelint: disable=identity-cache-key
    hit = _PACK_CACHE.get(key)
    if hit is not None and hit[0] is params:
        return hit[1]
    packed = family_of(params).pack(params)
    if len(_PACK_CACHE) >= _PACK_CACHE_MAX:
        _PACK_CACHE.pop(next(iter(_PACK_CACHE)))
    _PACK_CACHE[key] = (params, packed)
    return packed


_OPERAND_CACHE: dict = {}


def _kernel_operands_cached(params):
    """Device-resident (w1, b1, w2, b2) for a single proxy, memoized on
    params identity — the per-stage path packs and uploads once, not per
    microbatch."""
    from repro.core.proxy_family import cascade_kernel_operands, pack_cascade

    # same id()-plus-strong-ref-plus-`is`-recheck pattern as pack_proxy_cached
    key = id(params)  # corelint: disable=identity-cache-key
    hit = _OPERAND_CACHE.get(key)
    if hit is not None and hit[0] is params:
        return hit[1]
    ops = tuple(jnp.asarray(a) for a in cascade_kernel_operands(
        pack_cascade([params], pack_fn=pack_proxy_cached)))
    if len(_OPERAND_CACHE) >= _PACK_CACHE_MAX:
        _OPERAND_CACHE.pop(next(iter(_OPERAND_CACHE)))
    _OPERAND_CACHE[key] = (params, ops)
    return ops


def proxy_score_batch(params, x, threshold: float):
    """Single-proxy convenience used by the per-stage kernel path: returns
    the keep mask.  Family-agnostic — params may be any registered family's."""
    w1, b1, w2, b2 = _kernel_operands_cached(params)
    _scores, mask, _pk, _cnt = cascade_score(
        jnp.asarray(x, jnp.float32), w1, b1, w2, b2,
        jnp.asarray([threshold], jnp.float32), x.shape[0],
        interpret=interpret_default(), with_scores=False,
        with_compaction=False,
    )
    return np.asarray(mask[:, 0])


class CascadeScorer:
    """Whole-cascade fused scorer (DESIGN.md §3), every proxy family.

    Packs every stage's params ONCE at construction ("plan-compile time")
    via the family registry — standardizers folded, each stage lowered to
    the packed depth-1 MLP form, the whole cascade stacked into
    bucket-padded ``(F, H, P)`` tensors kept on device — and scores record
    tiles through the fused two-pass ``cascade_score`` Pallas kernel: one
    launch yields every stage's keep mask plus on-device-compacted
    survivor index lists, for linear, MLP, and mixed cascades alike.

    Input batches are bucket-padded to a small geometric ladder of static
    shapes so ``jax.jit`` traces a handful of programs total instead of one
    per survivor count; batches larger than the top bucket are chunked.

    The packed operands and every scored tile live on ``self.device``:
    the device that was current (``current_device``) when the scorer was
    built, so a serving host inside ``jax.default_device(d)`` scores on
    ``d``.
    """

    def __init__(self, param_list, thresholds, *, block_m: int = None,
                 interpret=None, max_tile: int = 8192,
                 dtype: str = "float32", n_rows_hint: int = None,
                 packed=None):
        from repro.core.proxy_family import (
            cascade_kernel_operands, pack_cascade, quantize_cascade)

        if not param_list:
            raise ValueError("CascadeScorer needs at least one proxy")
        if packed is None:
            packed = pack_cascade(list(param_list), pack_fn=pack_proxy_cached)
            if dtype != "float32":
                # weight-only quantization at plan-compile time: scales
                # folded so the kernel dequantizes once per tile
                packed = quantize_cascade(packed, dtype)
        self.packed = packed
        self.dtype = packed.dtype
        self.device = current_device()
        w1, b1, w2, b2 = cascade_kernel_operands(self.packed)
        self.w1 = self._put(w1)  # (F, H*P) stacked hidden weights/codes
        self.b1 = self._put(b1)
        self.w2 = self._put(w2)  # (H*P, P) block-diagonal readout
        self.b2 = self._put(b2)
        self.out_scale = (None if self.packed.out_scale is None
                          else self._put(self.packed.out_scale))
        self.thr = self._put(np.asarray(thresholds, np.float32))
        self.families = self.packed.families
        self.n_proxies = len(param_list)
        self.n_features = int(self.w1.shape[0])
        if block_m is None:
            # roofline autotune (kernels/autotune.py): sweep candidate
            # blocks against exact per-launch byte counts at the expected
            # chunk size.  With no row hint the winner coincides with the
            # previous static 8MB-budget heuristic by construction (same
            # feasibility bound, equal bytes at every feasible block, so
            # fewer grid steps win); a small hint right-sizes the block
            # for serving chunks instead of padding 8-16x.  Cache-keyed
            # on (F, HP-bucket, P-bucket, dtype, backend, hint), so
            # repeat installs skip the sweep.
            cfg = autotune.choose_block_m(
                self.n_features, int(self.w1.shape[1]), self.n_proxies,
                self.dtype, n_rows_hint=n_rows_hint, max_tile=max_tile)
            block_m = cfg.block_m
        self.block_m = min(block_m, max_tile)
        self.interpret = interpret_default() if interpret is None else interpret
        buckets = []
        size = self.block_m
        while size < max_tile:
            buckets.append(size)
            size *= 2
        buckets.append(max_tile)
        self.buckets = tuple(buckets)
        self.max_tile = max_tile
        # stage index -> proxy column (filled by from_plan; identity default)
        self.stage_cols = list(range(self.n_proxies))

    @classmethod
    def from_plan(cls, plan, **kw):
        """Build a scorer over ALL of the plan's proxied stages (any
        family).  Returns None only when no stage carries a proxy.
        ``scorer.stage_cols[si]`` maps stage index to its proxy column, or
        None for proxy-less stages.  A plan stamped with
        ``meta["quant_dtype"]`` (optimizer flag or wire artifact) builds
        its scorer at that weight dtype unless the caller overrides.
        """
        kw.setdefault("dtype", plan.meta.get("quant_dtype", "float32"))
        params, thrs, cols = [], [], []
        for stage in plan.stages:
            if stage.proxy is not None:
                cols.append(len(params))
                params.append(stage.proxy.params)
                thrs.append(stage.threshold)
            else:
                cols.append(None)
        if not params:
            return None
        scorer = cls(params, thrs, **kw)
        scorer.stage_cols = cols
        return scorer

    def covers_all(self, plan) -> bool:
        """Every proxied stage has a column — trivially true since the
        packed format covers every registered family; kept as an API
        invariant check."""
        return all(
            col is not None
            for col, stage in zip(self.stage_cols, plan.stages)
            if stage.proxy is not None
        )

    @classmethod
    def from_plans(cls, plans, **kw):
        """Stack several plans' proxied stages into ONE packed cascade
        (multi-query serving, DESIGN.md §10).  Returns
        ``(scorer | None, col_maps)`` where ``col_maps[qi][si]`` is the
        shared-scorer column for plan ``qi``'s stage ``si`` (None for
        proxy-less stages).  Stages with byte-identical packed params AND
        threshold — keyed on the content fingerprint, never ``id()`` —
        share one column, so a predicate proxied identically by two
        queries is scored once per record, not once per query.

        Column masks are bit-identical to each plan's isolated scorer:
        the readout is block-diagonal, so a column's score sums only its
        own hidden block — every cross-block term is an exact float zero
        and stacking more columns cannot perturb the per-column sums.

        The weight storage dtype is the plans' common ``quant_dtype``
        when they agree; disagreeing tenants fall back to float32 (a
        shared launch must not silently quantize a tenant that asked for
        full precision).  ``None`` scorer means no plan has any proxied
        stage."""
        params, thrs = [], []
        col_of = {}
        col_maps = []
        for plan in plans:
            cols = []
            for stage in plan.stages:
                if stage.proxy is None:
                    cols.append(None)
                    continue
                key = (params_fingerprint(stage.proxy.params),
                       float(stage.threshold))
                col = col_of.get(key)
                if col is None:
                    col = len(params)
                    col_of[key] = col
                    params.append(stage.proxy.params)
                    thrs.append(stage.threshold)
                cols.append(col)
            col_maps.append(cols)
        if not params:
            return None, col_maps
        dtypes = {str(plan.meta.get("quant_dtype", "float32"))
                  for plan in plans}
        kw.setdefault("dtype",
                      dtypes.pop() if len(dtypes) == 1 else "float32")
        scorer = cls(params, thrs, **kw)
        scorer.stage_cols = list(range(len(params)))
        return scorer, col_maps

    def _put(self, a):
        return jax.device_put(np.asarray(a), self.device)

    def _bucket(self, n: int) -> int:
        for size in self.buckets:
            if n <= size:
                return size
        return self.max_tile

    def _pad_tile(self, x_tile: np.ndarray) -> np.ndarray:
        n = x_tile.shape[0]
        bucket = self._bucket(n)
        if n < bucket:  # bucket-pad: static shape -> no retrace
            xp = np.zeros((bucket, x_tile.shape[1]), np.float32)
            xp[:n] = x_tile
            return xp
        return np.ascontiguousarray(x_tile, np.float32)

    def _score_tile(self, x_tile: np.ndarray, need_scores: bool,
                    need_compaction: bool = True, compact_cols=None):
        n = x_tile.shape[0]
        with span("scorer.launch"):
            scores, mask, packed, counts = cascade_score(
                self._put(self._pad_tile(x_tile)), self.w1, self.b1,
                self.w2, self.b2, self.thr, n, out_scale=self.out_scale,
                block_m=self.block_m, interpret=self.interpret,
                with_scores=need_scores, with_compaction=need_compaction,
                compact_cols=compact_cols,
            )
        with span("scorer.fetch"):
            return (np.asarray(scores[:n]) if need_scores else None,
                    np.asarray(mask[:n]),
                    np.asarray(packed) if need_compaction else None,
                    np.asarray(counts) if need_compaction else None)

    def score_compact(self, x: np.ndarray, *, need_scores: bool = False,
                      compact_cols=None):
        """Score every stage over ``x`` (N, F) in one fused pass per tile.

        Returns (scores (N, P) | None, masks (N, P), packed, counts) where
        ``packed[p][:counts[p]]`` are the ascending row indices surviving
        stage p's proxy gate (dense UDF batch order).  ``scores`` is only
        fetched off device when ``need_scores`` (the engines gate on masks).

        ``compact_cols`` restricts survivor-list assembly to the named
        proxy columns (the executor only consumes the first full-tile
        stage's list); unassembled entries of ``packed`` are None.  The
        per-stage survivor ``counts`` cover every column either way.
        """
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        cols_sel = (tuple(range(self.n_proxies)) if compact_cols is None
                    else tuple(int(c) for c in compact_cols))
        kernel_cols = None if compact_cols is None else cols_sel
        if n <= self.max_tile:
            scores, masks, packed, counts = self._score_tile(
                x, need_scores, compact_cols=kernel_cols)
            out = [None] * self.n_proxies
            for ci, col in enumerate(cols_sel):
                out[col] = packed[ci, :counts[col]]
            return scores, masks, out, counts
        scores = np.empty((n, self.n_proxies), np.float32) if need_scores else None
        masks = np.empty((n, self.n_proxies), bool)
        parts = {col: [] for col in cols_sel}
        counts = np.zeros(self.n_proxies, np.int32)
        for start in range(0, n, self.max_tile):
            stop = min(start + self.max_tile, n)
            s, m, pk, cnt = self._score_tile(
                x[start:stop], need_scores, compact_cols=kernel_cols)
            if need_scores:
                scores[start:stop] = s
            masks[start:stop] = m
            counts += cnt
            for ci, col in enumerate(cols_sel):
                parts[col].append(pk[ci, :cnt[col]] + start)
        packed = [None] * self.n_proxies
        for col in cols_sel:
            packed[col] = (np.concatenate(parts[col]) if parts[col]
                           else np.empty(0, np.int32))
        return scores, masks, packed, counts

    @spanned("scorer.score")
    def score_masks(self, x: np.ndarray) -> np.ndarray:
        """Per-stage keep masks only (N, P): skips the compaction outputs
        and their device round-trips — the serving engine's submit-time
        path gates on mask rows alone."""
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        masks = np.empty((n, self.n_proxies), bool)
        for start in range(0, n, self.max_tile):
            stop = min(start + self.max_tile, n)
            _s, mask, _pk, _cnt = self._score_tile(
                x[start:stop], need_scores=False, need_compaction=False)
            masks[start:stop] = mask
        return masks

    @spanned("scorer.score")
    def score_margins(self, x: np.ndarray):
        """Masks (N, P) plus per-record distance to the NEAREST stage
        threshold (N,) — the importance-audit weight signal (records near
        any proxy decision boundary are the ones whose audited labels are
        most informative).  The min-|score - thr| reduction runs on
        device, so only an (N,) vector is fetched instead of the full
        (N, P) score matrix.  The kernel does write its (N, Pp) score
        output to HBM for this path — an in-kernel margin output could
        not be narrower anyway (TPU outputs are 128-lane minimum, the
        same width as the score tile for P <= 128), and the extra
        ~512 B/row is <0.1% of HBM bandwidth at full serving rate."""
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        masks = np.empty((n, self.n_proxies), bool)
        margins = np.empty(n, np.float32)
        for start in range(0, n, self.max_tile):
            stop = min(start + self.max_tile, n)
            tile = x[start:stop]
            m = tile.shape[0]
            with span("scorer.launch"):
                scores, mask, _pk, _cnt = cascade_score(
                    self._put(self._pad_tile(tile)), self.w1, self.b1,
                    self.w2, self.b2, self.thr, m, out_scale=self.out_scale,
                    block_m=self.block_m, interpret=self.interpret,
                    with_scores=True, with_compaction=False,
                )
            with span("scorer.fetch"):
                masks[start:stop] = np.asarray(mask[:m])
                margins[start:stop] = np.asarray(
                    jnp.min(jnp.abs(scores[:m] - self.thr[None, :]), axis=1))
        return masks, margins


# --------------------------------------------- scorer compile cache (serving)
# The adaptive server hot-swaps plans mid-stream and can oscillate between
# plan versions; each CascadeScorer carries packed weights + jit programs,
# so re-entering a previously compiled plan version must be a cache hit,
# not a repack + retrace.  Keyed on a CONTENT fingerprint of every stage's
# packed parameters — (pred, family, packed-bytes digest, threshold) — not
# on ``id(params)``: an id key would need the cache to pin the params alive
# forever (or risk a recycled id aliasing a stale compiled scorer after the
# old params are garbage-collected), whereas the fingerprint is immune to
# id reuse by construction, lets swapped-out plans' params be collected,
# and makes byte-identical params (e.g. a deserialized wire artifact of a
# plan this process already compiled) a cache hit.
_SCORER_CACHE: dict = {}
_SCORER_CACHE_MAX = 64


def params_fingerprint(params) -> str:
    """Content digest of one proxy's PACKED parameters (folded depth-1
    form, family-agnostic).  Packing is memoized (``pack_proxy_cached``),
    so the recurring cost is one blake2b over ~F*hidden floats — paid per
    plan install, never per batch."""
    import hashlib

    pk = pack_proxy_cached(params)
    h = hashlib.blake2b(digest_size=16)
    h.update(str((pk.hidden,) + tuple(pk.w1.shape)).encode())
    for a in (pk.w1, pk.b1, pk.w2):
        h.update(np.ascontiguousarray(a, np.float32).tobytes())
    h.update(np.float32(pk.b2).tobytes())
    return h.hexdigest()


def current_device():
    """The device new arrays land on: the ``jax.default_device`` in
    effect (thread-local), else the backend's first device."""
    dev = jax.config.jax_default_device
    return jax.devices()[0] if dev is None else dev


def _plan_scorer_key(plan, max_tile: int):
    # no family component: the packed fingerprint already determines the
    # compiled program bit-for-bit, so e.g. a deserialized wire copy
    # ("packed1" family) of a locally-built linear plan hits the same entry.
    # The quant dtype IS a key component: the same fp32 params packed at
    # int8 vs fp32 are different compiled programs (different codes and
    # masks), so a stale-dtype scorer must never be served.  So is the
    # device: hosts placed on different chips must not share operands.
    dev = current_device()
    return tuple(
        (s.pred_idx,
         params_fingerprint(s.proxy.params) if s.proxy is not None else None,
         float(s.threshold))
        for s in plan.stages
    ) + (int(max_tile), str(plan.meta.get("quant_dtype", "float32")),
         (dev.platform, dev.id))


def cascade_scorer_for_plan(plan, *, max_tile: int = 8192):
    """Memoized ``CascadeScorer.from_plan``.

    Returns (scorer | None, cache_hit).  None means the plan has no
    proxied stage at all (nothing to fuse) — that outcome is cached too.
    """
    key = _plan_scorer_key(plan, max_tile)
    if key in _SCORER_CACHE:
        return _SCORER_CACHE[key], True
    scorer = CascadeScorer.from_plan(plan, max_tile=max_tile)
    if len(_SCORER_CACHE) >= _SCORER_CACHE_MAX:
        _SCORER_CACHE.pop(next(iter(_SCORER_CACHE)))
    _SCORER_CACHE[key] = scorer
    return scorer, False


# ------------------------------------------------- scorer wire format (v1)
# A plan swap in multi-host serving ships a single serializable artifact:
# the plan's stage metadata + the bucket-padded packed cascade tensors +
# thresholds (DESIGN.md §6).  Layout:
#
#   b"COREWIRE" | u16 version | u16 pad | u64 header_len
#   | header (canonical JSON, utf-8) | concatenated raw array payloads
#
# Every numeric tensor travels as raw dtype bytes (descriptors in the
# header), so deserialize(serialize(x)) is BIT-exact: the receiving host's
# scorer computes the identical masks, and re-serializing a deserialized
# artifact reproduces the original bytes (tested).  Scalar floats live in
# JSON, which round-trips float64 exactly (repr-based).  Deserialized
# plans carry ``packed1``-family proxies (the folded form is the wire
# truth; the training-side parameterization never travels).
WIRE_MAGIC = b"COREWIRE"
WIRE_VERSION = 1
# v1.1: the two pad bytes after the version become a MINOR field.  Minor 0
# is the v1 scorer artifact (bytes unchanged — round-trips stay bit-exact);
# minor 1 is a control FRAME wrapping a kind-tagged payload (re-sync
# catch-up installs, replicated coordinator state deltas).  v1 readers
# never see frames (they ride the control channel, not the artifact
# broadcast), and v1.1 readers still parse v1 artifacts byte-for-byte.
WIRE_MINOR_FRAME = 1
FRAME_RESYNC = "resync"  # payload: a v1 scorer artifact for a fenced host
FRAME_DELTA = "delta"  # payload: JSON-encoded consensus StateDelta
# payload: a v1/v1.2 scorer artifact; meta: the plan-cache stats sidecar
# (fingerprint digest + stat vector, B&B candidate orders and L-node
# measurements, hit counters) — one frame per persisted cache entry, so
# the cross-query plan cache (core/plan_cache.py) survives restarts and
# ships coordinator->fleet over the same wire family as everything else
FRAME_PLANCACHE = "plancache"
# v1.2: minor 2 is a QUANTIZED scorer artifact — the packed tensors travel
# as int8 (or fp8-simulated) codes, and the scorer header gains "dtype"
# plus a per-stage "out_scale" array ref.  fp32 artifacts keep minor 0
# with byte-identical layout (no new header keys), so v1.0 readers and
# blobs are untouched; readers reject any OTHER minor explicitly rather
# than misparsing a future format.
WIRE_MINOR_QUANT = 2


class WireFormatError(ValueError):
    """Malformed / incompatible scorer artifact."""


def pack_le(value: int, width: int) -> bytes:
    """Canonical little-endian unsigned field for COREWIRE containers.

    Every integer field in the wire family (scorer artifacts, control
    frames, the plan-cache file) is encoded through this pair so the
    byte-level layout discipline lives in one module
    (corelint: wire-pack-outside-ops).
    """
    return int(value).to_bytes(width, "little")


def unpack_le(buf, start: int, width: int) -> int:
    """Inverse of :func:`pack_le`: read ``width`` bytes at ``start``."""
    return int.from_bytes(bytes(buf[start:start + width]), "little")


class _ArrayPool:
    """Array blob registry for one serialization pass."""

    def __init__(self):
        self.descs: list = []
        self.blobs: list = []
        self._offset = 0

    def put(self, a: np.ndarray) -> int:
        a = np.ascontiguousarray(a)
        raw = a.tobytes()
        self.descs.append({
            "dtype": a.dtype.str, "shape": list(a.shape),
            "offset": self._offset, "nbytes": len(raw),
        })
        self.blobs.append(raw)
        self._offset += len(raw)
        return len(self.descs) - 1


def _pool_get(descs, payload: memoryview, ref: int) -> np.ndarray:
    d = descs[ref]
    a = np.frombuffer(
        payload[d["offset"]:d["offset"] + d["nbytes"]], dtype=np.dtype(d["dtype"])
    )
    return a.reshape(d["shape"]).copy()


def serialize_scorer(plan, scorer=None, *, max_tile: int = 8192) -> bytes:
    """Pack ``(plan, fused scorer)`` into the versioned wire artifact.

    ``scorer=None`` builds (or cache-hits) the plan's scorer first.  Only
    fully-proxied-or-proxyless stage metadata plus the packed cascade
    travels — never UDFs (the receiving host binds its own ``Query``).
    """
    import json

    if scorer is None:
        scorer, _ = cascade_scorer_for_plan(plan, max_tile=max_tile)
    if scorer is None:
        raise WireFormatError("plan has no proxied stage: nothing to ship")
    pool = _ArrayPool()
    packed = scorer.packed
    src_families = plan.meta.get("wire_src_families") or tuple(
        s.proxy.family for s in plan.stages if s.proxy is not None)
    stages = []
    for s in plan.stages:
        entry = {
            "pred_idx": int(s.pred_idx), "alpha": float(s.alpha),
            "threshold": float(s.threshold),
            "est_reduction": float(s.est_reduction),
            "est_selectivity": float(s.est_selectivity),
            "est_cost": float(s.est_cost),
            "proxy": None,
        }
        if s.proxy is not None:
            rc = s.proxy.r_curve
            entry["proxy"] = {
                "d": [int(i) for i in s.proxy.d],
                "cost": float(s.proxy.cost),
                "train_f1": float(s.proxy.train_f1),
                "n_train": int(s.proxy.n_train),
                "r_curve": {
                    "alphas": pool.put(np.asarray(rc.alphas)),
                    "thresholds": pool.put(np.asarray(rc.thresholds)),
                    "reductions": pool.put(np.asarray(rc.reductions)),
                },
            }
        stages.append(entry)
    header = {
        "wire_version": WIRE_VERSION,
        "plan": {
            "stages": stages,
            "est_total_cost": float(plan.est_total_cost),
            "plan_version": int(plan.meta.get("plan_version", 0)),
            "accuracy_target": float(plan.query.accuracy_target),
            "n_predicates": int(plan.query.n),
            "src_families": list(src_families),
        },
        "scorer": {
            "w1": pool.put(packed.w1), "b1": pool.put(packed.b1),
            "w2": pool.put(packed.w2), "b2": pool.put(packed.b2),
            "thr": pool.put(np.asarray(scorer.thr, np.float32)),
            "hidden": [int(h) for h in packed.hidden],
            "stage_cols": [None if c is None else int(c)
                           for c in scorer.stage_cols],
            "block_m": int(scorer.block_m),
            "max_tile": int(scorer.max_tile),
        },
        "arrays": pool.descs,
    }
    # v1.2 quantized artifact: dtype + per-stage readout scales ride the
    # header; minor stays 0 for fp32 so those blobs are byte-identical to
    # every earlier release (round-trip tests pin this).
    minor = 0
    if packed.dtype != "float32":
        minor = WIRE_MINOR_QUANT
        header["scorer"]["dtype"] = str(packed.dtype)
        header["scorer"]["out_scale"] = pool.put(
            np.asarray(packed.out_scale, np.float32))
    hdr = json.dumps(header, sort_keys=True,
                     separators=(",", ":")).encode("utf-8")
    out = bytearray()
    out += WIRE_MAGIC
    out += int(WIRE_VERSION).to_bytes(2, "little")
    out += int(minor).to_bytes(2, "little")
    out += len(hdr).to_bytes(8, "little")
    out += hdr
    for raw in pool.blobs:
        out += raw
    return bytes(out)


def serialize_frame(kind: str, epoch: int, payload: bytes,
                    meta: dict | None = None) -> bytes:
    """Wrap a control payload in a COREWIRE v1.1 frame:

      b"COREWIRE" | u16 major=1 | u16 minor=1 | u64 header_len
      | header JSON {"kind", "epoch", "meta", "payload_len"} | payload

    Frames carry the fault-tolerance control plane — re-sync catch-up
    artifacts for fenced hosts (``FRAME_RESYNC``, payload = a v1 scorer
    artifact) and replicated coordinator state deltas (``FRAME_DELTA``)
    — over the same wire family as the artifact broadcast.  Minor-version
    discrimination keeps it backward-compatible: a v1 scorer blob's bytes
    are untouched, and ``deserialize_scorer`` rejects frames explicitly
    instead of misparsing them."""
    import json

    hdr = json.dumps(
        {"kind": str(kind), "epoch": int(epoch), "meta": meta or {},
         "payload_len": len(payload)},
        sort_keys=True, separators=(",", ":")).encode("utf-8")
    out = bytearray()
    out += WIRE_MAGIC
    out += int(WIRE_VERSION).to_bytes(2, "little")
    out += int(WIRE_MINOR_FRAME).to_bytes(2, "little")
    out += len(hdr).to_bytes(8, "little")
    out += hdr
    out += payload
    return bytes(out)


def deserialize_frame(blob: bytes):
    """Inverse of ``serialize_frame``: returns (kind, epoch, payload,
    meta).  Raises ``WireFormatError`` on v1 artifacts (minor 0) so the
    two channels cannot be confused."""
    import json

    if blob[:len(WIRE_MAGIC)] != WIRE_MAGIC:
        raise WireFormatError("bad magic: not a COREWIRE frame")
    ver = int.from_bytes(blob[8:10], "little")
    minor = int.from_bytes(blob[10:12], "little")
    if ver != WIRE_VERSION or minor != WIRE_MINOR_FRAME:
        raise WireFormatError(
            f"wire {ver}.{minor} is not a v{WIRE_VERSION}.{WIRE_MINOR_FRAME} "
            f"control frame")
    hdr_len = int.from_bytes(blob[12:20], "little")
    header = json.loads(blob[20:20 + hdr_len].decode("utf-8"))
    payload = bytes(blob[20 + hdr_len:])
    if len(payload) != int(header["payload_len"]):
        raise WireFormatError(
            f"frame payload truncated: {len(payload)} != "
            f"{header['payload_len']}")
    return header["kind"], int(header["epoch"]), payload, header["meta"]


def deserialize_scorer(blob: bytes, query):
    """Inverse of ``serialize_scorer``: rebuild ``(plan, scorer)`` against
    the locally-bound ``query``.  The scorer's packed tensors, thresholds,
    and therefore every keep decision are bit-identical to the sender's;
    proxies come back as first-class ``packed1``-family models (reference
    scoring and the per-stage kernel fallback both still work)."""
    import json

    from repro.core.proxy import ProxyModel, RCurve
    from repro.core.proxy_family import unpack_cascade
    from repro.core.query import PhysicalPlan, PlanStage

    if blob[:len(WIRE_MAGIC)] != WIRE_MAGIC:
        raise WireFormatError("bad magic: not a CORE scorer artifact")
    ver = int.from_bytes(blob[8:10], "little")
    if ver != WIRE_VERSION:
        raise WireFormatError(f"wire version {ver} != supported {WIRE_VERSION}")
    minor = int.from_bytes(blob[10:12], "little")
    if minor == WIRE_MINOR_FRAME:
        raise WireFormatError(
            f"wire minor {minor} is a control frame, not a scorer artifact "
            f"(use deserialize_frame)")
    if minor not in (0, WIRE_MINOR_QUANT):
        raise WireFormatError(
            f"unknown wire minor {minor}: this reader supports scorer "
            f"artifacts v{WIRE_VERSION}.0 (fp32) and "
            f"v{WIRE_VERSION}.{WIRE_MINOR_QUANT} (quantized)")
    hdr_len = int.from_bytes(blob[12:20], "little")
    header = json.loads(blob[20:20 + hdr_len].decode("utf-8"))
    payload = memoryview(blob)[20 + hdr_len:]
    descs = header["arrays"]
    ph = header["plan"]
    if int(ph["n_predicates"]) != query.n:
        raise WireFormatError(
            f"artifact built for {ph['n_predicates']} predicates; local "
            f"query has {query.n}")
    if abs(float(ph["accuracy_target"]) - float(query.accuracy_target)) > 1e-12:
        raise WireFormatError("artifact/query accuracy targets differ")
    sh = header["scorer"]
    from repro.core.proxy_family import PackedCascade

    quant_dtype = str(sh.get("dtype", "float32"))
    packed = PackedCascade(
        w1=_pool_get(descs, payload, sh["w1"]),
        b1=_pool_get(descs, payload, sh["b1"]),
        w2=_pool_get(descs, payload, sh["w2"]),
        b2=_pool_get(descs, payload, sh["b2"]),
        hidden=tuple(int(h) for h in sh["hidden"]),
        families=tuple(ph["src_families"]),
        dtype=quant_dtype,
        out_scale=(_pool_get(descs, payload, sh["out_scale"])
                   if minor == WIRE_MINOR_QUANT else None),
    )
    thr = _pool_get(descs, payload, sh["thr"])
    params_by_col = [unpack_cascade(packed, c) for c in range(packed.n_stages)]
    stages = []
    for st in ph["stages"]:
        proxy = None
        col = sh["stage_cols"][len(stages)]
        if st["proxy"] is not None:
            if col is None:
                raise WireFormatError("proxied stage without a scorer column")
            rc = st["proxy"]["r_curve"]
            proxy = ProxyModel(
                pred_idx=int(st["pred_idx"]),
                d=tuple(st["proxy"]["d"]),
                family="packed1",
                params=params_by_col[col],
                r_curve=RCurve(
                    alphas=_pool_get(descs, payload, rc["alphas"]),
                    thresholds=_pool_get(descs, payload, rc["thresholds"]),
                    reductions=_pool_get(descs, payload, rc["reductions"]),
                ),
                cost=float(st["proxy"]["cost"]),
                train_f1=float(st["proxy"]["train_f1"]),
                n_train=int(st["proxy"]["n_train"]),
            )
        stages.append(PlanStage(
            pred_idx=int(st["pred_idx"]), proxy=proxy,
            alpha=float(st["alpha"]), threshold=float(st["threshold"]),
            est_reduction=float(st["est_reduction"]),
            est_selectivity=float(st["est_selectivity"]),
            est_cost=float(st["est_cost"]),
        ))
    meta = {
        "mode": "wire",
        "plan_version": int(ph["plan_version"]),
        "wire_src_families": tuple(ph["src_families"]),
    }
    if quant_dtype != "float32":
        meta["quant_dtype"] = quant_dtype
    plan = PhysicalPlan(
        query=query, stages=stages,
        est_total_cost=float(ph["est_total_cost"]),
        meta=meta,
    )
    # packed= hands the wire codes straight to the scorer — no re-pack,
    # no re-quantize — so the receiving host's masks are bit-identical to
    # the sender's and re-serializing reproduces the original bytes
    scorer = CascadeScorer(
        [params_by_col[c] for c in range(packed.n_stages)], thr,
        block_m=int(sh["block_m"]), max_tile=int(sh["max_tile"]),
        packed=packed,
    )
    scorer.stage_cols = [None if c is None else int(c)
                         for c in sh["stage_cols"]]
    return plan, scorer


# ------------------------------------------------------ quant parity gate
def quant_parity_report(plan, x, *, dtype: str = "int8",
                        calib_frac: float = 0.5,
                        max_tile: int = 8192) -> dict:
    """Decision-flip audit of a quantized cascade against its fp32 twin.

    The contract (DESIGN.md §3): quantization may flip a keep decision
    ONLY for records whose fp32 score sits within ``tol`` of the stage
    threshold, where ``tol`` is calibrated as 2x the max |quant - fp32|
    score error over the first ``calib_frac`` of ``x`` and VALIDATED on
    the held-out remainder.  Records with real margin must be untouched.

    Returns a report dict; ``flips_within_tol`` is the gate bit, the
    rest (score errors, per-stage selectivity deltas) are advisory.
    """
    x = np.asarray(x, np.float32)
    f32 = CascadeScorer.from_plan(plan, max_tile=max_tile, dtype="float32")
    if f32 is None:
        raise ValueError("plan has no proxied stage: nothing to audit")
    qs = CascadeScorer.from_plan(plan, max_tile=max_tile, dtype=dtype)
    n_cal = int(np.clip(int(len(x) * calib_frac), 1, len(x) - 1))
    thr = np.asarray(f32.thr)

    def _scores_masks(scorer, chunk):
        s, m, _pk, _cnt = scorer.score_compact(chunk, need_scores=True)
        return s, m

    s_f, m_f = _scores_masks(f32, x[:n_cal])
    s_q, _ = _scores_masks(qs, x[:n_cal])
    tol = 2.0 * float(np.max(np.abs(s_q - s_f)))
    ev_f, mask_f = _scores_masks(f32, x[n_cal:])
    ev_q, mask_q = _scores_masks(qs, x[n_cal:])
    flips = mask_f != mask_q
    near = np.abs(ev_f - thr[None, :]) <= tol
    sel_f = mask_f.mean(axis=0)
    sel_q = mask_q.mean(axis=0)
    return {
        "dtype": dtype,
        "tol": tol,
        "max_err_calib": float(np.max(np.abs(s_q - s_f))),
        "max_err_eval": float(np.max(np.abs(ev_q - ev_f))),
        "n_eval": int(flips.shape[0]),
        "n_flips": int(flips.sum()),
        "flip_rate": float(flips.mean()),
        "flips_within_tol": bool(np.all(near[flips])),
        "max_sel_delta": float(np.max(np.abs(sel_f - sel_q))),
        "sel_fp32": [float(v) for v in sel_f],
        "sel_quant": [float(v) for v in sel_q],
    }


# -------------------------------------------------------------- attention
def attention(q, k, v, *, causal=True):
    return flash_attention(q, k, v, causal=causal, interpret=interpret_default())


# ------------------------------------------------------------------- SSD
def ssd(x, dt, A_log, B, C, D, chunk: int):
    """Full SSD forward built on the chunk kernel + jnp inter-chunk scan.

    Same signature/semantics as models.ssm.ssd_chunked (b, s, h, p)...
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = s // chunk
    A = -jnp.exp(A_log.astype(jnp.float32))
    dA = dt.astype(jnp.float32) * A[None, None, :]
    xdt = (x * dt[..., None].astype(x.dtype)).reshape(b, nc, chunk, h, p)
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).reshape(b, nc, chunk, h, n)
    Ch = jnp.repeat(C, rep, axis=2).reshape(b, nc, chunk, h, n)
    dAc = dA.reshape(b, nc, chunk, h)

    def per_batch(args):
        xb, dab, bb, cb = args
        return ssd_chunk(xb, dab, bb, cb, interpret=interpret_default())

    # vmap over batch: kernel grid covers (nc*h); batch handled by vmap
    y_diag, states, chunk_decay = jax.vmap(
        lambda xb, dab, bb, cb: ssd_chunk(xb, dab, bb, cb, interpret=interpret_default())
    )(xdt, dAc, Bh, Ch)
    # inter-chunk recurrence (nc steps, tiny)
    def scan_body(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry

    from jax import lax

    final, prev = lax.scan(
        scan_body,
        jnp.zeros((b, h, p, n), jnp.float32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev = prev.transpose(1, 0, 2, 3, 4)  # (b, nc, h, p, n)
    cum = jnp.cumsum(dAc.transpose(0, 3, 1, 2), axis=-1)  # (b, h, nc, Q)
    state_decay_out = jnp.exp(cum)
    y_off = jnp.einsum("bcqhn,bchpn,bhcq->bcqhp", Ch.astype(jnp.float32), prev, state_decay_out)
    y = (y_diag + y_off).reshape(b, s, h, p)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), final
