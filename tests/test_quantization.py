"""Quantized packed cascades + roofline autotune (ISSUE 6).

* quantize_cascade: symmetric per-column scales, int8 codes, folded
  readout — dequantized form tracks fp32 within the scale bound; the
  linear +/- trick's negation symmetry survives quantization exactly;
  pad columns stay inert (scale 1, zero codes);
* the fused kernel on int8 codes matches the quantized jnp oracle
  bit-for-bit, and the fp32 path is bit-identical to pre-quantization
  (the out_scale multiply is an IEEE identity at ones);
* decision-flip parity: masks differ from fp32 only on rows within the
  calibrated threshold tolerance;
* COREWIRE v1.2: quantized artifacts round-trip bit-exact at minor 2,
  fp32 artifacts keep minor 0 with an unchanged byte layout, unknown
  minors are rejected explicitly;
* compile-cache keys: same params at int8 vs fp32 are DISTINCT entries
  (no stale-dtype scorer), byte-identical quantized artifacts cache-HIT;
* autotune: full-tile hint reproduces the old static heuristic, small
  serving chunks choose smaller (faster-modeled) blocks, winners are
  cache-keyed (memory + disk), feasibility bound respected;
* estimate_order_regret is stable under quantization noise (<= tol, same
  chosen order), including the >6-stage greedy fallback;
* a quantized plan survives the full K=4 distributed path: quorum swap,
  hot-swap install, fused scoring — conservation and epoch agreement
  unchanged, dtype preserved through reoptimize + re-serialize.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from repro.core import execute_plan, optimize
from repro.core.proxy_family import (
    QUANT_WEIGHT_BYTES,
    cascade_kernel_operands,
    pack_cascade,
    quantize_cascade,
    unpack_cascade,
)
from repro.data.synthetic import (
    make_dataset,
    make_query,
    make_sharded_drifting_streams,
    make_udfs,
)
from repro.kernels import autotune, ref
from repro.kernels.ops import (
    CascadeScorer,
    WIRE_MINOR_QUANT,
    WireFormatError,
    cascade_scorer_for_plan,
    deserialize_frame,
    deserialize_scorer,
    quant_parity_report,
    serialize_scorer,
)
from repro.training.proxy_models import LinearParams, MLPParams

jnp = pytest.importorskip("jax.numpy")


def _linear(rng, F):
    return LinearParams(
        w=rng.randn(F).astype(np.float32), b=np.float32(rng.randn()),
        mean=rng.randn(F).astype(np.float32),
        scale=(np.abs(rng.randn(F)) + 0.5).astype(np.float32))


def _mlp(rng, F, H):
    return MLPParams(
        w1=rng.randn(F, H).astype(np.float32),
        b1=rng.randn(H).astype(np.float32),
        w2=rng.randn(H).astype(np.float32), b2=np.float32(rng.randn()),
        mean=rng.randn(F).astype(np.float32),
        scale=(np.abs(rng.randn(F)) + 0.5).astype(np.float32))


@pytest.fixture(scope="module")
def workload():
    ds = make_dataset(n=9000, n_features=64, n_columns=3, correlation=0.9,
                      feature_noise=0.9, label_noise=0.2, seed=41)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1200, seed=41,
                     declared_cost_ms=10.0)
    q = make_query(ds, udfs, columns=[0, 1, 2], target_selectivity=0.5,
                   accuracy_target=0.9, seed=42)
    return ds, q


@pytest.fixture(scope="module")
def mixed_plan(workload):
    ds, q = workload
    return optimize(q, ds.x[:1200], mode="core-a", step=0.05, kind="mixed")


# --------------------------------------------------------- quantize math
@given(f=st.integers(3, 32), n_stages=st.integers(1, 4),
       seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_quantize_codes_scales_and_bound(f, n_stages, seed):
    """int8 codes bounded, scales positive, pad columns inert, and the
    dequantized cascade tracks the fp32 one within the per-column scale
    bound (half an int8 step in w1, one out_scale step in the readout)."""
    rng = np.random.RandomState(seed)
    params = [(_linear(rng, f) if rng.rand() < 0.5
               else _mlp(rng, f, rng.randint(1, 24)))
              for _ in range(n_stages)]
    packed = pack_cascade(params)
    qp = quantize_cascade(packed, "int8")
    assert qp.dtype == "int8" and qp.w1.dtype == np.int8
    assert qp.out_scale is not None and qp.out_scale.shape == (qp.n_stages,)
    assert np.all(qp.out_scale > 0)
    assert np.abs(qp.w1).max() <= 127 and np.abs(qp.w2).max() <= 127
    np.testing.assert_array_equal(qp.b2, packed.b2)  # biases never quantized
    # reconstruct: w1 codes * s1 must sit within s1/2 of the fp32 weights
    a1 = np.max(np.abs(packed.w1), axis=0)
    s1 = np.where(a1 > 0, a1 / 127.0, 1.0)
    assert np.all(np.abs(qp.w1 * s1[None] - packed.w1) <= s1[None] * 0.5 + 1e-7)
    # pad columns (hidden >= stage width) carry zero codes and scale 1
    for col, p in enumerate(params):
        h = packed.hidden[col]
        assert not qp.w1[:, h:, col].any()
        assert not qp.w2[h:, col].any()
        np.testing.assert_array_equal(s1[h:, col], 1.0)


def test_pm_trick_negation_survives_int8():
    """Paired +/- hidden columns of a linear stage share a max-abs, so
    their int8 codes are exact negations — the trick's cancellation
    property is preserved under quantization, not just approximated."""
    rng = np.random.RandomState(7)
    packed = quantize_cascade(pack_cascade([_linear(rng, 16)]), "int8")
    np.testing.assert_array_equal(packed.w1[:, 0, 0],
                                  -packed.w1[:, 1, 0].astype(np.int16)
                                  .astype(np.int8))
    assert packed.w2[0, 0] == -packed.w2[1, 0]


def test_fp8_codes_live_on_e4m3_grid():
    """fp8-simulated codes are exactly representable in float8_e4m3fn and
    clipped at the format's +-448 max."""
    ml_dtypes = pytest.importorskip("ml_dtypes")
    rng = np.random.RandomState(3)
    packed = quantize_cascade(pack_cascade([_mlp(rng, 12, 9)]), "fp8")
    assert packed.dtype == "fp8"
    for a in (packed.w1, packed.w2):
        grid = a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
        np.testing.assert_array_equal(grid, a)
        assert np.abs(a).max() <= 448.0
    with pytest.raises(ValueError):
        quantize_cascade(packed, "int8")  # double-quantize is an error
    with pytest.raises(ValueError):
        quantize_cascade(pack_cascade([_mlp(rng, 4, 3)]), "int4")


def test_unpack_dequantizes_per_stage():
    """unpack_cascade of a quantized cascade returns the fp32-equivalent
    per-proxy form (readout folded through out_scale), so reference
    scoring of a wire-deserialized quantized proxy matches the kernel."""
    rng = np.random.RandomState(11)
    params = [_mlp(rng, 10, 6), _linear(rng, 10)]
    qp = quantize_cascade(pack_cascade(params), "int8")
    x = rng.randn(64, 10).astype(np.float32)
    w1, b1, w2, b2 = cascade_kernel_operands(qp)
    hid = np.maximum(x @ w1.astype(np.float32) + b1, 0.0)
    kernel_scores = hid @ w2.astype(np.float32) * qp.out_scale[None] + b2
    from repro.training.proxy_models import packed_score

    for col in range(2):
        pp = unpack_cascade(qp, col)
        assert pp.w2.dtype == np.float32
        np.testing.assert_allclose(packed_score(pp, x),
                                   kernel_scores[:, col], rtol=1e-5,
                                   atol=1e-4)


# ----------------------------------------------------------- kernel parity
def test_kernel_matches_quantized_oracle():
    """The fused kernel over int8 codes + out_scale agrees with the jnp
    oracle on the same quantized operands: masks bit-identical (the repo's
    standing fused-vs-ref contract), scores to f32 rounding (XLA picks
    M-dependent matmul micro-kernels, so the last-bit sum order differs
    between a 128-row tile and the full array)."""
    rng = np.random.RandomState(5)
    params = [_linear(rng, 20), _mlp(rng, 20, 7), _mlp(rng, 20, 3)]
    thr = rng.randn(3).astype(np.float32)
    x = rng.randn(300, 20).astype(np.float32)
    scorer = CascadeScorer(params, thr, block_m=128, max_tile=512,
                           dtype="int8")
    s, m, _pk, _cnt = scorer.score_compact(x, need_scores=True)
    w1, b1, w2, b2 = cascade_kernel_operands(scorer.packed)
    assert w1.dtype == np.int8 and w2.dtype == np.int8
    sref, mref, _ = ref.cascade_score_ref(
        jnp.asarray(x), jnp.asarray(w1), jnp.asarray(b1), jnp.asarray(w2),
        jnp.asarray(b2), jnp.asarray(thr),
        out_scale=jnp.asarray(scorer.packed.out_scale))
    np.testing.assert_allclose(s, np.asarray(sref), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(m, np.asarray(mref))


def test_fp32_path_bit_exact_through_out_scale_identity():
    """The ones out_scale multiply must not perturb a single bit of the
    fp32 path (x * 1.0 is an IEEE identity): the kernel with out_scale
    omitted and with explicit ones produces identical bytes, so every
    pre-quantization mask/score artifact is reproduced verbatim."""
    from repro.kernels.proxy_score import cascade_score

    rng = np.random.RandomState(9)
    params = [_linear(rng, 16), _mlp(rng, 16, 5)]
    thr = rng.randn(2).astype(np.float32)
    x = rng.randn(257, 16).astype(np.float32)
    scorer = CascadeScorer(params, thr, block_m=128, max_tile=512)
    assert scorer.dtype == "float32" and scorer.out_scale is None
    args = (jnp.asarray(x), scorer.w1, scorer.b1, scorer.w2, scorer.b2,
            scorer.thr, x.shape[0])
    s0, m0, _p0, _c0 = cascade_score(*args, block_m=128)
    s1, m1, _p1, _c1 = cascade_score(
        *args, out_scale=jnp.ones_like(scorer.b2), block_m=128)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))


def test_int8_scores_block_m_invariant():
    """Tiling must not change quantized results: the same int8 cascade
    scored at block 128 vs one 512-row tile is bit-identical per row."""
    rng = np.random.RandomState(21)
    params = [_mlp(rng, 24, 9), _linear(rng, 24)]
    thr = rng.randn(2).astype(np.float32)
    x = rng.randn(500, 24).astype(np.float32)
    s_a = CascadeScorer(params, thr, block_m=128, max_tile=512, dtype="int8")
    s_b = CascadeScorer(params, thr, block_m=512, max_tile=512, dtype="int8")
    sa, ma, _pa, _ca = s_a.score_compact(x, need_scores=True)
    sb, mb, _pb, _cb = s_b.score_compact(x, need_scores=True)
    np.testing.assert_array_equal(ma, mb)
    np.testing.assert_allclose(sa, sb, rtol=1e-6, atol=1e-6)


def test_quant_parity_flips_only_near_threshold(workload, mixed_plan):
    """The parity gate on a real mixed plan: every decision flip lies
    within the calibrated tolerance of a stage threshold, and per-stage
    selectivities barely move."""
    ds, _q = workload
    rep = quant_parity_report(mixed_plan, ds.x[1200:5200], dtype="int8")
    assert rep["flips_within_tol"]
    assert rep["max_sel_delta"] <= 0.02
    assert rep["max_err_eval"] <= 2.0 * rep["tol"]  # calib generalizes


def test_quant_end_to_end_accuracy_within_half_point(workload, mixed_plan):
    """Acceptance: end-to-end cascade accuracy within 0.5pt of fp32."""
    from repro.core import orig_plan

    ds, q = workload
    x = ds.x[1200:4200]
    plan_q = dataclasses.replace(
        mixed_plan, meta={**mixed_plan.meta, "quant_dtype": "int8"})
    truth = set(execute_plan(orig_plan(q), x).passed.tolist())
    acc_f = sum(1 for i in execute_plan(mixed_plan, x).passed.tolist()
                if i in truth) / max(len(truth), 1)
    acc_q = sum(1 for i in execute_plan(plan_q, x).passed.tolist()
                if i in truth) / max(len(truth), 1)
    assert abs(acc_f - acc_q) <= 0.005


# ------------------------------------------------------------ wire v1.2
def test_wire_quant_roundtrip_bit_exact(workload, mixed_plan):
    ds, q = workload
    plan_q = dataclasses.replace(
        mixed_plan, meta={**mixed_plan.meta, "quant_dtype": "int8"})
    blob = serialize_scorer(plan_q)
    assert int.from_bytes(blob[10:12], "little") == WIRE_MINOR_QUANT
    plan2, scorer2 = deserialize_scorer(blob, q)
    assert scorer2.dtype == "int8"
    assert plan2.meta["quant_dtype"] == "int8"
    assert scorer2.packed.w1.dtype == np.int8
    assert serialize_scorer(plan2, scorer2) == blob  # bit-exact round trip
    scorer1, _ = cascade_scorer_for_plan(plan_q)
    x = ds.x[2000:3000]
    np.testing.assert_array_equal(scorer1.score_masks(x),
                                  scorer2.score_masks(x))
    np.testing.assert_array_equal(
        np.asarray(scorer1.out_scale), np.asarray(scorer2.out_scale))


def test_wire_fp32_layout_unchanged_minor0(workload, mixed_plan):
    """fp32 artifacts must stay bit-for-bit what a v1.0 writer produced:
    minor 0, no quant header keys — an old blob deserializes unchanged."""
    import json

    ds, q = workload
    blob = serialize_scorer(mixed_plan)
    assert int.from_bytes(blob[10:12], "little") == 0
    hdr_len = int.from_bytes(blob[12:20], "little")
    header = json.loads(blob[20:20 + hdr_len].decode("utf-8"))
    assert "dtype" not in header["scorer"]
    assert "out_scale" not in header["scorer"]
    plan2, scorer2 = deserialize_scorer(blob, q)
    assert scorer2.dtype == "float32" and scorer2.out_scale is None
    assert "quant_dtype" not in plan2.meta
    assert serialize_scorer(plan2, scorer2) == blob


def test_wire_unknown_minor_rejected(workload, mixed_plan):
    ds, q = workload
    blob = serialize_scorer(mixed_plan)
    future = blob[:10] + (3).to_bytes(2, "little") + blob[12:]
    with pytest.raises(WireFormatError, match="unknown wire minor"):
        deserialize_scorer(future, q)
    framed = blob[:10] + (1).to_bytes(2, "little") + blob[12:]
    with pytest.raises(WireFormatError, match="control frame"):
        deserialize_scorer(framed, q)
    # and the frame channel refuses quantized artifacts symmetrically
    plan_q = dataclasses.replace(
        mixed_plan, meta={**mixed_plan.meta, "quant_dtype": "int8"})
    with pytest.raises(WireFormatError):
        deserialize_frame(serialize_scorer(plan_q))


# ------------------------------------------------- fingerprints / caching
def test_cache_key_distinct_per_dtype(workload, mixed_plan):
    """Same fp32 params packed at int8 vs fp32 must be DISTINCT cache
    entries — a stale-dtype scorer must never be served."""
    from repro.kernels import ops

    plan_q = dataclasses.replace(
        mixed_plan, meta={**mixed_plan.meta, "quant_dtype": "int8"})
    assert ops._plan_scorer_key(mixed_plan, 8192) != \
        ops._plan_scorer_key(plan_q, 8192)
    ops._SCORER_CACHE.clear()
    s_f, hit_f = cascade_scorer_for_plan(mixed_plan)
    s_q, hit_q = cascade_scorer_for_plan(plan_q)
    assert not hit_f and not hit_q and s_f is not s_q
    assert s_f.dtype == "float32" and s_q.dtype == "int8"
    # re-entry of each is its own hit
    assert cascade_scorer_for_plan(mixed_plan) == (s_f, True)
    assert cascade_scorer_for_plan(plan_q) == (s_q, True)


def test_identical_quant_artifacts_cache_hit(workload, mixed_plan):
    """Two byte-identical quantized artifacts deserialize to plans whose
    scorers share one compile-cache entry (content keying, dtype-aware)."""
    from repro.kernels import ops

    ds, q = workload
    plan_q = dataclasses.replace(
        mixed_plan, meta={**mixed_plan.meta, "quant_dtype": "int8"})
    blob = serialize_scorer(plan_q)
    plan_a, _ = deserialize_scorer(bytes(blob), q)
    plan_b, _ = deserialize_scorer(bytes(blob), q)
    ops._SCORER_CACHE.clear()
    s_a, hit_a = cascade_scorer_for_plan(plan_a)
    s_b, hit_b = cascade_scorer_for_plan(plan_b)
    assert not hit_a and hit_b and s_a is s_b


def test_params_fingerprint_unchanged_for_fp32(workload, mixed_plan):
    """Quantization plumbing must not churn existing fp32 fingerprints
    (they key live serving caches across plan swaps)."""
    from repro.kernels.ops import params_fingerprint

    p = mixed_plan.stages[0].proxy.params
    assert params_fingerprint(p) == params_fingerprint(p)


# --------------------------------------------------------------- autotune
def test_autotune_full_tile_matches_static_heuristic():
    """With no row hint the tuner reproduces the old static pick exactly
    — existing compiled-program caches and tests see no block change."""
    for (f, hp, p) in [(64, 128, 2), (64, 256, 4), (256, 1024, 8),
                       (32, 64, 2), (128, 2048, 16)]:
        static = autotune.static_heuristic_block_m(f, hp, p)
        cfg = autotune.choose_block_m(f, hp, p, "float32", backend="test")
        assert cfg.block_m == static, (f, hp, p)
        assert cfg.static_block_m == static


def test_autotune_small_chunk_picks_smaller_block():
    """A serving-chunk hint far below the static block chooses a smaller
    block with strictly less modeled time and fewer padded rows."""
    static = autotune.static_heuristic_block_m(64, 128, 2)
    assert static >= 2048  # precondition: the old rule over-blocks here
    cfg = autotune.choose_block_m(64, 128, 2, "int8", n_rows_hint=256,
                                  backend="test")
    assert cfg.block_m <= 256
    stat_cell = autotune.cell_model(64, 128, 2, "int8", static, 256)
    assert cfg.t_model_s < stat_cell.t_model_s
    assert cfg.bytes_moved < stat_cell.bytes_moved


def test_autotune_feasibility_and_weight_bytes():
    """Chosen blocks respect the 8MB per-block budget; int8 weight bytes
    are a quarter of fp32's in the model."""
    for hint in (None, 256, 8192):
        cfg = autotune.choose_block_m(256, 4096, 32, "float32",
                                      n_rows_hint=hint, backend="test")
        hpp = -(-4096 // 128) * 128
        pp = -(-32 // 128) * 128
        per_row = 4 * (256 + hpp) + 9 * pp
        assert per_row * cfg.block_m <= autotune.VMEM_BLOCK_BUDGET
    c_f = autotune.cell_model(64, 512, 4, "float32", 256, 256)
    c_q = autotune.cell_model(64, 512, 4, "int8", 256, 256)
    assert c_f.bytes_moved > c_q.bytes_moved
    assert QUANT_WEIGHT_BYTES["int8"] * 4 == QUANT_WEIGHT_BYTES["float32"]


def test_autotune_cache_hits_and_disk_persistence(tmp_path, monkeypatch):
    """Repeat lookups are cache hits (no re-sweep); with
    CORE_AUTOTUNE_CACHE set the table survives a cleared in-memory cache
    (a fresh process would skip the sweep too)."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("CORE_AUTOTUNE_CACHE", str(path))
    autotune.clear_autotune_cache()
    autotune.reset_autotune_stats()
    cfg1 = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="test")
    assert cfg1.source == "sweep"
    cfg2 = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="test")
    assert cfg2.source == "cache" and cfg2.block_m == cfg1.block_m
    stats = autotune.autotune_stats()
    assert stats["sweeps"] == 1 and stats["hits"] == 1
    assert path.exists()
    # simulate a fresh process: clear memory, reload from disk
    autotune.clear_autotune_cache()
    autotune.reset_autotune_stats()
    cfg3 = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="test")
    assert cfg3.source == "cache" and cfg3.block_m == cfg1.block_m
    assert autotune.autotune_stats()["sweeps"] == 0
    autotune.clear_autotune_cache()


def test_autotune_disk_cache_concurrent_writer_merges(tmp_path, monkeypatch):
    """K subprocess hosts share one CORE_AUTOTUNE_CACHE file.  A host
    that loaded the (empty) table BEFORE a peer's save lands must not
    clobber the peer's entries when it saves its own sweep: merge-on-save
    re-reads the file immediately before the atomic replace, so both
    shapes survive."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("CORE_AUTOTUNE_CACHE", str(path))
    # peer host sweeps shape B and publishes it
    autotune.clear_autotune_cache()
    cfg_b = autotune.choose_block_m(64, 384, 8, "float32", n_rows_hint=256,
                                    backend="test")
    assert len(autotune._read_disk_table(str(path))) == 1
    # our host: fresh memory, but it "loaded" the disk table before the
    # peer's save landed (the concurrent interleave) — its save must
    # still keep the peer's shape-B entry alongside our shape-A sweep
    autotune.clear_autotune_cache()
    autotune._DISK_LOADED = True
    cfg_a = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                    backend="test")
    assert cfg_a.source == "sweep"
    merged = autotune._read_disk_table(str(path))
    assert len(merged) == 2
    blocks = {(k[1], k[3]): v.block_m for k, v in merged.items()}
    assert blocks[(384, "float32")] == cfg_b.block_m
    assert blocks[(256, "int8")] == cfg_a.block_m
    # no temp-file litter from the atomic publish
    assert [p.name for p in tmp_path.iterdir()] == ["autotune.json"]
    autotune.clear_autotune_cache()


def test_autotune_disk_cache_tolerates_corrupt_file(tmp_path, monkeypatch):
    """A torn or unrelated file behind CORE_AUTOTUNE_CACHE must warn and
    fall back to a fresh sweep (never silently poison configs), and the
    next save replaces it with a valid table."""
    path = tmp_path / "autotune.json"
    path.write_text('{"torn prefix: [1, 2')
    monkeypatch.setenv("CORE_AUTOTUNE_CACHE", str(path))
    autotune.clear_autotune_cache()
    with pytest.warns(RuntimeWarning, match="corrupt or partial"):
        cfg = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                      backend="test")
    assert cfg.source == "sweep"
    table = autotune._read_disk_table(str(path))  # healed: parses again
    assert len(table) == 1 and next(iter(table.values())).block_m == cfg.block_m
    autotune.clear_autotune_cache()


def test_scorer_uses_autotuned_block(workload, mixed_plan):
    """CascadeScorer with a row hint adopts the tuner's block; without
    one it keeps the static heuristic's pick."""
    params = [s.proxy.params for s in mixed_plan.stages
              if s.proxy is not None]
    thrs = [s.threshold for s in mixed_plan.stages if s.proxy is not None]
    default = CascadeScorer(params, thrs)
    assert default.block_m == autotune.static_heuristic_block_m(
        default.n_features, int(default.w1.shape[1]), default.n_proxies)
    hinted = CascadeScorer(params, thrs, n_rows_hint=256)
    assert hinted.block_m <= 256


# ------------------------------------------------- backend calibration
@pytest.fixture()
def clean_backends():
    autotune.reset_backend_constants()
    autotune.clear_autotune_cache()
    yield
    autotune.reset_backend_constants()
    autotune.clear_autotune_cache()


def test_uncalibrated_backend_is_bit_identical(clean_backends):
    """The default path must not move: a backend with no registered
    constants scores every cell exactly as the nominal module constants
    do (existing block picks, caches, and tests see no change)."""
    for (f, hp, p, bm, rows) in [(64, 128, 2, 256, 512),
                                 (256, 4096, 32, 512, 8192)]:
        a = autotune.cell_model(f, hp, p, "float32", bm, rows)
        b = autotune.cell_model(f, hp, p, "float32", bm, rows,
                                backend="never-calibrated")
        assert a == b


def test_tpu_backend_priced_by_device_kind(clean_backends, monkeypatch):
    """On a TPU the autotune backend is the chip's device_kind, priced by
    its published peaks; a TPU with no table entry raises instead of
    borrowing the nominal envelope, and the CPU path does not move."""
    import jax

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert autotune.resolve_backend() == "TPU v5 lite"
    v5e = autotune.backend_constants("TPU v5 lite")
    assert (v5e.hbm_bytes_per_s, v5e.peak_flops, v5e.source) == (
        819e9, 197e12, "published")
    assert autotune.choose_block_m(64, 4, 2).source == "sweep"
    Chip.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="no published peaks"):
        autotune.choose_block_m(64, 4, 2)
    assert autotune.backend_constants("cpu") == autotune.BackendConstants()


def test_set_backend_constants_reprices_and_invalidates(clean_backends):
    """Registered constants change modeled time for THAT backend only,
    and evict its cached sweep winners (a winner picked under the
    nominal envelope may not survive the measured one)."""
    cfg1 = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="calib")
    assert cfg1.source == "sweep"
    assert autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="calib").source == "cache"
    other = autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                    backend="other")
    assert other.source == "sweep"
    slow = autotune.BackendConstants(hbm_bytes_per_s=1.2e10,
                                     peak_flops=7.0e11, source="measured")
    autotune.set_backend_constants("calib", slow)
    assert autotune.backend_constants("calib").source == "measured"
    # 100x slower roofs: same cell, much larger modeled time (the fixed
    # launch/grid overheads stay nominal, so the ratio lands below 100)
    base = autotune.cell_model(64, 256, 4, "int8", 256, 512)
    cal = autotune.cell_model(64, 256, 4, "int8", 256, 512,
                              backend="calib")
    assert cal.t_model_s > 5 * base.t_model_s
    # "calib" winners were evicted; "other" survived
    assert autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="calib").source == "sweep"
    assert autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                                   backend="other").source == "cache"


def test_calibrated_backend_never_touches_disk_cache(
        clean_backends, tmp_path, monkeypatch):
    """Measured constants are machine-local: winners picked under them
    must not be published to the shared disk table, where a host with
    different silicon would inherit them."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("CORE_AUTOTUNE_CACHE", str(path))
    autotune.set_backend_constants(
        "calib", autotune.BackendConstants(source="measured"))
    autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                            backend="calib")
    assert not path.exists()
    # a default-constants backend still persists as before
    autotune.choose_block_m(64, 256, 4, "int8", n_rows_hint=512,
                            backend="default-bk")
    assert path.exists()
    table = autotune._read_disk_table(str(path))
    assert {k[4] for k in table} == {"default-bk"}


def test_calibrate_backend_fits_and_registers(clean_backends, mixed_plan):
    """calibrate_backend fits positive constants from two measure_cell
    points and registers them: subsequent sweeps for that backend score
    under the measured envelope."""
    scorer, _ = cascade_scorer_for_plan(mixed_plan)
    bc = autotune.calibrate_backend(scorer, backend="calib-e2e",
                                    rows=(256, 4096), repeats=1)
    assert bc.source == "measured"
    assert bc.hbm_bytes_per_s > 0 and bc.peak_flops > 0
    assert bc.launch_overhead_s > 0
    # the default knee ratio is preserved (order-only compute roof)
    assert bc.peak_flops / bc.hbm_bytes_per_s == pytest.approx(
        autotune.PEAK_FLOPS / autotune.HBM_BYTES_PER_S)
    assert autotune.backend_constants("calib-e2e") == bc
    cfg = autotune.choose_block_m(
        scorer.n_features, int(scorer.w1.shape[1]), scorer.n_proxies,
        str(scorer.dtype), n_rows_hint=512, backend="calib-e2e")
    assert cfg.source == "sweep" and cfg.block_m >= 128


def test_calibrate_backend_register_false_leaves_registry(
        clean_backends, mixed_plan):
    scorer, _ = cascade_scorer_for_plan(mixed_plan)
    bc = autotune.calibrate_backend(scorer, backend="calib-dry",
                                    rows=(256, 2048), repeats=1,
                                    register=False)
    assert bc.source == "measured"
    assert autotune.backend_constants("calib-dry").source == "default"


# ------------------------------------------------- regret under quant noise
def _mask_sels(plan, masks):
    cols = {s.pred_idx: i for i, s in enumerate(plan.stages)}
    return {p: float(masks[:, cols[p]].mean()) for p in cols}


def test_regret_stable_under_quantization(workload, mixed_plan):
    """Escalation must not flap on quantization noise: selectivities
    measured from int8 masks feed estimate_order_regret to the same
    chosen order and nearly the same regret as fp32 masks."""
    from repro.serving.stats import estimate_order_regret

    ds, _q = workload
    x = ds.x[1200:4200]
    s_f = CascadeScorer.from_plan(mixed_plan, dtype="float32")
    s_q = CascadeScorer.from_plan(mixed_plan, dtype="int8")
    sel_f = _mask_sels(mixed_plan, s_f.score_masks(x))
    sel_q = _mask_sels(mixed_plan, s_q.score_masks(x))
    reg_f, order_f = estimate_order_regret(mixed_plan, sel_f)
    reg_q, order_q = estimate_order_regret(mixed_plan, sel_q)
    assert order_f == order_q
    assert abs(reg_f - reg_q) <= 0.02


def test_regret_greedy_fallback_stable_under_quant():
    """Same stability through the >6-stage greedy rank-ordering path
    (carried-over PR 3 follow-up)."""
    from repro.serving.stats import estimate_order_regret

    ds = make_dataset(n=6000, n_columns=7, correlation=0.85, seed=13)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=13,
                     declared_cost_ms=8.0)
    q = make_query(ds, udfs, columns=list(range(7)),
                   target_selectivity=0.5, seed=14)
    plan = optimize(q, ds.x[:1000], mode="core-a", kind="svm")
    assert len(plan.stages) == 7  # precondition: greedy fallback engaged
    x = ds.x[1000:3000]
    s_f = CascadeScorer.from_plan(plan, dtype="float32")
    s_q = CascadeScorer.from_plan(plan, dtype="int8")
    sel_f = _mask_sels(plan, s_f.score_masks(x))
    sel_q = _mask_sels(plan, s_q.score_masks(x))
    reg_f, order_f = estimate_order_regret(plan, sel_f)
    reg_q, order_q = estimate_order_regret(plan, sel_q)
    assert order_f == order_q
    assert abs(reg_f - reg_q) <= 0.02


# ---------------------------------------------------- distributed end-to-end
def test_quantized_plan_survives_sharded_swap(workload):
    """Acceptance: a quantized plan through the FULL distributed path —
    COREWIRE serialize, K=4 quorum-voted swap, hot-swap install, fused
    scoring — with conservation and epoch agreement intact, and the int8
    dtype preserved across the coordinator's reoptimize + re-broadcast."""
    from repro.distributed.serving import ShardedCascadeServer
    from repro.serving.stats import AdaptivePolicy

    ds, q = workload
    plan = optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True,
                    quant_dtype="int8")
    blob = serialize_scorer(plan)
    assert int.from_bytes(blob[10:12], "little") == WIRE_MINOR_QUANT
    streams = make_sharded_drifting_streams(
        ds, 4, 800, 2400, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    policy = AdaptivePolicy(cooldown_records=1024, min_reservoir=128,
                            threshold=50.0, audit_rate=0.03,
                            reservoir_capacity=512)
    srv = ShardedCascadeServer(plan, 4, tile=256, policy=policy, seed=3)
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.swaps_committed >= 1
    assert stats.submitted == stats.emitted + stats.rejected  # conservation
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    for h in srv.hosts:
        state = h.engine._states[-1]
        assert state.plan.meta.get("quant_dtype") == "int8"
        assert state.cascade is not None and state.cascade.dtype == "int8"
        assert state.cascade.packed.w1.dtype == np.int8
