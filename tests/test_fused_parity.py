"""Fused-cascade parity: CascadeScorer masks and on-device-compacted
survivor indices must EXACTLY match the reference oracle, across ragged
tile sizes (N not a multiple of block_m), the P > 128 lane-pad path,
empty-survivor stages, MLP and mixed-family cascades (hidden-width
bucket boundaries included)."""
import contextlib

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.proxy_family import cascade_kernel_operands
from repro.kernels import ref
from repro.kernels.ops import CascadeScorer, fold_standardizer
from repro.training.proxy_models import LinearParams, MLPParams
from repro.util import spans


@contextlib.contextmanager
def recording():
    """Program spans on, from empty totals, for the block."""
    spans.reset()
    spans.enable()
    try:
        yield
    finally:
        spans.disable()


def _make_params(rng, F, P):
    """P independent LinearParams with nontrivial standardizers."""
    out = []
    for _ in range(P):
        out.append(LinearParams(
            w=rng.randn(F).astype(np.float32),
            b=np.float32(rng.randn()),
            mean=rng.randn(F).astype(np.float32),
            scale=(np.abs(rng.randn(F)) + 0.5).astype(np.float32),
        ))
    return out


def _make_mlp_params(rng, F, H):
    return MLPParams(
        w1=rng.randn(F, H).astype(np.float32),
        b1=rng.randn(H).astype(np.float32),
        w2=(rng.randn(H) / np.sqrt(H)).astype(np.float32),
        b2=np.float32(rng.randn()),
        mean=rng.randn(F).astype(np.float32),
        scale=(np.abs(rng.randn(F)) + 0.5).astype(np.float32),
    )


def _make_mixed_params(rng, F, P, max_hidden=33):
    """Alternating linear / MLP stages; MLP hidden widths deliberately
    straddle the bucket ladder (1, 2, 3, 4, 5, 8, 9, ... boundaries)."""
    widths = [1, 2, 3, 4, 5, 8, 9, 16, 17, 32, max_hidden]
    out = []
    for p in range(P):
        if p % 2 == 0:
            out.append(_make_params(rng, F, 1)[0])
        else:
            out.append(_make_mlp_params(rng, F, widths[p % len(widths)]))
    return out


def _reference(param_list, thresholds, x):
    """Pure-numpy oracle: standardize, score, threshold, compact."""
    masks = np.empty((x.shape[0], len(param_list)), bool)
    for p, (params, thr) in enumerate(zip(param_list, thresholds)):
        w, b = fold_standardizer(params)
        scores = x.astype(np.float32) @ w + b
        masks[:, p] = scores >= thr
    packed = [np.flatnonzero(masks[:, p]) for p in range(len(param_list))]
    return masks, packed


def _packed_reference(scorer, thresholds, x):
    """kernels/ref.py two-pass oracle on the scorer's OWN packed operands:
    the fused kernel must be bit-identical to this for every family."""
    w1, b1, w2, b2 = cascade_kernel_operands(scorer.packed)
    _s, masks, packed = ref.cascade_score_ref(
        jnp.asarray(x, jnp.float32), jnp.asarray(w1), jnp.asarray(b1),
        jnp.asarray(w2), jnp.asarray(b2),
        jnp.asarray(thresholds, jnp.float32))
    return np.asarray(masks), packed


@given(
    n=st.integers(1, 700),
    f=st.integers(4, 96),
    p=st.integers(1, 6),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=25, deadline=None)
def test_fused_matches_reference_ragged_shapes(n, f, p, seed):
    """N deliberately not tied to block_m: exercises row padding + masking."""
    rng = np.random.RandomState(seed)
    params = _make_params(rng, f, p)
    thresholds = rng.randn(p).astype(np.float32)
    x = rng.randn(n, f).astype(np.float32)
    scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True,
                           max_tile=512)
    _scores, masks, packed, counts = scorer.score_compact(x)
    mref, pref = _reference(params, thresholds, x)
    np.testing.assert_array_equal(masks, mref)
    for col in range(p):
        assert counts[col] == len(pref[col])
        np.testing.assert_array_equal(packed[col], pref[col])


def test_fused_lane_pad_path_p_over_128():
    """P > 128 forces the 128-lane pad inside the kernel; padded columns
    must never leak into masks, packed indices, or counts."""
    rng = np.random.RandomState(7)
    F, P, N = 24, 130, 300
    params = _make_params(rng, F, P)
    thresholds = rng.randn(P).astype(np.float32)
    x = rng.randn(N, F).astype(np.float32)
    scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True)
    _scores, masks, packed, counts = scorer.score_compact(x)
    mref, pref = _reference(params, thresholds, x)
    np.testing.assert_array_equal(masks, mref)
    for col in range(P):
        np.testing.assert_array_equal(packed[col], pref[col])


def test_fused_empty_survivor_stage():
    """A +inf threshold kills every record at one stage: its packed list is
    empty while the other stages are unaffected."""
    rng = np.random.RandomState(3)
    F, N = 16, 257  # N not a multiple of block_m
    params = _make_params(rng, F, 3)
    thresholds = np.asarray([-1e30, np.float32(np.finfo(np.float32).max), 0.0],
                            np.float32)
    x = rng.randn(N, F).astype(np.float32)
    scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True)
    _scores, masks, packed, counts = scorer.score_compact(x)
    assert counts[0] == N and len(packed[0]) == N  # keep-all stage
    assert counts[1] == 0 and len(packed[1]) == 0  # empty-survivor stage
    assert not masks[:, 1].any()
    mref, pref = _reference(params, thresholds, x)
    np.testing.assert_array_equal(masks, mref)
    np.testing.assert_array_equal(packed[2], pref[2])


def test_fused_chunked_matches_single_tile():
    """Batches larger than max_tile are chunked; survivor indices must be
    globally offset correctly."""
    rng = np.random.RandomState(11)
    F, P, N = 20, 2, 1500
    params = _make_params(rng, F, P)
    thresholds = np.zeros(P, np.float32)
    x = rng.randn(N, F).astype(np.float32)
    small = CascadeScorer(params, thresholds, block_m=128, interpret=True,
                          max_tile=512)
    big = CascadeScorer(params, thresholds, block_m=128, interpret=True,
                        max_tile=4096)
    _, m1, p1, c1 = small.score_compact(x)
    _, m2, p2, c2 = big.score_compact(x)
    np.testing.assert_array_equal(m1, m2)
    for col in range(P):
        np.testing.assert_array_equal(p1[col], p2[col])
    np.testing.assert_array_equal(c1, c2)


def test_executor_fused_vs_reference_end_to_end():
    """Full plan execution: fused path returns the identical survivor set,
    stage bookkeeping, and flags the kernel path in StageStats."""
    from repro.core import execute_plan, optimize
    from repro.data.synthetic import make_dataset, make_query, make_udfs

    ds = make_dataset(n=6000, correlation=0.85, feature_noise=1.0, seed=21)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=21,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=22)
    plan = optimize(q, ds.x[:900], mode="core-a", step=0.05)
    x = ds.x[1500:4500]
    ref = execute_plan(plan, x, use_kernel=False)
    fus = execute_plan(plan, x, use_kernel=True, fused=True, batch_size=1024)
    assert set(ref.passed.tolist()) == set(fus.passed.tolist())
    assert abs(ref.model_cost_ms - fus.model_cost_ms) < 1e-6
    for a, b in zip(ref.stages, fus.stages):
        assert (a.n_in, a.n_proxy_kept, a.n_udf, a.n_pass) == \
            (b.n_in, b.n_proxy_kept, b.n_udf, b.n_pass)
        assert not a.used_kernel
    assert any(s.used_kernel for s in fus.stages if s.pred_idx is not None)
    assert fus.fused_score_ms > 0.0


# ------------------------------------------------- MLP / mixed cascades
@given(
    n=st.integers(1, 700),
    f=st.integers(4, 64),
    p=st.integers(1, 5),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=20, deadline=None)
def test_fused_mixed_cascade_matches_packed_reference(n, f, p, seed):
    """Mixed linear/MLP cascades, ragged N, hidden widths straddling the
    bucket ladder: fused masks, survivor indices, and counts must be
    bit-identical to the kernels/ref.py two-pass oracle."""
    rng = np.random.RandomState(seed)
    params = _make_mixed_params(rng, f, p)
    thresholds = rng.randn(p).astype(np.float32)
    x = rng.randn(n, f).astype(np.float32)
    scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True,
                           max_tile=512)
    _scores, masks, packed, counts = scorer.score_compact(x)
    mref, pref = _packed_reference(scorer, thresholds, x)
    np.testing.assert_array_equal(masks, mref)
    for col in range(p):
        assert counts[col] == len(pref[col])
        np.testing.assert_array_equal(packed[col], pref[col])


def test_fused_mlp_lane_pad_path_p_over_128():
    """P > 128 MLP stages force the 128-lane pad on BOTH kernel dims (the
    stacked hidden dim and the stage dim); padded columns must never leak
    into masks, packed indices, or counts."""
    rng = np.random.RandomState(17)
    F, P, N = 12, 130, 300
    params = [_make_mlp_params(rng, F, 2) for _ in range(P)]
    thresholds = rng.randn(P).astype(np.float32)
    x = rng.randn(N, F).astype(np.float32)
    scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True)
    _scores, masks, packed, counts = scorer.score_compact(x)
    mref, pref = _packed_reference(scorer, thresholds, x)
    np.testing.assert_array_equal(masks, mref)
    for col in range(P):
        np.testing.assert_array_equal(packed[col], pref[col])
        assert counts[col] == len(pref[col])


def test_fused_mixed_empty_survivor_stage():
    """+inf threshold on the MLP stage of a mixed cascade: its packed list
    is empty while the linear stages are unaffected."""
    rng = np.random.RandomState(23)
    F, N = 16, 257  # N not a multiple of block_m
    params = [_make_params(rng, F, 1)[0], _make_mlp_params(rng, F, 8),
              _make_params(rng, F, 1)[0]]
    thresholds = np.asarray(
        [-1e30, np.float32(np.finfo(np.float32).max), 0.0], np.float32)
    x = rng.randn(N, F).astype(np.float32)
    scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True)
    _scores, masks, packed, counts = scorer.score_compact(x)
    assert counts[0] == N and len(packed[0]) == N  # keep-all stage
    assert counts[1] == 0 and len(packed[1]) == 0  # empty MLP stage
    assert not masks[:, 1].any()
    mref, pref = _packed_reference(scorer, thresholds, x)
    np.testing.assert_array_equal(masks, mref)
    np.testing.assert_array_equal(packed[2], pref[2])


def test_fused_hidden_bucket_boundary_widths():
    """Hidden widths exactly at and one past each bucket boundary pack and
    score identically to the oracle (the pad slots must stay inert)."""
    rng = np.random.RandomState(29)
    F, N = 10, 200
    for h in (1, 2, 3, 4, 5, 8, 9, 16, 17, 32, 33):
        params = [_make_mlp_params(rng, F, h), _make_params(rng, F, 1)[0]]
        thresholds = rng.randn(2).astype(np.float32)
        x = rng.randn(N, F).astype(np.float32)
        scorer = CascadeScorer(params, thresholds, block_m=128, interpret=True)
        _s, masks, packed, counts = scorer.score_compact(x)
        mref, pref = _packed_reference(scorer, thresholds, x)
        np.testing.assert_array_equal(masks, mref)
        for col in range(2):
            np.testing.assert_array_equal(packed[col], pref[col])


def test_executor_mixed_fused_vs_reference_end_to_end():
    """Full mixed-cascade plan execution: the fused path returns the
    identical survivor set and runs EVERY proxied stage on the kernel —
    no silent reference fallback left for MLP stages."""
    from repro.core import execute_plan, optimize
    from repro.data.synthetic import make_dataset, make_query, make_udfs

    ds = make_dataset(n=6000, correlation=0.85, feature_noise=1.0, seed=51)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=51,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=52)
    plan = optimize(q, ds.x[:900], mode="core-a", step=0.05, kind="mixed")
    assert sorted(s.proxy.family for s in plan.stages) == ["linear", "mlp1"]
    x = ds.x[1500:4500]
    ref_res = execute_plan(plan, x, use_kernel=False)
    fus = execute_plan(plan, x, use_kernel=True, fused=True, batch_size=1024)
    # MLP standardizer folding is a f32 reassociation (~1e-4 agreement with
    # standardize-then-score), so exact-threshold records may flip; allow
    # boundary ties but nothing that could hide a real mask bug
    diff = set(ref_res.passed.tolist()) ^ set(fus.passed.tolist())
    assert len(diff) <= 3, f"{len(diff)} records disagree"
    assert abs(ref_res.model_cost_ms - fus.model_cost_ms) <= \
        1e-3 * ref_res.model_cost_ms
    for a, b in zip(ref_res.stages, fus.stages):
        for fa, fb in [(a.n_in, b.n_in), (a.n_proxy_kept, b.n_proxy_kept),
                       (a.n_udf, b.n_udf), (a.n_pass, b.n_pass)]:
            assert abs(fa - fb) <= 3
    assert all(s.used_kernel for s in fus.stages)


def test_mlp_plan_scorer_cache_hit_on_reswap():
    """Hot-swapping back to an MLP-bearing plan version is a scorer
    compile-cache hit (keyed on packed-param identity, family included)."""
    from repro.core import optimize
    from repro.data.synthetic import make_dataset, make_query, make_udfs
    from repro.kernels.ops import cascade_scorer_for_plan

    ds = make_dataset(n=4000, correlation=0.85, seed=61)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=800, seed=61,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=62)
    plan_mlp = optimize(q, ds.x[:800], mode="core-a", step=0.05, kind="mlp")
    plan_mix = optimize(q, ds.x[:800], mode="core-a", step=0.05, kind="mixed")
    s1, hit1 = cascade_scorer_for_plan(plan_mlp)
    s2, hit2 = cascade_scorer_for_plan(plan_mix)
    s3, hit3 = cascade_scorer_for_plan(plan_mlp)  # re-swap
    s4, hit4 = cascade_scorer_for_plan(plan_mix)  # re-swap
    assert not hit1 and not hit2 and hit3 and hit4
    assert s1 is s3 and s2 is s4 and s1 is not s2
    assert all(c is not None for c in s1.stage_cols)  # MLP stages covered


def test_server_mixed_cascade_all_stages_kernel():
    """Serving engine on a mixed plan: every stage gates on the fused
    kernel path and output matches the reference engine."""
    from repro.core import optimize
    from repro.data.synthetic import make_dataset, make_query, make_udfs
    from repro.serving.engine import CascadeServer

    ds = make_dataset(n=5000, correlation=0.85, feature_noise=1.0, seed=71)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=71,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=72)
    plan = optimize(q, ds.x[:800], mode="core-a", step=0.05, kind="mixed")
    x = ds.x[1000:4000]
    a = CascadeServer(plan, tile=257, use_kernel=True)
    with recording():
        sa = a.run_stream(x, chunk=700)
    b = CascadeServer(plan, tile=257, use_kernel=False)
    sb = b.run_stream(x, chunk=700)
    # boundary ties allowed (MLP fold reassociation), see executor test
    assert len(set(a.emitted) ^ set(b.emitted)) <= 3
    assert sa.emitted + sa.rejected == len(x)
    assert all(sa.stage_used_kernel)
    assert spans.snapshot()["scorer.launch"][0] > 0


def test_server_fused_stats_and_parity():
    from repro.core import optimize
    from repro.data.synthetic import make_dataset, make_query, make_udfs
    from repro.serving.engine import CascadeServer

    ds = make_dataset(n=5000, correlation=0.85, feature_noise=1.0, seed=31)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=31,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=32)
    plan = optimize(q, ds.x[:800], mode="core-a", step=0.05)
    x = ds.x[1000:4000]
    a = CascadeServer(plan, tile=257, use_kernel=True)
    with recording():
        sa = a.run_stream(x, chunk=700)
    b = CascadeServer(plan, tile=257, use_kernel=False)
    sb = b.run_stream(x, chunk=700)
    assert a.emitted == b.emitted
    assert sa.emitted + sa.rejected == len(x)
    assert all(sa.stage_used_kernel)
    assert not any(sb.stage_used_kernel)
    assert spans.snapshot()["scorer.launch"][0] > 0
    assert abs(sa.model_cost_ms - sb.model_cost_ms) < 1e-6
