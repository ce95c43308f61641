"""Multi-host sharded serving (DESIGN.md §6): scorer wire format, quorum
vote + two-phase swap protocol, merged-reservoir estimator equivalence,
and K=4 end-to-end conservation across a quorum-voted plan swap."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # benchmarks/

from repro.core import optimize
from repro.data.synthetic import (
    make_dataset,
    make_query,
    make_sharded_drifting_streams,
    make_udfs,
)
from repro.distributed.consensus import (
    DriftVote,
    QuorumSwapCoordinator,
    SwapAck,
    quorum,
)
from repro.distributed.serving import ShardedCascadeServer, ShardHost
from repro.kernels.ops import (
    WireFormatError,
    cascade_scorer_for_plan,
    deserialize_scorer,
    serialize_scorer,
)
from repro.serving.stats import (
    AdaptivePolicy,
    DriftEvent,
    Reservoir,
    ReservoirSample,
    ipw_selectivity,
    merge_reservoir_samples,
)


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


def _policy(**kw):
    base = dict(cooldown_records=1024, min_reservoir=128, threshold=50.0,
                audit_rate=0.03, reservoir_capacity=512)
    base.update(kw)
    return AdaptivePolicy(**base)


# ------------------------------------------------------------- wire format
def test_wire_roundtrip_bit_exact(workload, mixed_plan):
    """serialize -> deserialize -> serialize reproduces the exact bytes;
    the deserialized scorer's packed tensors, thresholds, and keep masks
    are bit-identical to the sender's (mixed linear+MLP cascade)."""
    ds, q = workload
    scorer, _ = cascade_scorer_for_plan(mixed_plan)
    blob = serialize_scorer(mixed_plan, scorer)
    plan2, scorer2 = deserialize_scorer(blob, q)
    assert serialize_scorer(plan2, scorer2) == blob
    for a, b in [(scorer.packed.w1, scorer2.packed.w1),
                 (scorer.packed.b1, scorer2.packed.b1),
                 (scorer.packed.w2, scorer2.packed.w2),
                 (scorer.packed.b2, scorer2.packed.b2)]:
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert np.array_equal(np.asarray(scorer.thr), np.asarray(scorer2.thr))
    x = ds.x[2000:3000]
    assert np.array_equal(scorer.score_masks(x), scorer2.score_masks(x))
    # plan metadata survives: order, thresholds, estimates, r-curves
    assert plan2.order == mixed_plan.order
    for s1, s2 in zip(mixed_plan.stages, plan2.stages):
        assert s2.threshold == float(s1.threshold)
        assert s2.alpha == float(s1.alpha)
        assert np.array_equal(s1.proxy.r_curve.thresholds,
                              s2.proxy.r_curve.thresholds)
    # deserialized proxies are first-class packed1 models: reference
    # scoring still works and agrees with the original family's scorer
    s_ref = plan2.stages[0].proxy.score(x[:64])
    s_orig = mixed_plan.stages[0].proxy.score(x[:64])
    assert np.allclose(s_ref, s_orig, atol=1e-5)


def test_wire_rejects_garbage_and_mismatches(workload, mixed_plan):
    ds, q = workload
    blob = serialize_scorer(mixed_plan)
    with pytest.raises(WireFormatError):
        deserialize_scorer(b"NOTAWIRE" + blob[8:], q)
    bad_ver = blob[:8] + (99).to_bytes(2, "little") + blob[10:]
    with pytest.raises(WireFormatError):
        deserialize_scorer(bad_ver, q)
    # wrong query shape: a 2-predicate query cannot bind a 3-stage artifact
    udfs2 = [q.predicates[0].udf, q.predicates[1].udf]
    from repro.core.query import Predicate, Query

    q2 = Query([Predicate(udf=u, values=frozenset({1})) for u in udfs2],
               accuracy_target=0.9)
    with pytest.raises(WireFormatError):
        deserialize_scorer(blob, q2)


def test_packed1_family_is_not_trainable(workload, mixed_plan):
    ds, q = workload
    plan2, _ = deserialize_scorer(serialize_scorer(mixed_plan), q)
    from repro.core.proxy_family import get_family

    with pytest.raises(TypeError):
        get_family("packed1").train(ds.x[:32], np.ones(32), 0)


# --------------------------------------------- scorer cache vs id reuse
def test_scorer_cache_immune_to_param_id_reuse(workload):
    """Regression (ISSUE 4 sweep): the compile cache used to key on
    ``id(params)``; recycled ids (params GC'd, new allocation at the same
    address) could then alias a stale compiled scorer.  Content
    fingerprints make the hazard structurally impossible — this test
    provokes real id reuse and checks every lookup still scores with the
    CURRENT parameters."""
    import gc

    from repro.core.proxy import ProxyModel, build_r_curve
    from repro.core.query import PhysicalPlan, PlanStage
    from repro.kernels import ops
    from repro.training.proxy_models import LinearParams

    ds, q = workload
    x = ds.x[:256].astype(np.float32)
    F = x.shape[1]
    rng = np.random.RandomState(0)

    def fresh_plan(seed):
        w = rng.randn(F).astype(np.float32)
        params = LinearParams(w=w, b=np.float32(0.1 * seed),
                              mean=np.zeros(F, np.float32),
                              scale=np.ones(F, np.float32))
        scores = x @ w + 0.1 * seed
        curve = build_r_curve(scores, scores > np.median(scores))
        proxy = ProxyModel(pred_idx=0, d=(), family="linear", params=params,
                           r_curve=curve, cost=1e-4)
        stage = PlanStage(pred_idx=0, proxy=proxy, alpha=0.9,
                          threshold=float(np.median(scores)))
        return PhysicalPlan(query=q, stages=[stage])

    seen_ids, reused = [], 0
    for seed in range(40):
        # drop every strong ref the caches hold so CPython can recycle
        # the NamedTuple's address between iterations
        ops._PACK_CACHE.clear()
        ops._OPERAND_CACHE.clear()
        ops._SCORER_CACHE.clear()
        gc.collect()
        plan = fresh_plan(seed)
        pid = id(plan.stages[0].proxy.params)
        reused += int(pid in seen_ids)
        seen_ids.append(pid)
        scorer, _hit = cascade_scorer_for_plan(plan)
        expect = (x @ plan.stages[0].proxy.params.w
                  + plan.stages[0].proxy.params.b) >= plan.stages[0].threshold
        got = scorer.score_masks(x)[:, 0]
        assert np.array_equal(got, np.asarray(expect)), (
            f"stale scorer served for recycled id at seed {seed}")
        del plan, scorer
    assert reused > 0, "test never provoked id reuse; tighten the loop"


def test_scorer_cache_hits_on_identical_content(workload, mixed_plan):
    """Content keying also dedupes: a deserialized copy of a plan this
    process already compiled is a cache HIT (same packed bytes), even
    though every params object differs."""
    ds, q = workload
    from repro.kernels import ops

    ops._SCORER_CACHE.clear()
    s1, hit1 = cascade_scorer_for_plan(mixed_plan)
    plan2, _ = deserialize_scorer(serialize_scorer(mixed_plan, s1), q)
    s2, hit2 = cascade_scorer_for_plan(plan2)
    assert not hit1 and hit2
    assert s1 is s2


# --------------------------------------------------- merged reservoirs
@given(
    n_rows=st.integers(16, 120),
    n_hosts=st.integers(1, 5),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=25, deadline=None)
def test_merged_reservoirs_match_single_reservoir(n_rows, n_hosts, seed):
    """Satellite property (ISSUE 4): splitting a labeled stream across K
    per-host reservoirs and merging the exports yields EXACTLY the same
    IPW-corrected selectivity as one reservoir fed the whole stream —
    order-insensitive, weights preserved."""
    rng = np.random.RandomState(seed)
    rows = rng.randn(n_rows, 3).astype(np.float32)
    sigma = rng.random_sample(n_rows) < 0.4
    weights = 1.0 / rng.uniform(0.05, 1.0, n_rows)  # arbitrary audit IPW
    assign = rng.randint(0, n_hosts, n_rows)

    single = Reservoir(n_preds=1, capacity=n_rows, stride=1)
    parts = [Reservoir(n_preds=1, capacity=n_rows, stride=1)
             for _ in range(n_hosts)]
    for i in range(n_rows):
        single.add(i, rows[i], force=True)
        single.observe(i, 0, bool(sigma[i]), weight=float(weights[i]))
        h = assign[i]
        parts[h].add(i, rows[i], force=True)
        parts[h].observe(i, 0, bool(sigma[i]), weight=float(weights[i]))
    merged = merge_reservoir_samples([p.export() for p in parts])
    perm = merge_reservoir_samples(
        [p.export() for p in reversed(parts)])  # order-insensitive
    want = ipw_selectivity(single.export(), 0)
    assert abs(ipw_selectivity(merged, 0) - want) < 1e-12
    assert abs(ipw_selectivity(perm, 0) - want) < 1e-12
    assert merged.n_rows == n_rows
    # weights rode through untouched
    order = np.argsort(merged.indices)
    assert np.allclose(merged.weights[order], weights, rtol=0, atol=0)


# ------------------------------------------------------ consensus protocol
def _vote(host, epoch=0, escalated=False, n_rows=4):
    rng = np.random.RandomState(host)
    return DriftVote(
        host=host, epoch=epoch,
        event=DriftEvent(at_record=100, signal=f"stage0:keep",
                         observed=0.1, expected=0.5, escalated=escalated),
        reservoir=ReservoirSample(
            indices=np.arange(n_rows) + 1000 * host,
            x=rng.randn(n_rows, 3).astype(np.float32),
            known_sigma={0: (np.ones(n_rows, bool),
                             rng.random_sample(n_rows) < 0.5)},
            weights=np.ones(n_rows),
        ),
    )


def test_quorum_sizes():
    assert quorum(1) == 1
    assert quorum(2) == 2
    assert quorum(3) == 2
    assert quorum(4) == 3
    assert quorum(5) == 3
    assert quorum(4, frac=0.75) == 4


def test_coordinator_vote_accounting(mixed_plan):
    coord = QuorumSwapCoordinator(
        mixed_plan, 4, reopt_fn=lambda plan, merged, mode: mixed_plan)
    assert not coord.offer_vote(_vote(0))
    assert not coord.offer_vote(_vote(0))  # duplicate host: ignored
    assert coord.votes_pending == 1
    assert not coord.offer_vote(_vote(1, epoch=3))  # stale/future epoch
    assert not coord.offer_vote(_vote(1))
    assert coord.offer_vote(_vote(2))  # 3rd distinct host = quorum of 3
    with pytest.raises(RuntimeError):  # propose() twice
        coord.propose()
        coord.propose()


def test_coordinator_two_phase_commit_and_abort(mixed_plan):
    reopts = []

    def reopt_fn(plan, merged, mode):
        reopts.append((merged.n_rows, mode))
        return mixed_plan

    coord = QuorumSwapCoordinator(mixed_plan, 3, reopt_fn=reopt_fn)
    for h in range(2):
        coord.offer_vote(_vote(h))
    prep = coord.propose(extra_reservoirs=[_vote(9).reservoir])
    assert prep.epoch == 1 and len(reopts) == 1
    assert reopts[0][0] == 12  # 2 votes + 1 extra, 4 rows each, merged
    # acks from 2 of 3 hosts: no commit yet (ALL hosts must ack)
    a = coord.pending.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=a)) is None
    assert coord.offer_ack(SwapAck(host=1, epoch=1, ok=True,
                                   attempt=a)) is None
    commit = coord.offer_ack(SwapAck(host=2, epoch=1, ok=True, attempt=a))
    assert commit is not None and commit.epoch == 1
    assert coord.epoch == 1 and coord.swaps_committed == 1
    assert coord.votes_pending == 0  # round cleared
    # next round: a NACK aborts and leaves the epoch unchanged
    for h in range(2):
        coord.offer_vote(_vote(h, epoch=1))
    coord.propose()
    a = coord.pending.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=2, ok=True,
                                   attempt=a)) is None
    assert coord.offer_ack(
        SwapAck(host=1, epoch=2, ok=False, error="boom",
                attempt=a)) is None
    assert coord.pending is None and coord.epoch == 1
    assert [r.committed for r in coord.swap_log] == [True, False]
    assert coord.swap_log[-1].aborted_by == 1


def test_majority_escalated_votes_force_bnb(mixed_plan):
    modes = []
    coord = QuorumSwapCoordinator(
        mixed_plan, 3,
        reopt_fn=lambda p, m, mode: modes.append(mode) or mixed_plan,
        choose_mode=lambda p, fresh: "alloc")
    coord.offer_vote(_vote(0, escalated=True))
    coord.offer_vote(_vote(1, escalated=True))
    coord.propose()
    assert modes == ["bnb"]  # 2/2 escalated overrides the alloc decision


# ----------------------------------------------------- end-to-end sharded
@pytest.fixture(scope="module")
def sharded_run(workload):
    """One K=4 skewed-drift run with version tracking (shared across the
    conservation / protocol assertions below)."""
    ds, q = workload
    plan = optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds, 4, 800, 2400, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    srv = ShardedCascadeServer(plan, 4, tile=256, policy=_policy(), seed=3)
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    return srv, stats


def test_sharded_quorum_swap_fires(sharded_run):
    srv, stats = sharded_run
    assert stats.swaps_committed >= 1
    assert stats.votes_cast >= srv.coordinator.quorum_size
    assert stats.final_epoch == stats.swaps_committed
    assert stats.swaps_aborted == 0
    for r in stats.swap_log:
        assert r.committed
        assert len(r.voters) >= srv.coordinator.quorum_size
        assert r.lag_records == 0  # two-phase barrier closed before serving
        assert r.merged_rows > 0


def test_sharded_conservation_across_swaps(sharded_run):
    """Acceptance: every submitted row is emitted-or-rejected exactly
    once, under the plan version it was scored with, across a quorum
    swap."""
    srv, stats = sharded_run
    assert stats.submitted == stats.emitted + stats.rejected
    all_emitted = []
    for h in srv.hosts:
        e = h.engine
        assert len(e.emitted) == len(set(e.emitted))  # no dupes per host
        assert len(e.emitted) == len(e.emitted_versions)
        # each record served under the version current at ITS submission
        for i, v in zip(e.emitted, e.emitted_versions):
            assert h.submit_version[i] == v
        all_emitted.extend(e.emitted)
    assert len(all_emitted) == len(set(all_emitted))  # shards disjoint


def test_sharded_hosts_share_epoch(sharded_run):
    srv, stats = sharded_run
    epochs = {h.epoch for h in srv.hosts}
    assert epochs == {stats.final_epoch}
    versions = {h.engine.plan_version for h in srv.hosts}
    assert versions == {stats.final_epoch}


def test_single_drifted_shard_cannot_swap(workload):
    """Only one of four shards drifts: its vote alone must never reach
    the 3-host quorum — the global plan stays at epoch 0 even though the
    local detector fired."""
    ds, q = workload
    plan = optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True)
    drifted = make_sharded_drifting_streams(
        ds, 1, 600, 2200, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.0, seed=41)[0]
    calm = ds.x[1500:1500 + 2800]
    streams = [drifted.x, calm, calm.copy(), calm.copy()]
    srv = ShardedCascadeServer(plan, 4, tile=256, policy=_policy(), seed=3)
    stats = srv.run_streams(streams, chunk=400)
    assert stats.votes_cast >= 1  # the drifted shard did fire locally
    assert stats.swaps_committed == 0
    assert stats.final_epoch == 0
    assert {h.epoch for h in srv.hosts} == {0}
    assert stats.submitted == stats.emitted + stats.rejected


def test_prepare_nack_aborts_fleetwide(workload):
    """A host that cannot stage the artifact NACKs; the epoch aborts for
    EVERYONE — no partial installs, serving continues on the old plan."""
    ds, q = workload
    plan = optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds, 4, 800, 2400, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    srv = ShardedCascadeServer(plan, 4, tile=256, policy=_policy(), seed=3)
    broken = srv.hosts[2]
    broken.prepare = lambda msg: SwapAck(host=2, epoch=msg.epoch, ok=False,
                                         error="simulated stage failure",
                                         attempt=msg.attempt)
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.swaps_aborted >= 1
    assert stats.swaps_committed == 0
    assert {h.epoch for h in srv.hosts} == {0}
    assert {h.engine.plan_version for h in srv.hosts} == {0}
    assert stats.submitted == stats.emitted + stats.rejected


def test_abort_then_recovery_commits(workload):
    """Regression: an aborted epoch must re-arm voting — a TRANSIENT NACK
    (host fails one prepare, then heals) may not permanently disable
    quorum swaps for hosts whose votes were cleared with the round."""
    ds, q = workload
    plan = optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds, 4, 800, 2400, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    srv = ShardedCascadeServer(plan, 4, tile=256, policy=_policy(), seed=3)
    flaky = srv.hosts[2]
    real_prepare, fails = flaky.prepare, [0]

    def prepare_once_broken(msg):
        if not fails[0]:
            fails[0] += 1
            return SwapAck(host=2, epoch=msg.epoch, ok=False,
                           error="transient stage failure",
                           attempt=msg.attempt)
        return real_prepare(msg)

    flaky.prepare = prepare_once_broken
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.swaps_aborted == 1
    assert stats.swaps_committed >= 1  # the fleet recovered and swapped
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    assert stats.final_epoch >= 1
    assert stats.submitted == stats.emitted + stats.rejected


def test_thread_transport_conservation(workload):
    """Thread-isolated hosts: same protocol across real thread boundaries,
    same conservation guarantee."""
    ds, q = workload
    plan = optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds, 2, 600, 1600, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.2, seed=41)
    srv = ShardedCascadeServer(plan, 2, tile=256, policy=_policy(), seed=3,
                               transport="thread")
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.submitted == stats.emitted + stats.rejected
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}


def test_hosts_placed_on_devices(workload, mixed_plan):
    """Host k scores on ``jax.devices()[k % n]``; with one device every
    host shares it, exactly as before placement existed."""
    import jax

    srv = ShardedCascadeServer(mixed_plan, 3, tile=256, policy=_policy(),
                               seed=3)
    devices = jax.devices()
    for h in srv.hosts:
        assert h.device == devices[h.host_id % len(devices)]
        assert h.engine.cascade.w1.devices() == {h.device}


PLACEMENT_SUBPROC = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, "src")
    import jax
    from repro.core import OptimizeOptions, build_plan
    from repro.data.synthetic import make_dataset, make_query, make_udfs
    from repro.distributed.serving import ShardedCascadeServer

    ds = make_dataset(n=3000, n_features=64, n_columns=2, seed=5)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=800, seed=5,
                     declared_cost_ms=10.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5,
                   accuracy_target=0.9, seed=6)
    plan = build_plan(q, ds.x[:800], OptimizeOptions(mode="core-a",
                                                     step=0.05))
    assert len(jax.devices()) == 4
    srv = ShardedCascadeServer(plan, 4, tile=256, seed=3)
    stats = srv.run_stream(ds.x[800:2400], chunk=400)
    assert stats.submitted == stats.emitted + stats.rejected
    placed = [sorted(d.id for d in h.engine.cascade.w1.devices())
              for h in srv.hosts]
    assert placed == [[0], [1], [2], [3]], placed
    print("PLACED_OK", placed)
    """
)


def test_hosts_placed_on_distinct_devices_subprocess():
    """Four virtual devices, four hosts: each host's scorer operands sit
    on its own device (own process: the device count is fixed at JAX
    start-up)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run(
        [sys.executable, "-c", PLACEMENT_SUBPROC], capture_output=True,
        text=True, cwd=str(Path(__file__).resolve().parents[1]), env=env,
        timeout=300)
    assert "PLACED_OK" in r.stdout, (
        f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-3000:]}")


SUBPROC = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    from repro.core import optimize
    from repro.data.synthetic import (
        make_dataset, make_query, make_sharded_drifting_streams, make_udfs)
    from repro.distributed.serving import ShardedCascadeServer
    from repro.serving.stats import AdaptivePolicy

    ds = make_dataset(n=7000, n_features=64, n_columns=3, correlation=0.9,
                      feature_noise=0.9, label_noise=0.2, seed=41)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=41,
                     declared_cost_ms=10.0)
    q = make_query(ds, udfs, columns=[0, 1, 2], target_selectivity=0.5,
                   accuracy_target=0.9, seed=42)
    plan = optimize(q, ds.x[:1200], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds, 4, 700, 2000, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    policy = AdaptivePolicy(cooldown_records=1024, min_reservoir=128,
                            threshold=50.0, audit_rate=0.03,
                            reservoir_capacity=512)
    srv = ShardedCascadeServer(plan, 4, tile=256, policy=policy, seed=3)
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.submitted == stats.emitted + stats.rejected
    assert stats.swaps_committed >= 1, stats.votes_cast
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    print("SHARDED_OK", stats.swaps_committed, stats.final_epoch)
    """
)


@pytest.mark.slow
@pytest.mark.flaky  # cold-interpreter subprocess under a wall-clock timeout
def test_sharded_serving_subprocess():
    """Whole-fleet run inside an isolated OS process (the
    test_distribution harness pattern): the sharded server, quorum swap,
    and wire-format install all work from a cold interpreter."""
    r = subprocess.run(
        [sys.executable, "-c", SUBPROC], capture_output=True, text=True,
        cwd="/root/repo", timeout=560,
    )
    assert "SHARDED_OK" in r.stdout, (
        f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-3000:]}")
