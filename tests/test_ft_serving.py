"""Fault-tolerant multi-host serving (DESIGN.md §6 failure model):
COREWIRE v1.1 control frames, standby-coordinator replication + takeover,
straggler fencing with serve-behind + re-sync, cross-host kappa² pooling,
the process-level transport, and the consensus edge cases (duplicate
votes, acks after abort, K=2 quorum arithmetic)."""
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # benchmarks/

from repro.core import optimize
from repro.core.correlation import StreamingKappa2
from repro.data.synthetic import (
    make_dataset,
    make_query,
    make_sharded_drifting_streams,
    make_udfs,
)
from repro.distributed.consensus import (
    DriftVote,
    QuorumSwapCoordinator,
    StandbyCoordinator,
    StateDelta,
    SwapAck,
    SwapCommit,
    kappa_export_from_json,
    kappa_export_to_json,
    quorum,
)
from repro.distributed.serving import ShardedCascadeServer
from repro.kernels.ops import (
    FRAME_DELTA,
    FRAME_RESYNC,
    WireFormatError,
    deserialize_frame,
    deserialize_scorer,
    serialize_frame,
    serialize_scorer,
)
from repro.serving.stats import AdaptivePolicy, DriftEvent, ReservoirSample


@pytest.fixture(scope="module")
def workload():
    ds = make_dataset(n=9000, n_features=64, n_columns=3, correlation=0.9,
                      feature_noise=0.9, label_noise=0.2, seed=41)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1200, seed=41,
                     declared_cost_ms=10.0)
    q = make_query(ds, udfs, columns=[0, 1, 2], target_selectivity=0.5,
                   accuracy_target=0.9, seed=42)
    return ds, q


def _policy(**kw):
    base = dict(cooldown_records=1024, min_reservoir=128, threshold=50.0,
                audit_rate=0.03, reservoir_capacity=512)
    base.update(kw)
    return AdaptivePolicy(**base)


def _plan(workload):
    ds, q = workload
    return optimize(q, ds.x[:1500], mode="core", step=0.05, keep_state=True)


def _streams(workload, n_hosts=4, n_before=800, n_after=2400):
    ds, _q = workload
    return make_sharded_drifting_streams(
        ds, n_hosts, n_before, n_after,
        shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)


def _assert_conserved(srv, stats):
    assert stats.submitted == stats.emitted + stats.rejected
    all_emitted = []
    for h in srv.hosts:
        e = h.engine
        assert e.in_flight() == 0
        assert len(e.emitted) == len(set(e.emitted))
        assert len(e.emitted) == len(e.emitted_versions)
        for i, v in zip(e.emitted, e.emitted_versions):
            assert h.submit_version[i] == v
        all_emitted.extend(e.emitted)
    assert len(all_emitted) == len(set(all_emitted))


# --------------------------------------------------- COREWIRE v1.1 frames
def test_frame_roundtrip_and_discrimination(workload):
    ds, q = workload
    plan = _plan(workload)
    artifact = serialize_scorer(plan)
    frame = serialize_frame(FRAME_RESYNC, 7, artifact, meta={"host": 3})
    kind, epoch, payload, meta = deserialize_frame(frame)
    assert (kind, epoch, meta) == (FRAME_RESYNC, 7, {"host": 3})
    assert payload == artifact  # artifact bytes ride through untouched
    plan2, scorer2 = deserialize_scorer(payload, q)
    assert plan2.order == plan.order
    # the two channels cannot be confused in either direction
    with pytest.raises(WireFormatError):
        deserialize_scorer(frame, q)  # frame is not an artifact
    with pytest.raises(WireFormatError):
        deserialize_frame(artifact)  # artifact is not a frame
    # v1 artifact bytes are untouched by the v1.1 addition
    assert artifact[:8] == b"COREWIRE" and artifact[10:12] == b"\x00\x00"
    # truncated frame payloads are detected
    with pytest.raises(WireFormatError):
        deserialize_frame(frame[:-10])


def test_delta_frame_carries_consensus_state():
    delta_payload = b"\x00\x01binary-artifact-bytes\xff"
    frame = serialize_frame(FRAME_DELTA, 3, delta_payload,
                            meta={"kind": "prepare", "host": None})
    kind, epoch, payload, meta = deserialize_frame(frame)
    assert kind == FRAME_DELTA and epoch == 3
    assert payload == delta_payload
    assert meta["kind"] == "prepare" and meta["host"] is None


# --------------------------------------------------- kappa pooling pieces
@given(n_rows=st.integers(8, 80), n_hosts=st.integers(1, 5),
       seed=st.integers(0, 5000))
@settings(max_examples=25, deadline=None)
def test_kappa_merge_matches_single_tracker(n_rows, n_hosts, seed):
    """Summing K shards' exported contingency tables yields exactly the
    kappa² of one tracker fed the union of their rows — the property the
    coordinator's fleet pooling rests on."""
    rng = np.random.RandomState(seed)
    a = rng.randint(0, 3, n_rows)
    b = rng.randint(0, 3, n_rows)
    w = 1.0 / rng.uniform(0.05, 1.0, n_rows)
    assign = rng.randint(0, n_hosts, n_rows)
    single = StreamingKappa2()
    single.update(a, b, weights=w)
    parts = [StreamingKappa2() for _ in range(n_hosts)]
    for k in range(n_hosts):
        m = assign == k
        if m.any():
            parts[k].update(a[m], b[m], weights=w[m])
    pooled = StreamingKappa2()
    for p in parts:
        pooled.merge_counts(*p.export())
    assert pooled.n_rows == single.n_rows == n_rows
    assert abs(pooled.value() - single.value()) < 1e-12


def test_kappa_export_json_roundtrip():
    k = StreamingKappa2()
    k.update([0, 1, 1, 2], [1, 1, 0, 2], weights=[1.0, 2.5, 3.0, 1.5])
    export = {(0, 1): k.export(), (0, 2): k.export()}
    back = kappa_export_from_json(kappa_export_to_json(export))
    assert back.keys() == export.keys()
    for pair in export:
        c1, n1, r1 = export[pair]
        c2, n2, r2 = back[pair]
        assert c1 == c2 and n1 == n2 and r1 == r2
    assert kappa_export_from_json(kappa_export_to_json(None)) is None


# ------------------------------------------------- consensus edge cases
def _vote(host, epoch=0, escalated=False, n_rows=4):
    rng = np.random.RandomState(host)
    return DriftVote(
        host=host, epoch=epoch,
        event=DriftEvent(at_record=100, signal="stage0:keep",
                         observed=0.1, expected=0.5, escalated=escalated),
        reservoir=ReservoirSample(
            indices=np.arange(n_rows) + 1000 * host,
            x=rng.randn(n_rows, 3).astype(np.float32),
            known_sigma={0: (np.ones(n_rows, bool),
                             rng.random_sample(n_rows) < 0.5)},
            weights=np.ones(n_rows),
        ),
    )


@pytest.fixture(scope="module")
def mixed_plan(workload):
    ds, q = workload
    return optimize(q, ds.x[:1200], mode="core-a", step=0.05, kind="mixed")


def test_duplicate_votes_do_not_double_merge(mixed_plan):
    """A host re-sending its vote within one epoch is dropped BEFORE the
    merge: the merged optimization sample must count each host's
    reservoir exactly once or pooled estimates double-weight that
    shard."""
    merged_rows = []
    coord = QuorumSwapCoordinator(
        mixed_plan, 3,
        reopt_fn=lambda p, m, mode: merged_rows.append(m.n_rows) or mixed_plan)
    assert not coord.offer_vote(_vote(0))
    for _ in range(5):  # persistent duplicate sender
        assert not coord.offer_vote(_vote(0))
    assert coord.votes_pending == 1
    assert coord.offer_vote(_vote(1))  # quorum(3) == 2
    coord.propose()
    assert merged_rows == [8]  # 2 hosts x 4 rows; duplicates contributed 0


def test_prepare_ack_after_abort_is_inert(mixed_plan):
    """Late acks for an aborted epoch (straggler finally answering after
    the round died) must not resurrect the swap or leak into any later
    round's barrier accounting."""
    coord = QuorumSwapCoordinator(
        mixed_plan, 3, reopt_fn=lambda p, m, mode: mixed_plan)
    for h in range(2):
        coord.offer_vote(_vote(h))
    coord.propose()
    att1 = coord.pending.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=att1)) is None
    assert coord.offer_ack(
        SwapAck(host=1, epoch=1, ok=False, error="boom",
                attempt=att1)) is None  # abort
    assert coord.pending is None
    # the straggling host 2 answers AFTER the abort: inert
    assert coord.offer_ack(SwapAck(host=2, epoch=1, ok=True,
                                   attempt=att1)) is None
    assert coord.pending is None and coord.epoch == 0
    assert [r.committed for r in coord.swap_log] == [False]
    # a NEW round must need a fresh full barrier (the late ack from the
    # dead round may not count toward this one) — note the retried round
    # re-proposes the SAME epoch number: aborts do not advance it
    for h in range(2):
        coord.offer_vote(_vote(h))
    prep2 = coord.propose()
    assert prep2.epoch == 1
    # same epoch NUMBER, fresh attempt nonce: round-1 acks cannot leak in
    assert prep2.attempt == att1 + 1
    a2 = prep2.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=a2)) is None
    assert coord.offer_ack(SwapAck(host=1, epoch=1, ok=True,
                                   attempt=a2)) is None
    assert coord.offer_ack(SwapAck(host=2, epoch=1, ok=True,
                                   attempt=a2)) is not None
    assert coord.epoch == 1


def test_fenced_host_ack_after_fence_is_inert(mixed_plan):
    """A straggler is fenced out of the barrier while its prepare-ack is
    still in flight (protocol_check.py: deadline_fence then deliver_ack).
    The late ack must be inert: it may not close the shrunken barrier or
    re-enter the fenced host into barrier accounting — the commit must
    come from live acks only."""
    coord = QuorumSwapCoordinator(
        mixed_plan, 3, reopt_fn=lambda p, m, mode: mixed_plan)
    for h in range(2):
        coord.offer_vote(_vote(h))
    coord.propose()
    att = coord.pending.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=att)) is None
    coord.mark_fenced(2)  # deadline resolution: barrier shrinks to {0, 1}
    # the fenced host's ack lands AFTER its fence: inert
    assert coord.offer_ack(SwapAck(host=2, epoch=1, ok=True,
                                   attempt=att)) is None
    assert coord.pending is not None  # barrier still open
    commit = coord.offer_ack(SwapAck(host=1, epoch=1, ok=True, attempt=att))
    assert commit is not None and commit.epoch == 1
    assert coord.epoch == 1


def test_stale_attempt_ack_during_retry_round_is_inert(mixed_plan):
    """The interleaving protocol_check.py's legacy mode flags: round 1 on
    epoch 1 aborts, the retry round re-proposes the SAME epoch number,
    and a round-1 ack then arrives MID-round-2.  The epoch matches, so
    only the attempt nonce distinguishes the rounds — without it the
    stale ack closes the barrier and a host installs an artifact no
    coordinator committed."""
    coord = QuorumSwapCoordinator(
        mixed_plan, 3, reopt_fn=lambda p, m, mode: mixed_plan)
    for h in range(2):
        coord.offer_vote(_vote(h))
    coord.propose()
    att1 = coord.pending.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=att1)) is None
    assert coord.offer_ack(
        SwapAck(host=1, epoch=1, ok=False, error="slow",
                attempt=att1)) is None  # abort round 1
    for h in range(2):
        coord.offer_vote(_vote(h))
    prep2 = coord.propose()
    att2 = prep2.attempt
    assert prep2.epoch == 1 and att2 == att1 + 1
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=att2)) is None
    # host 2's ROUND-1 ack finally arrives: same epoch, stale attempt
    assert coord.offer_ack(SwapAck(host=2, epoch=1, ok=True,
                                   attempt=att1)) is None
    assert coord.pending is not None  # must NOT have closed the barrier
    assert coord.offer_ack(SwapAck(host=1, epoch=1, ok=True,
                                   attempt=att2)) is None
    commit = coord.offer_ack(SwapAck(host=2, epoch=1, ok=True, attempt=att2))
    assert commit is not None and commit.attempt == att2
    assert coord.epoch == 1


def test_quorum_k2_is_unanimity(mixed_plan):
    """K=2: strict majority is floor(2/2)+1 = 2, i.e. BOTH hosts must
    vote and both must ack — one noisy host can never swap alone, and
    one dead host blocks the swap (which fencing then resolves)."""
    assert quorum(2) == 2
    coord = QuorumSwapCoordinator(
        mixed_plan, 2, reopt_fn=lambda p, m, mode: mixed_plan)
    assert coord.quorum_size == 2
    assert not coord.offer_vote(_vote(0))  # one vote is NOT quorum at K=2
    with pytest.raises(RuntimeError):
        coord.propose()
    assert coord.offer_vote(_vote(1))
    coord.propose()
    a = coord.pending.attempt
    assert coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                                   attempt=a)) is None
    commit = coord.offer_ack(SwapAck(host=1, epoch=1, ok=True, attempt=a))
    assert commit is not None and coord.epoch == 1
    # ...and with one host fenced, K=2 degrades to a quorum of one
    coord.mark_fenced(1)
    assert coord.quorum_size == 1


# --------------------------------------------- standby coordinator (unit)
class _StubHost:
    def __init__(self, host_id, epoch=0, staged=None):
        self.host_id = host_id
        self.epoch = epoch
        self._staged = staged  # epoch the host staged, or None
        self.committed = []
        self.aborted = 0

    def commit(self, msg):
        if self._staged != msg.epoch:
            raise RuntimeError("no staged plan")
        self.epoch = msg.epoch
        self._staged = None
        self.committed.append(msg.epoch)

    def abort(self):
        self._staged = None
        self.aborted += 1


def _standby(plan, n_hosts=3):
    return StandbyCoordinator(plan, n_hosts,
                              reopt_fn=lambda p, m, mode: plan)


def test_standby_mirrors_deltas(mixed_plan):
    sb = _standby(mixed_plan)
    sb.apply(StateDelta(kind="vote", epoch=0, host=0))
    sb.apply(StateDelta(kind="vote", epoch=0, host=2))
    assert sb.voted == {0, 2}
    sb.apply(StateDelta(kind="prepare", epoch=1, artifact=b"abc"))
    assert sb.pending == (1, b"abc")
    sb.apply(StateDelta(kind="ack", epoch=1, host=0))
    assert sb.acks == {0}
    sb.apply(StateDelta(kind="commit", epoch=1, artifact=b"abc"))
    assert sb.epoch == 1 and sb.pending is None and sb.voted == set()
    assert sb.last_artifact == b"abc"
    sb.apply(StateDelta(kind="fence", epoch=1, host=2))
    assert sb.fenced == {2}
    sb.apply(StateDelta(kind="rejoin", epoch=1, host=2))
    assert sb.fenced == set()


def test_standby_takeover_completes_closed_barrier(mixed_plan):
    """Primary died between collecting the last ack and broadcasting the
    commit (no commit delta): every active host staged + acked, so the
    standby COMPLETES the install."""
    sb = _standby(mixed_plan)
    sb.apply(StateDelta(kind="prepare", epoch=1, artifact=b"abc"))
    for h in range(3):
        sb.apply(StateDelta(kind="ack", epoch=1, host=h))
    hosts = [_StubHost(h, epoch=0, staged=1) for h in range(3)]
    coord, resolution = sb.take_over(hosts)
    assert resolution == "completed"
    assert coord.epoch == 1 and coord.last_artifact == b"abc"
    assert all(h.epoch == 1 for h in hosts)
    assert coord.swap_log[-1].committed \
        and coord.swap_log[-1].initiated_by == "failover"


def test_standby_takeover_aborts_open_barrier(mixed_plan):
    """Primary died mid-prepare (partial staging, partial acks): nothing
    installed anywhere, so the standby cleanly ABORTS — staged copies
    drop, voting re-arms, the epoch does not advance."""
    sb = _standby(mixed_plan)
    sb.apply(StateDelta(kind="vote", epoch=0, host=0))
    sb.apply(StateDelta(kind="prepare", epoch=1, artifact=b"abc"))
    sb.apply(StateDelta(kind="ack", epoch=1, host=0))
    hosts = [_StubHost(0, staged=1), _StubHost(1, staged=1), _StubHost(2)]
    coord, resolution = sb.take_over(hosts)
    assert resolution == "aborted"
    assert coord.epoch == 0
    assert all(h.aborted == 1 for h in hosts)
    assert all(h._staged is None for h in hosts)
    assert not coord.swap_log[-1].committed


def test_standby_takeover_resyncs_after_lost_commit_broadcast(mixed_plan):
    """Primary committed internally (commit delta replicated) but died
    mid-broadcast: one host installed, the rest are behind — takeover
    fences them for COREWIRE re-sync instead of re-running the barrier."""
    sb = _standby(mixed_plan)
    sb.apply(StateDelta(kind="prepare", epoch=1, artifact=b"abc"))
    for h in range(3):
        sb.apply(StateDelta(kind="ack", epoch=1, host=h))
    sb.apply(StateDelta(kind="commit", epoch=1, artifact=b"abc"))
    hosts = [_StubHost(0, epoch=1), _StubHost(1, epoch=0),
             _StubHost(2, epoch=0)]
    coord, resolution = sb.take_over(hosts)
    assert resolution == "resync"
    assert coord.epoch == 1
    assert coord.fenced == {1, 2}  # behind hosts await re-sync
    assert hosts[0].epoch == 1  # the installed host is untouched


def test_snapshot_deltas_rearm_open_barrier(mixed_plan):
    """A replacement standby registered AFTER a takeover starts blind —
    snapshot_deltas() re-emits the live coordinator state (votes, open
    prepare barrier, partial acks) so a replayed standby mirrors it
    exactly and a SECOND failover can resolve the same barrier."""
    coord = QuorumSwapCoordinator(
        mixed_plan, 3, reopt_fn=lambda p, m, mode: mixed_plan)
    coord.offer_vote(_vote(0))
    coord.offer_vote(_vote(1))
    coord.propose()
    coord.offer_ack(SwapAck(host=0, epoch=1, ok=True,
                            attempt=coord.pending.attempt))
    sb = _standby(mixed_plan)
    for delta in coord.snapshot_deltas():
        sb.apply(delta)
    assert sb.voted == {0, 1}
    assert sb.pending == (1, coord.pending.artifact)
    assert sb.acks == {0}
    assert sb.epoch == 0 and sb.last_artifact is None


def test_snapshot_deltas_rearm_committed_state(mixed_plan):
    """After a committed epoch with a fenced host, the snapshot replays
    the commit (with artifact, for future re-syncs) and the fence."""
    coord = QuorumSwapCoordinator(
        mixed_plan, 3, reopt_fn=lambda p, m, mode: mixed_plan)
    coord.mark_fenced(2)
    coord.offer_vote(_vote(0))
    coord.offer_vote(_vote(1))
    coord.propose()
    a = coord.pending.attempt
    coord.offer_ack(SwapAck(host=0, epoch=1, ok=True, attempt=a))
    commit = coord.offer_ack(SwapAck(host=1, epoch=1, ok=True, attempt=a))
    assert commit is not None and coord.epoch == 1
    sb = _standby(mixed_plan)
    for delta in coord.snapshot_deltas():
        sb.apply(delta)
    assert sb.epoch == 1
    assert sb.last_artifact == coord.last_artifact
    assert sb.fenced == {2}
    assert sb.pending is None


# ------------------------------------------------ end-to-end failover
def test_failover_completes_swap_mid_epoch(workload):
    """Acceptance: the primary dies after the barrier closed but before
    the commit broadcast; the standby takes over mid-epoch and the fleet
    still converges on the committed swap — conservation holds and no
    host ever serves an unacknowledged version."""
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256,
                               policy=_policy(), seed=3,
                               kill_coordinator_at="commit")
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in _streams(workload)], chunk=400)
    assert stats.failovers == 1
    assert stats.failover_resolution == "resync"
    assert stats.swaps_committed >= 1
    assert stats.resyncs == 4  # the whole fleet caught up via re-sync
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    assert stats.final_epoch >= 1
    _assert_conserved(srv, stats)


def test_failover_aborts_partial_prepare_then_recovers(workload):
    """The primary dies with the prepare half-broadcast (some hosts
    staged, no closed barrier): the standby must cleanly ABORT — and the
    recovered fleet must still be able to commit a later swap."""
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256,
                               policy=_policy(), seed=3,
                               kill_coordinator_at="prepare")
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in _streams(workload)], chunk=400)
    assert stats.failovers == 1
    assert stats.failover_resolution == "aborted"
    assert stats.swaps_aborted >= 1
    assert stats.swaps_committed >= 1  # voting re-armed; the fleet recovered
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    _assert_conserved(srv, stats)


def test_failover_mid_commit_broadcast(workload):
    """Hardest corner: the primary dies with ONE host installed.  An
    abort would strand that host, so the takeover must drive everyone
    else forward (re-sync), never backward."""
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256,
                               policy=_policy(), seed=3,
                               kill_coordinator_at="mid-commit")
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in _streams(workload)], chunk=400)
    assert stats.failovers == 1
    assert stats.failover_resolution == "resync"
    assert stats.resyncs == 3  # everyone but the already-installed host
    assert stats.swaps_committed >= 1
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    _assert_conserved(srv, stats)


def test_failover_rearmed_standby_survives_second_kill(workload):
    """Acceptance (re-arm): after the first takeover the promoted
    coordinator registers a FRESH standby and replays its live state via
    snapshot_deltas(), so killing the SECOND primary must also resolve
    cleanly — two failovers, two re-arms, fleet still converged and
    conserved.  Without re-arm the second kill would strand the fleet
    with no coordinator at all."""
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256,
                               policy=_policy(), seed=3,
                               kill_coordinator_at=(2000, "commit"))
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in _streams(workload)], chunk=400)
    assert stats.failovers == 2
    assert stats.standby_rearms == 2
    assert stats.failover_resolution in ("completed", "aborted", "resync")
    assert stats.swaps_committed >= 1
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    assert stats.final_epoch >= 1
    _assert_conserved(srv, stats)


# ------------------------------------------------- straggler fencing
def test_straggler_fenced_serves_behind_then_resyncs(workload):
    """Acceptance: a silent host neither blocks the commit (the fleet
    commits with K-1 acks) nor serves an unacked version (it stays
    pinned on its old epoch until the COREWIRE re-sync)."""
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256,
                               policy=_policy(), seed=3,
                               straggler_host=2)
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in _streams(workload)], chunk=400)
    straggler = srv.hosts[2]
    assert stats.fences == 1
    assert stats.resyncs >= 1 and straggler.resyncs >= 1
    assert stats.swaps_committed >= 1  # the straggler did not block commit
    fenced_swaps = [r for r in stats.swap_log if r.committed and r.fenced]
    assert fenced_swaps and fenced_swaps[0].fenced == [2]
    # serve-behind: everything the straggler served while the fleet was
    # at epoch>=1 ran under ITS pinned version, never an unacked one
    fence_epoch = fenced_swaps[0].epoch
    for i, v in zip(straggler.engine.emitted,
                    straggler.engine.emitted_versions):
        assert v == straggler.submit_version[i]
        assert v in (0, fence_epoch) or v > fence_epoch
    # after rejoin the whole fleet agrees again
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    _assert_conserved(srv, stats)


def test_straggler_nack_policy_aborts(workload):
    """policy="nack": a deadline miss is a NACK — the epoch aborts
    fleet-wide instead of fencing, and serving continues on the old
    plan."""
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256,
                               policy=_policy(), seed=3,
                               straggler_host=2, straggler_policy="nack")
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in _streams(workload)], chunk=400)
    assert stats.fences == 0
    assert stats.swaps_aborted >= 1
    aborted = [r for r in stats.swap_log if not r.committed]
    assert aborted and aborted[0].aborted_by == 2
    # the healed host re-enters quorum: a later swap can still commit
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    _assert_conserved(srv, stats)


# ------------------------------------------------ cross-host kappa² pool
def test_pooled_kappa_escalates_split_correlation_drift(workload):
    """Acceptance: a correlation-only drift split evenly across K=4
    shards fires NO local detector (zero votes, every escalation hint
    says alloc) — yet the pooled contingency tables cross the fleet
    baseline's tolerance and the coordinator escalates straight to a
    B&B re-search."""
    ds, q = workload
    streams = make_sharded_drifting_streams(
        ds, 4, 1200, 2600, shift_targets={}, shift=0.0, corr_gain=3.0,
        drift_skew=0.3, skew_corr=True, seed=41)
    srv = ShardedCascadeServer(
        _plan(workload), 4, tile=256, seed=3,
        policy=_policy(threshold=200.0, kappa_pool_baseline=60))
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.votes_cast == 0  # no shard's local detector fired
    assert stats.pooled_swaps >= 1
    pooled = [r for r in stats.swap_log if r.initiated_by == "pooled:kappa2"]
    assert pooled and all(r.mode == "bnb" for r in pooled)
    assert all(r.voters == [] for r in pooled)
    assert stats.swaps_committed >= 1
    # the locals stayed quiet even at end of stream
    for h in srv.hosts:
        mode, escalated = h.engine.escalation_hint()
        assert not escalated
    _assert_conserved(srv, stats)


def test_pooled_kappa_disabled_by_default(workload):
    """The same split correlation drift with the default policy
    (kappa_pool_baseline=0) swaps nothing: pooling is an explicit
    opt-in — the coordinator may not open unvoted swaps unless asked."""
    ds, q = workload
    streams = make_sharded_drifting_streams(
        ds, 4, 1200, 1800, shift_targets={}, shift=0.0, corr_gain=3.0,
        drift_skew=0.3, skew_corr=True, seed=41)
    srv = ShardedCascadeServer(_plan(workload), 4, tile=256, seed=3,
                               policy=_policy(threshold=200.0))
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.pooled_swaps == 0
    assert stats.swaps_committed == 0


# ------------------------------------------------- process transport
def test_process_transport_refuses_a_tpu(monkeypatch):
    """On a TPU the parent process holds the chip, so a process host
    refuses at construction instead of starting a child that cannot get
    it."""
    import jax

    from repro.distributed import procworker

    def no_child(*_a, **_k):
        raise AssertionError("a worker process was started")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(procworker.subprocess, "Popen", no_child)
    with pytest.raises(RuntimeError, match="one process at a time"):
        procworker.ProcessHost(0, spec={}, artifact=b"", tile=64,
                               policy=AdaptivePolicy(), seed=0)


@pytest.mark.slow
@pytest.mark.flaky
def test_process_transport_fleet(workload):
    """One host per OS subprocess speaking COREWIRE + newline-JSON over
    pipes: the same quorum swap commits across real process boundaries
    and the conservation invariants survive the marshalling."""
    ds, q = workload
    spec = {
        "dataset": dict(n=7000, n_features=64, n_columns=3, correlation=0.9,
                        feature_noise=0.9, label_noise=0.2, seed=41),
        "udfs": dict(hidden=16, depth=1, train_rows=1000, seed=41,
                     declared_cost_ms=10.0),
        "query": dict(columns=[0, 1, 2], target_selectivity=0.5,
                      accuracy_target=0.9, seed=42),
    }
    ds2 = make_dataset(**spec["dataset"])
    udfs2 = make_udfs(ds2, **spec["udfs"])
    q2 = make_query(ds2, udfs2, **spec["query"])
    plan = optimize(q2, ds2.x[:1200], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds2, 2, 700, 2000, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    srv = ShardedCascadeServer(plan, 2, tile=256, policy=_policy(), seed=3,
                               transport="process", worker_spec=spec)
    for h in srv.hosts:
        h.track_versions = True
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert stats.swaps_committed >= 1
    assert {h.epoch for h in srv.hosts} == {stats.final_epoch}
    _assert_conserved(srv, stats)


@pytest.mark.slow
@pytest.mark.flaky
def test_process_transport_slo_frontend(workload):
    """slo_ms crosses the process boundary: each subprocess host runs an
    SLO front end worker-side, its FrontEndStats ride the drain reply
    back over the pipe, and fleet_goodput_ratio aggregates them — the
    thread transport's goodput contract, minus the shared memory."""
    spec = {
        "dataset": dict(n=7000, n_features=64, n_columns=3, correlation=0.9,
                        feature_noise=0.9, label_noise=0.2, seed=41),
        "udfs": dict(hidden=16, depth=1, train_rows=1000, seed=41,
                     declared_cost_ms=10.0),
        "query": dict(columns=[0, 1, 2], target_selectivity=0.5,
                      accuracy_target=0.9, seed=42),
    }
    ds2 = make_dataset(**spec["dataset"])
    udfs2 = make_udfs(ds2, **spec["udfs"])
    q2 = make_query(ds2, udfs2, **spec["query"])
    plan = optimize(q2, ds2.x[:1200], mode="core", step=0.05, keep_state=True)
    streams = make_sharded_drifting_streams(
        ds2, 2, 700, 2000, shift_targets={0: 2.8, 1: -2.6, 2: 2.8},
        corr_gain=2.5, drift_skew=0.3, seed=41)
    # generous per-chunk deadline: every request should meet its SLO
    slo = 200.0 * plan.est_total_cost * 400
    srv = ShardedCascadeServer(plan, 2, tile=256,
                               policy=_policy(threshold=200.0), seed=3,
                               transport="process", worker_spec=spec,
                               slo_ms=slo)
    stats = srv.run_streams([s.x for s in streams], chunk=400)
    assert len(stats.frontend_stats) == 2
    assert all(f.requests_done > 0 for f in stats.frontend_stats)
    assert all(f.requests_rejected_admission == 0
               for f in stats.frontend_stats)
    assert stats.fleet_goodput_ratio > 0.0
    # frontend-aware conservation at fleet level (the engines live in
    # the subprocesses; their row-level invariants are checked worker-side)
    shed = sum(f.records_shed for f in stats.frontend_stats)
    assert stats.submitted == stats.emitted + stats.rejected + shed
