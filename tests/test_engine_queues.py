"""The engine's columnar stage queue (``serving.engine._StageQueue``),
its per-stage segment counter, and the finalize-hook contract."""
from __future__ import annotations

import numpy as np
import pytest

from repro.serving.engine import _StageQueue
from test_engine_equivalence import PLAN_A, _plan, _query, _rows

F = 3
P = 2
# segment sizes that line up with a take of 4, and ones that do not
SEGMENTS = [(4, 4, 4), (3, 5, 7), (1, 1, 9, 2), (13,)]


def _segment(start, n, masked=True):
    ids = np.arange(start, start + n, dtype=np.int64)
    rows = (ids[:, None] * 10 + np.arange(F)).astype(np.float32)
    masks = (np.stack([ids % 2 == 0, ids % 3 == 0], axis=1)
             if masked else None)
    return ids, rows, masks


def _filled(sizes, masked=True):
    q, start = _StageQueue(), 0
    for n in sizes:
        q.push(*_segment(start, n, masked))
        start += n
    return q, start


def _check(ids, rows, masks, expect_ids, masked=True):
    np.testing.assert_array_equal(ids, expect_ids)
    assert ids.dtype == np.int64
    np.testing.assert_array_equal(
        rows, (expect_ids[:, None] * 10 + np.arange(F)).astype(np.float32))
    if masked:
        assert masks.shape == (len(expect_ids), P)
        np.testing.assert_array_equal(masks[:, 0], expect_ids % 2 == 0)
        np.testing.assert_array_equal(masks[:, 1], expect_ids % 3 == 0)
    else:
        assert masks is None


@pytest.mark.parametrize("sizes", SEGMENTS)
@pytest.mark.parametrize("masked", [True, False])
def test_takes_keep_fifo_order_across_segments(sizes, masked):
    q, total = _filled(sizes, masked)
    done, lengths = 0, []
    while len(q):
        ids, rows, masks, segments = q.take(4)
        _check(ids, rows, masks, np.arange(done, done + len(ids)), masked)
        assert 1 <= segments <= len(sizes)
        done += len(ids)
        lengths.append(len(ids))
    assert done == total
    # every take is a full 4 but the last, which drains the remainder
    assert lengths == [4] * (total // 4) + ([total % 4] if total % 4 else [])


@pytest.mark.parametrize("sizes", SEGMENTS)
@pytest.mark.parametrize("where", ["below", "at", "above"])
def test_take_below_at_and_above_the_head_segment(sizes, where):
    q, total = _filled(sizes)
    head = sizes[0]
    n = {"below": max(head - 1, 1), "at": head, "above": head + 2}[where]
    ids, rows, masks, segments = q.take(n)
    k = min(n, total)
    _check(ids, rows, masks, np.arange(k))
    # segments the take touched: the head, plus one for each further
    # segment it reached into
    bounds = np.cumsum(sizes)
    assert segments == int(np.searchsorted(bounds, k, side="left")) + 1
    assert len(q) == total - k
    if len(q):
        _check(*q.take(total)[:3], np.arange(k, total))


def test_a_take_inside_one_segment_is_a_view():
    q = _StageQueue()
    ids, rows, masks = _segment(0, 10)
    q.push(ids, rows, masks)
    got_ids, got_rows, got_masks, segments = q.take(4)
    assert segments == 1
    assert np.shares_memory(got_rows, rows)
    assert np.shares_memory(got_ids, ids)
    assert np.shares_memory(got_masks, masks)


@pytest.mark.parametrize("sizes", SEGMENTS)
def test_drain_take_larger_than_the_queue_returns_everything(sizes):
    q, total = _filled(sizes)
    ids, rows, masks, segments = q.take(total + 100)
    _check(ids, rows, masks, np.arange(total))
    assert segments == len(sizes)
    assert len(q) == 0


def test_length_after_mixed_push_and_take():
    q = _StageQueue()
    assert len(q) == 0 and not q
    q.push(*_segment(0, 5))
    q.push(*_segment(5, 0))  # an empty segment is not queued
    assert len(q) == 5
    q.take(3)
    assert len(q) == 2
    q.push(*_segment(5, 6))
    assert len(q) == 8
    ids = q.take(4)[0]
    np.testing.assert_array_equal(ids, [3, 4, 5, 6])
    assert len(q) == 4
    q.push(*_segment(11, 1))
    ids = q.take(10)[0]
    np.testing.assert_array_equal(ids, [7, 8, 9, 10, 11])
    assert len(q) == 0 and not q


def test_queue_refuses_to_mix_masked_and_unmasked_segments():
    q = _StageQueue()
    q.push(*_segment(0, 3))
    with pytest.raises(AssertionError):
        q.push(*_segment(3, 3, masked=False))


# ------------------------------------------------ the engine's counter
def _pass_all_plan():
    """Two unproxied stages whose UDFs keep every record."""
    from repro.core.query import (MLUDF, PhysicalPlan, PlanStage, Predicate,
                                  Query)

    preds = [Predicate(udf=MLUDF(name=f"all{j}",
                                 fn=lambda x: np.ones(len(x), np.int64),
                                 cost=1.0), values=frozenset({1}))
             for j in range(2)]
    return PhysicalPlan(query=Query(preds), stages=[
        PlanStage(pred_idx=j, proxy=None) for j in range(2)])


def test_stage_take_segments_counts_the_segments_each_take_cut():
    from repro.serving.engine import CascadeServer

    srv = CascadeServer(_pass_all_plan(), tile=4, use_kernel=False)
    x = np.zeros((20, F), np.float32)
    # one 8-row segment: two takes inside it, one segment each
    srv.submit(np.arange(8), x[:8])
    srv.pump(drain=True)
    assert srv.stats.stage_take_segments[0] == 2
    # stage 1 got two 4-row survivor segments: one take each
    assert srv.stats.stage_take_segments[1] == 2
    assert srv.stats.stage_udf_batches == [2, 2]
    # two 3-row segments: the first take stitches both, the drain take
    # is the second's remainder
    srv.submit(np.arange(8, 11), x[8:11])
    srv.submit(np.arange(11, 14), x[11:14])
    srv.pump(drain=True)
    assert srv.stats.stage_take_segments[0] == 2 + 2 + 1
    assert srv.emitted == list(range(14))
    assert srv.in_flight() == 0


# --------------------------------------------------- the hook contract
@pytest.mark.parametrize("use_kernel", [False, True])
def test_finalize_hooks_get_lists_of_python_ints(use_kernel):
    from repro.serving.engine import CascadeServer

    srv = CascadeServer(_plan(_query(), *PLAN_A), tile=257,
                        use_kernel=use_kernel)
    calls = []
    srv.add_finalize_hook(lambda e, r, v: calls.append((e, r, v)))
    srv.run_stream(_rows()[:1200], chunk=600)
    seen_empty = set()
    for emitted, rejected, version in calls:
        for side, ids in (("emitted", emitted), ("rejected", rejected)):
            assert type(ids) is list
            assert all(type(i) is int for i in ids)
            if not ids:
                seen_empty.add(side)
        assert emitted or rejected
        assert version == 0
    # early stages emit nothing: their batches hand the hooks an empty
    # emitted side, which is [] (a numpy array would raise on ``not ids``)
    assert "emitted" in seen_empty
    assert sum(len(e) + len(r) for e, r, _ in calls) == 1200
