"""The engine's stage queues against recorded digests and an oracle.

Five seeded CPU drives run a hand-built three-predicate query whose
features, proxy weights and thresholds make every proxy score an exact
small integer, so the gate decisions are the same under any precision,
backend or summation order.  Each drive is digested: the sha256 of every
finalize-hook call (emitted ids, rejected ids, plan version, in call
order) and of ``emitted`` / ``emitted_versions``, plus the ``ServeStats``
counts.  ``tests/fixtures/engine_golden.json`` holds the digests the
per-record-queue engine produced on these drives; the columnar queues
must reproduce them bit for bit: the same batches, in the same order,
with the same outcomes.

Re-record (only when the engine's semantics change on purpose)::

    PYTHONPATH=src python tests/test_engine_equivalence.py --record
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "engine_golden.json")
F = 8
N = 3000
TILE = 257
CHUNK = 600
STAT_KEYS = ("stage_in", "stage_udf_batches", "stage_kept",
             "stage_used_kernel", "emitted", "rejected", "plan_swaps",
             "model_cost_ms")


# ------------------------------------------------------------ the query
def _udf_fn(j):
    def fn(x):
        return (x[:, j] + x[:, j + 4] > 0).astype(np.int64)
    return fn


def _query():
    from repro.core.query import MLUDF, Predicate, Query

    preds = [Predicate(udf=MLUDF(name=f"u{j}", fn=_udf_fn(j), cost=1.0 + j),
                       values=frozenset({1})) for j in range(3)]
    return Query(preds, accuracy_target=0.9)


def _proxy_weights(j):
    w = np.zeros(F, np.float32)
    w[j] = w[j + 4] = 1.0
    w[(j + 1) % F] = -1.0
    return w


def _proxy(j, thr):
    from repro.core.proxy import ProxyModel, RCurve
    from repro.training.proxy_models import LinearParams

    params = LinearParams(w=_proxy_weights(j), b=np.float32(0.0),
                          mean=np.zeros(F, np.float32),
                          scale=np.ones(F, np.float32))
    curve = RCurve(alphas=np.asarray([0.9]), thresholds=np.asarray([thr]),
                   reductions=np.asarray([0.3]))
    return ProxyModel(pred_idx=j, d=(), family="linear", params=params,
                      r_curve=curve, cost=0.01)


def _plan(q, order, thresholds):
    """``thresholds[j]`` gates predicate ``j``; None runs it unproxied."""
    from repro.core.query import PhysicalPlan, PlanStage

    stages = []
    for j in order:
        thr = thresholds[j]
        stages.append(PlanStage(
            pred_idx=j, proxy=None if thr is None else _proxy(j, thr),
            alpha=0.9, threshold=-np.inf if thr is None else thr,
            est_reduction=0.3, est_selectivity=0.5))
    return PhysicalPlan(query=q, stages=stages, est_total_cost=3.0)


PLAN_A = ((2, 0, 1), (-1.5, None, -0.5))
PLAN_B = ((1, 2, 0), (-0.5, -1.5, -0.5))


def _rows(seed=7):
    rng = np.random.RandomState(seed)
    return rng.randint(-3, 4, size=(N, F)).astype(np.float32)


# ------------------------------------------------------------ recording
class _Recorder:
    def __init__(self):
        self.calls = []

    def __call__(self, emitted, rejected, version):
        self.calls.append((list(emitted), list(rejected), int(version)))


def _digest(recorder, engine) -> str:
    h = hashlib.sha256()
    h.update(json.dumps(recorder.calls).encode())
    h.update(json.dumps([list(map(int, engine.emitted)),
                         list(map(int, engine.emitted_versions))]).encode())
    return h.hexdigest()


def _stats(engine) -> dict:
    return {k: getattr(engine.stats, k) for k in STAT_KEYS}


def _served(engine, rec) -> dict:
    return {"digest": _digest(rec, engine), "stats": _stats(engine)}


def _server(plan, **kw):
    from repro.serving.engine import CascadeServer

    srv = CascadeServer(plan, tile=TILE, **kw)
    rec = _Recorder()
    srv.add_finalize_hook(rec)
    return srv, rec


def drive_run_stream(x):
    """``CascadeServer.run_stream``: fused masks from the submit-time scorer."""
    srv, rec = _server(_plan(_query(), *PLAN_A))
    srv.run_stream(x, chunk=CHUNK)
    return {"engine": srv, "recorder": rec, "out": _served(srv, rec)}


def drive_host(x):
    """No kernel: unmasked segments, the host ``proxy.score`` gate."""
    srv, rec = _server(_plan(_query(), *PLAN_A), use_kernel=False)
    srv.run_stream(x, chunk=CHUNK)
    return {"engine": srv, "recorder": rec, "out": _served(srv, rec)}


def drive_multi_query(x):
    """Two tenants behind one stacked scorer, served one ``pump_one``
    batch at a time by the fair scheduler."""
    from repro.serving.multiquery import MultiQueryEngine

    q = _query()
    handles = [SimpleNamespace(qid=0, plan=_plan(q, *PLAN_A)),
               SimpleNamespace(qid=1, plan=_plan(q, *PLAN_B))]
    mq = MultiQueryEngine(handles, tile=TILE, weights={0: 1.0, 1: 2.0})
    recs = []
    for srv in mq.servers:
        recs.append(_Recorder())
        srv.add_finalize_hook(recs[-1])
    mq.run_stream(x, chunk=CHUNK)
    return {"engines": mq.servers, "recorders": recs,
            "out": {"tenants": [_served(s, r)
                                for s, r in zip(mq.servers, recs)],
                    "finalized_per_query": list(
                        mq.stats.finalized_per_query)}}


def drive_front_end(x):
    """``ServingFrontEnd``: coalesced submits, ``pump(drain=True)`` every
    tick (partial takes), a degrade and a restore of the plan ladder."""
    from repro.serving.frontend import ServingFrontEnd

    srv, rec = _server(_plan(_query(), *PLAN_A))
    fe = ServingFrontEnd(srv)
    for r, s in enumerate(range(0, len(x), 100)):
        idx = np.arange(s, min(s + 100, len(x)))
        fe.submit_request(idx, x[idx], deadline_ms=2000.0,
                          arrival_ms=200.0 * r)
    fe.run()
    fst = fe.stats
    out = _served(srv, rec)
    out["front_end"] = {k: getattr(fst, k) for k in (
        "requests_done", "requests_met_slo", "requests_shed",
        "records_emitted", "records_rejected", "records_shed", "batches",
        "degrades", "restores", "final_level")}
    return {"engine": srv, "recorder": rec, "out": out}


def drive_swap(x):
    """``install_plan`` mid-stream: partial tiles left in every stage of
    the superseded state drain first, under the plan that scored them."""
    srv, rec = _server(_plan(_query(), *PLAN_A))
    half = (len(x) // CHUNK // 2) * CHUNK
    for s in range(0, len(x), CHUNK):
        if s == half:
            srv.install_plan(_plan(_query(), *PLAN_B))
        idx = np.arange(s, min(s + CHUNK, len(x)))
        srv.submit(idx, x[idx])
        srv.pump()
    srv.pump(drain=True)
    return {"engine": srv, "recorder": rec, "out": _served(srv, rec)}


DRIVES = {
    "run_stream": drive_run_stream,
    "host": drive_host,
    "multi_query": drive_multi_query,
    "front_end": drive_front_end,
    "swap": drive_swap,
}


def _jsonable(out):
    return json.loads(json.dumps(out))


# ---------------------------------------------------------------- tests
@pytest.fixture(scope="module")
def golden():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def x():
    return _rows()


@pytest.mark.parametrize("mode", sorted(DRIVES))
def test_engine_reproduces_recorded_digests(golden, x, mode):
    assert _jsonable(DRIVES[mode](x)["out"]) == golden[mode]


def _oracle_emitted(x, plan_spec):
    order, thresholds = plan_spec
    ok = np.ones(len(x), bool)
    for j in order:
        if thresholds[j] is not None:
            ok &= x @ _proxy_weights(j) >= np.float32(thresholds[j])
        ok &= _udf_fn(j)(x) == 1
    return set(np.flatnonzero(ok).tolist())


def _check_against_oracle(engine, rec, x, plan_spec):
    emitted = set(engine.emitted)
    assert len(emitted) == len(engine.emitted)
    assert emitted == _oracle_emitted(x, plan_spec)
    rejected = [i for _, r, _ in rec.calls for i in r]
    assert len(set(rejected)) == len(rejected)
    assert emitted.isdisjoint(rejected)
    assert emitted | set(rejected) == set(range(len(x)))
    assert engine.in_flight() == 0


@pytest.mark.parametrize("mode", ["run_stream", "host"])
def test_emitted_set_matches_the_oracle(x, mode):
    run = DRIVES[mode](x)
    _check_against_oracle(run["engine"], run["recorder"], x, PLAN_A)
    assert run["engine"].stats.stage_used_kernel[0] == (mode != "host")


def test_multi_query_emitted_sets_match_the_oracle(x):
    run = drive_multi_query(x)
    for srv, rec, spec in zip(run["engines"], run["recorders"],
                              (PLAN_A, PLAN_B)):
        _check_against_oracle(srv, rec, x, spec)


def _record(path=FIXTURE):
    x = _rows()
    out = {mode: _jsonable(fn(x)["out"]) for mode, fn in DRIVES.items()}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit(__doc__)
    _record()
