"""Program spans (``repro.util.spans``): the recorder itself, and the span
sites in the serving engine and the fused scorer."""
import threading
import time

import numpy as np
import pytest

from repro.util import spans


class FakeClock:
    """A clock that advances by hand and counts its reads."""

    def __init__(self):
        self.t = 0.0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.t


class FakeAnnotation:
    made = 0

    def __init__(self, name):
        FakeAnnotation.made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(spans, "_clock", c)
    FakeAnnotation.made = 0
    monkeypatch.setattr(spans, "_annotation", FakeAnnotation)
    spans.reset()
    yield c
    spans.disable()
    spans.reset()


@pytest.fixture
def recording():
    spans.reset()
    spans.enable()
    yield
    spans.disable()
    spans.reset()


# ---------------------------------------------------------------- recorder
def test_off_returns_one_shared_no_op_and_reads_no_clock(clock):
    a, b = spans.span("engine.stage"), spans.span("scorer.fetch")
    assert a is b
    with a:
        with b:
            clock.t += 1.0
    assert clock.reads == 0 and FakeAnnotation.made == 0
    assert spans.snapshot() == {}


def test_nesting_gives_self_time(clock):
    spans.enable()
    with spans.span("engine.pump"):
        clock.t += 1.0
        with spans.span("engine.stage"):
            clock.t += 2.0
            with spans.span("engine.udf"):
                clock.t += 4.0
            with spans.span("engine.udf"):
                clock.t += 8.0
        clock.t += 16.0
    snap = spans.snapshot()
    assert snap["engine.pump"] == (1, 31.0, 17.0)
    assert snap["engine.stage"] == (1, 14.0, 2.0)
    assert snap["engine.udf"] == (2, 12.0, 12.0)
    assert FakeAnnotation.made == 4


def test_threads_keep_their_own_stacks(recording):
    started = threading.Barrier(2)

    def work(name, nap):
        with spans.span(name):
            started.wait()
            time.sleep(nap)

    t = threading.Thread(target=work, args=("scorer.launch", 0.05))
    t.start()
    with spans.span("engine.pump"):
        work("engine.stage", 0.05)
    t.join()
    snap = spans.snapshot()
    # the other thread's span is no child of this thread's open span
    assert snap["scorer.launch"][0] == 1
    assert snap["engine.pump"][2] < 0.5 * snap["engine.pump"][1]
    assert snap["engine.stage"][2] == pytest.approx(snap["engine.stage"][1])


def test_reset_clears_and_disable_closes_open_spans(clock):
    spans.enable()
    with spans.span("engine.submit"):
        clock.t += 1.0
        spans.disable()
    assert spans.snapshot() == {"engine.submit": (1, 1.0, 1.0)}
    spans.reset()
    assert spans.snapshot() == {}


def test_spanned_calls_straight_through_while_off(clock):
    @spans.spanned("engine.pump")
    def pump(a, *, b):
        """Pump."""
        clock.t += 1.0
        return a + b

    assert pump(1, b=2) == 3 and pump.__doc__ == "Pump."
    assert clock.reads == 0 and FakeAnnotation.made == 0
    assert spans.snapshot() == {}
    spans.enable()
    with spans.span("engine.submit"):
        assert pump(2, b=3) == 5
    snap = spans.snapshot()
    assert snap["engine.pump"] == (1, 1.0, 1.0)
    assert snap["engine.submit"] == (1, 1.0, 0.0)
    assert FakeAnnotation.made == 2


# ----------------------------------------------------------- span sites
def _small_query(seed):
    from repro.core.api import OptimizeOptions, build_plan
    from repro.data.synthetic import make_dataset, make_query, make_udfs

    ds = make_dataset(n=4000, correlation=0.85, feature_noise=1.0, seed=seed)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=1000, seed=seed,
                     declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5,
                   seed=seed + 1)
    plan = build_plan(q, ds.x[:800], OptimizeOptions(mode="core-a", step=0.05))
    return ds, plan


def test_engine_spans_one_stage_span_per_batch(recording):
    from repro.serving.engine import CascadeServer

    ds, plan = _small_query(41)
    srv = CascadeServer(plan, tile=257, use_kernel=True)
    batches = [0]
    run_batch = srv._run_stage_batch

    def counted(*a):
        batches[0] += 1
        return run_batch(*a)

    srv._run_stage_batch = counted
    spans.reset()
    x = ds.x[1000:3000]
    t0 = time.perf_counter()
    st = srv.run_stream(x, chunk=600)
    wall = time.perf_counter() - t0
    snap = spans.snapshot()
    assert batches[0] > len(plan.stages)
    assert snap["engine.stage"][0] == batches[0]
    assert snap["engine.udf"][0] == sum(st.stage_udf_batches)
    covered = snap["engine.submit"][1] + snap["engine.pump"][1]
    assert covered >= 0.9 * wall
    # the scorer runs inside submit; the stage path inside pump
    assert snap["scorer.score"][1] <= snap["engine.submit"][1]
    assert snap["engine.stage"][1] <= snap["engine.pump"][1]


@pytest.mark.parametrize("method", ["score_masks", "score_margins"])
def test_scorer_one_launch_and_one_fetch_per_tile(recording, method):
    from repro.kernels.ops import CascadeScorer
    from repro.training.proxy_models import LinearParams

    rng = np.random.RandomState(3)
    params = [LinearParams(w=rng.randn(8).astype(np.float32),
                           b=np.float32(0.1), mean=np.zeros(8, np.float32),
                           scale=np.ones(8, np.float32)) for _ in range(2)]
    sc = CascadeScorer(params, [0.0, 0.0], block_m=128, max_tile=256)
    x = rng.randn(700, 8).astype(np.float32)
    spans.reset()
    getattr(sc, method)(x)
    snap = spans.snapshot()
    tiles = -(-len(x) // sc.max_tile)
    assert snap["scorer.score"][0] == 1
    assert snap["scorer.launch"][0] == snap["scorer.fetch"][0] == tiles
    children = snap["scorer.launch"][1] + snap["scorer.fetch"][1]
    assert snap["scorer.score"][2] == pytest.approx(
        snap["scorer.score"][1] - children)
