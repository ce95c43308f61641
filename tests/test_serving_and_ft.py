"""Tests: serving engine conservation, checkpoint roundtrip/reshard,
fault-tolerance components, data pipeline resume determinism."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import Cursor, Prefetcher, ShardedStream
from repro.distributed.fault_tolerance import (
    HeartbeatMonitor,
    ResilientRunner,
    StragglerDetector,
    compress_int8,
    decompress_int8,
)


# ------------------------------------------------------------ serving engine
@pytest.fixture(scope="module")
def small_plan():
    from repro.core import optimize, orig_plan
    from repro.data.synthetic import make_dataset, make_query, make_udfs

    ds = make_dataset(n=8000, correlation=0.85, feature_noise=1.0, seed=11)
    udfs = make_udfs(ds, hidden=32, depth=1, train_rows=1500, seed=11, declared_cost_ms=5.0)
    q = make_query(ds, udfs, columns=[0, 1], target_selectivity=0.5, seed=12)
    plan = optimize(q, ds.x[:1200], mode="core-a", step=0.05)
    return ds, q, plan


@pytest.mark.parametrize("tile", [64, 257, 1024])
def test_cascade_server_conservation(small_plan, tile):
    """Every submitted record is either emitted or rejected; none duplicated."""
    from repro.core import execute_plan
    from repro.serving.engine import CascadeServer

    ds, q, plan = small_plan
    x = ds.x[2000:5000]
    server = CascadeServer(plan, tile=tile, use_kernel=False)
    stats = server.run_stream(x, chunk=700)
    assert stats.emitted + stats.rejected == len(x)
    assert len(set(server.emitted)) == len(server.emitted)
    # same answer as the batch executor
    batch_res = execute_plan(plan, x)
    assert set(server.emitted) == set(batch_res.passed.tolist())


def test_cascade_server_kernel_path(small_plan):
    from repro.serving.engine import CascadeServer

    ds, q, plan = small_plan
    x = ds.x[2000:3000]
    a = CascadeServer(plan, tile=128, use_kernel=True).run_stream(x)
    b = CascadeServer(plan, tile=128, use_kernel=False).run_stream(x)
    assert a.emitted == b.emitted


def test_cascade_server_refuses_partial_scorer(small_plan, monkeypatch):
    """A fused scorer that misses a proxied stage is an error at install,
    never a quiet fall back to per-stage scoring."""
    from repro.kernels.ops import CascadeScorer
    from repro.serving.engine import CascadeServer

    _ds, _q, plan = small_plan
    assert any(s.proxy is not None for s in plan.stages)
    monkeypatch.setattr(CascadeScorer, "covers_all", lambda self, p: False)
    with pytest.raises(ValueError, match="does not cover"):
        CascadeServer(plan, tile=128)


# -------------------------------------------------------------- checkpointer
def test_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp

    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones(5, jnp.int32)}}
    ck = Checkpointer(tmp_path, async_save=True)
    ck.save(10, tree)
    ck.wait()
    restored = ck.restore(tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"]), np.asarray(tree["b"]["c"]))


def test_checkpoint_keeps_latest_and_gc(tmp_path):
    import jax.numpy as jnp

    ck = Checkpointer(tmp_path, keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        ck.save(s, {"x": jnp.full((2,), s)})
    assert ck.all_steps() == [3, 4]
    out = ck.restore({"x": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(out["x"]), [4, 4])


def test_checkpoint_integrity_check(tmp_path):
    import jax.numpy as jnp

    ck = Checkpointer(tmp_path, async_save=False)
    p = ck.save(1, {"x": jnp.ones(4)})
    shard = p / "shard_0.npz"
    raw = bytearray(shard.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    shard.write_bytes(bytes(raw))
    with pytest.raises(Exception):
        ck.restore({"x": jnp.zeros(4)})


# ---------------------------------------------------------- fault tolerance
def test_heartbeat_monitor_detects_dead_host():
    t = [0.0]
    mon = HeartbeatMonitor(["h0", "h1"], timeout=10, clock=lambda: t[0])
    t[0] = 5.0
    mon.beat("h0")
    t[0] = 12.0
    assert mon.dead_hosts() == ["h1"]
    mon.beat("h1")
    assert mon.all_alive()


def test_straggler_detector_flags_outliers():
    d = StragglerDetector(threshold=2.0, warmup=3)
    for i in range(10):
        assert not d.observe(i, 1.0)
    assert d.observe(10, 5.0)  # 5x slower
    assert d.events == [10]
    assert not d.observe(11, 1.05)


def test_resilient_runner_restarts_and_remeshes(tmp_path):
    saved = {}
    fail_at = {7}
    devices = [4]

    def step_fn(state, step):
        if step in fail_at:
            fail_at.remove(step)
            raise RuntimeError("simulated device loss")
        return state + 1

    def save_fn(step, state):
        saved["ckpt"] = (step, state)

    def restore_fn():
        return saved["ckpt"]

    remeshed = []

    def remesh_fn(state, n):
        remeshed.append(n)
        return state

    save_fn(0, 0)
    runner = ResilientRunner(
        step_fn, save_fn, restore_fn, remesh_fn=remesh_fn,
        device_count_fn=lambda: devices[0], checkpoint_every=5, max_restarts=3,
    )
    # shrink the device pool mid-run
    orig_step = runner.step_fn

    def step_and_shrink(state, step):
        if step == 9:
            devices[0] = 2
        return orig_step(state, step)

    runner.step_fn = step_and_shrink
    state, report = runner.run(0, 20)
    assert report.restarts == 1
    assert report.remeshes == 1
    assert remeshed == [2]
    assert state == 20  # all 20 increments applied exactly once after replay
    assert saved["ckpt"][0] == 20


@given(st.integers(1, 64), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_int8_compression_bounded_error(n, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32) * rng.uniform(0.1, 10)
    import jax.numpy as jnp

    q, scale = compress_int8(jnp.asarray(x))
    rec = np.asarray(decompress_int8(q, scale))
    amax = np.abs(x).max(axis=-1, keepdims=True)
    assert np.all(np.abs(rec - x) <= amax / 127.0 + 1e-6)


# ------------------------------------------------------------- data pipeline
def test_sharded_stream_resume_determinism():
    data = np.arange(1000)
    s1 = ShardedStream(data, batch=7, seed=3)
    it = iter(s1)
    seen = [next(it) for _ in range(10)]
    cur = Cursor.from_dict(s1.cursor.as_dict())
    # resume a fresh stream from the saved cursor
    s2 = ShardedStream(data, batch=7, seed=3, cursor=cur)
    a, b = next(iter(s2)), next(it)
    np.testing.assert_array_equal(a, b)


def test_sharded_stream_hosts_disjoint():
    data = np.arange(100)
    got = []
    for h in range(4):
        s = ShardedStream(data, host_id=h, num_hosts=4, batch=5, seed=0)
        it = iter(s)
        for _ in range(5):  # one epoch worth per host (25 records / 5)
            got.append(next(it))
    flat = np.concatenate(got)
    assert len(flat) == 100
    assert len(np.unique(flat)) == 100  # no overlap between host shards


def test_prefetcher_passthrough():
    out = list(Prefetcher(iter(range(10)), depth=3))
    assert out == list(range(10))
