"""The program's spans under the benchmark's trace reduction, the shares
the span tool computes from them, and the readers of the UDF batch fill
and the optimizer's split, off the chip."""
import importlib.util
import json
from types import SimpleNamespace

import pytest

from chipbench_testutil import BENCH_DIR, load_harness, tiny_run

NEW_METRICS = ("udf_batch_fill.scan", "optimizer_label_share",
               "optimizer_train_share", "optimizer_search_share")
OPTIMIZER_SHARES = NEW_METRICS[1:]


def load_tool():
    spec = importlib.util.spec_from_file_location(
        "chipbench_tools_program_spans", BENCH_DIR / "tools" / "program_spans.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_idle_gaps_go_to_the_innermost_program_span():
    """Program spans nested in harness spans: the same reduction names
    each idle gap by the innermost span of either kind."""
    tr = load_harness().trace_mod
    spans = [("bench.window", 0, 1000),
             ("bench.submit", 0, 200), ("engine.submit", 10, 190),
             ("scorer.score", 20, 120), ("scorer.launch", 20, 60),
             ("scorer.fetch", 60, 120),
             ("bench.pump", 200, 1000), ("engine.pump", 205, 995),
             ("engine.stage", 210, 700), ("engine.udf", 300, 500),
             ("bench.udf", 310, 490), ("engine.finalize", 600, 650)]
    ops = [("fusion", 40, 10), ("fusion", 350, 100)]
    raw = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
           "spans": spans}
    red = tr.reduce(raw, kernel_op=r"^%cascade_score", top=20)
    gaps = {k: v * 1e9 for k, v in red["idle_gaps"]}
    assert gaps == pytest.approx({
        "bench.submit": 10 + 10, "engine.submit": 10 + 70,
        "scorer.launch": 40 - 10, "scorer.fetch": 60,
        "bench.pump": 5 + 5, "engine.pump": 5 + 295,
        "engine.stage": 90 + 100 + 50, "engine.udf": 10 + 10,
        "bench.udf": 180 - 100, "engine.finalize": 50})
    # every idle nanosecond attributed exactly once
    assert sum(gaps.values()) == pytest.approx(1000 - 110)


def test_recorded_trace_reduces_to_the_same_numbers():
    """The recorded trace (harness spans only) reduces as it always did."""
    h = load_harness()
    raw = json.loads((BENCH_DIR / "tests" / "trace_fixture.json").read_text())
    red = h.trace_mod.reduce(raw, kernel_op=h.KERNEL_OP)
    assert red["window_s"] == pytest.approx(0.074710266, rel=1e-12)
    assert red["busy_s"] == pytest.approx(7.3826e-05, rel=1e-12)
    assert red["kernel_events"] == 8
    assert dict(red["idle_gaps"]) == pytest.approx({
        "bench.pump": 0.032004842, "bench.udf": 0.01671135,
        "bench.score": 0.014638738, "bench.submit": 0.01030049,
        "bench.generate": 0.00079801, h.trace_mod.NO_SPAN: 0.00018301},
        rel=1e-9)


def test_window_shares_from_the_recorder_totals():
    window = {"engine.submit": (4, 3.0, 1.0), "scorer.score": (4, 2.0, 0.1),
              "scorer.launch": (16, 0.9, 0.9), "scorer.fetch": (16, 1.0, 1.0),
              "engine.pump": (4, 5.0, 1.5), "engine.stage": (40, 3.5, 2.0),
              "engine.udf": (40, 1.0, 1.0), "engine.finalize": (40, 0.5, 0.5)}
    got = load_tool().shares(window, window_s=10.0)
    assert got == pytest.approx({
        "engine_enqueue_share": 10.0, "engine_stage_share": 35.0,
        "finalize_hook_share": 5.0, "scorer_launch_share": 9.0,
        "scorer_fetch_share": 10.0})
    # a window without the program's spans (the recorder off): no share
    assert load_tool().shares({}, window_s=10.0) == {}


def _ctx(**kw):
    h = load_harness()
    return h.ReaderContext(**kw)


def _plan(**meta):
    return SimpleNamespace(meta=meta)


def test_udf_batch_fill_reads_rows_over_calls_times_tile():
    read = load_harness().load_reader("udf_batch_fill.scan")
    probe = SimpleNamespace(
        spans=[("bench.udf", 0, 1), ("bench.score", 1, 2), ("bench.udf", 2, 3)],
        counters={"udf_rows": 1536.0})
    assert read(_ctx(probe=probe, cfg={"tile": 1024})) == pytest.approx(75.0)
    probe.spans = []
    assert read(_ctx(probe=probe, cfg={"tile": 1024})) is None


def test_optimizer_shares_read_the_builder_timers():
    h = load_harness()
    stats = {"labeling_ms": 5.0, "training_ms": 20.0, "search_ms": 60.0}
    plans = [_plan(stats=stats, wall_ms=90.0),
             _plan(stats=dict(stats, search_ms=0.0), wall_ms=10.0)]
    got = {m: h.load_reader(m)(_ctx(plans=plans)) for m in OPTIMIZER_SHARES}
    assert got == pytest.approx({"optimizer_label_share": 10.0,
                                 "optimizer_train_share": 40.0,
                                 "optimizer_search_share": 60.0})
    # a plan built without the builder's stats: nothing to read
    for m in OPTIMIZER_SHARES:
        assert h.load_reader(m)(_ctx(plans=plans + [_plan(wall_ms=1.0)])) \
            is None


def test_tiny_rehearsal_reports_the_batch_fill_and_optimizer_split():
    res = tiny_run(load_harness(), "synth3.scan", traced=True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_METRICS) <= set(m)
    assert all(v["unit"] == "%" for k, v in res["metrics"].items()
               if k in NEW_METRICS)
    assert 0.0 < m["udf_batch_fill.scan"] <= 100.0
    assert all(0.0 <= m[k] <= 100.0 for k in OPTIMIZER_SHARES)
    assert 90.0 <= sum(m[k] for k in OPTIMIZER_SHARES) <= 100.0
