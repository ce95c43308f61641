"""Units of the chip benchmark that need no run: the spec and the files it
names, the work counts and peaks, the record stream and the trace
reduction."""
import json
import re
import statistics

import numpy as np
import pytest

from chipbench_testutil import BENCH_DIR, ROOT, load_harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_named_piece_is_found(spec):
    h = load_harness()
    for cell in spec["workloads"]:
        r = h.resolve_cell(spec, cell["name"])
        assert r["config"]["name"] == cell["config"]
        assert r["traffic"]["loop"] == "closed"
        e2e = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert r["per_layer"], cell["name"]
    for m in spec["per_layer"]:
        assert callable(h.load_reader(m["name"]))
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(spec["paths"][0] + "/")


def test_spec_keeps_the_contract_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert all(NAME.match(n) for n in names)
    cells = {c["name"] for c in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert 0.0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in spec["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
    assert len({(c["config"], c["traffic"]) for c in spec["workloads"]}) \
        == len(spec["workloads"])


def test_peaks_table_knows_v5e_and_refuses_unknown_kinds():
    counts = load_harness().counts
    p = counts.peaks_for("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks_for("TPU v9 imaginary")


def test_cascade_counts_against_hand_worked_shapes():
    counts = load_harness().counts
    # 1024 rows, F=64, three linear proxies (hidden width 1 each)
    assert counts.cascade_flops(1024, 64, [1, 1, 1]) \
        == 2 * 1024 * 64 * 3 + 2 * 1024 * 3
    # features once, weights once per call, one keep byte per row and column
    weights = (64 * 3 + 2 * 3 + 2 * 3) * 4
    assert counts.cascade_bytes(1024, 1, 64, [1, 1, 1]) \
        == 4 * 1024 * 64 + weights + 1024 * 3
    peaks = counts.peaks_for("TPU v5 lite")
    t, bound = counts.least_time_s(counts.cascade_flops(1024, 64, [1, 1, 1]),
                                   counts.cascade_bytes(1024, 1, 64, [1, 1, 1]),
                                   peaks)
    assert bound == "memory"
    assert t == pytest.approx((4 * 1024 * 64 + weights + 1024 * 3) / 819e9)


def test_udf_flops_match_the_mlp_shape():
    h = load_harness()
    dims = h.wl.udf_layer_dims(64, 256, 4, 4)
    per = 64 * 256 + 3 * 256 * 256 + 256 * 4
    assert h.counts.mlp_flops(10, dims) == 2 * 10 * per
    # 0.43 MFLOP per record: the serve_mfu arithmetic of a UDF row
    assert h.counts.mlp_flops(1, dims) == pytest.approx(428032)


def _tiny_process():
    proc, _x, _t = load_harness().wl.make_process(
        n_features=8, n_latent=4, n_columns=3, n_classes=4, correlation=0.8,
        label_noise=0.1, feature_noise=0.8, n_rows=500, seed=0)
    return proc


def test_record_source_is_fixed_by_the_seed():
    h = load_harness()
    proc = _tiny_process()
    ids = np.arange(200)
    a = h.RecordSource(proc, 2**31 + 9, 200, block_rows=64).rows(ids)
    b = h.RecordSource(proc, 2**31 + 9, 200, block_rows=64).rows(ids)
    c = h.RecordSource(proc, -3, 200, block_rows=64).rows(ids)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_record_source_never_serves_a_record_twice():
    """A window that outruns the rows drawn at set-up gets fresh draws:
    a record's row depends on the seed and its id alone, and no two ids
    share a row."""
    h = load_harness()
    proc = _tiny_process()
    short = h.RecordSource(proc, 7, 64, block_rows=64)
    long = h.RecordSource(proc, 7, 64 * 5, block_rows=64)
    got = np.concatenate([short.take(50)[1] for _ in range(6)])
    assert len(short.x) == 64 * 5          # drawn as the window got there
    assert np.array_equal(got, long.rows(np.arange(300)))
    assert len(np.unique(got, axis=0)) == len(got)
    assert np.all(np.abs(got) < 1.0) and 0.2 < got.std() < 0.9


def test_trace_reduction_on_a_recorded_trace():
    tr = load_harness().trace_mod
    raw = json.loads((BENCH_DIR / "tests" / "trace_fixture.json").read_text())
    red = tr.reduce(raw, kernel_op=load_harness().KERNEL_OP)
    lo, hi = tr.window_of(raw)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # busy is the union of op intervals inside the window: recompute it
    iv = sorted((max(s, lo), min(s + d, hi))
                for _n, s, d in raw["devices"][red["devices"][0]]["ops"]
                if s + d > lo and s < hi)
    busy, end = 0.0, -1.0
    for a, b in iv:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["kernel_events"] > 0 and 0 < red["kernel_s"] <= red["busy_s"]
    # every idle nanosecond of the window is attributed exactly once
    idle = sum(v for _k, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert {k for k, _v in red["idle_gaps"]} <= {
        n for n, _a, _b in raw["spans"]} | {tr.NO_SPAN}
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_nested_spans_flatten_to_the_innermost():
    tr = load_harness().trace_mod
    segs = tr._segments([("bench.pump", 0, 100), ("bench.udf", 10, 20),
                         ("bench.udf", 30, 40)])
    assert segs == [(0, 10, "bench.pump"), (10, 20, "bench.udf"),
                    (20, 30, "bench.pump"), (30, 40, "bench.udf"),
                    (40, 100, "bench.pump")]
