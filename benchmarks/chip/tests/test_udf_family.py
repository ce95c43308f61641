"""The UDF family seam: ``families/mlp.py`` gives what the harness built
itself before (the same rows, labels and counts), a configuration's
``quant_dtype`` key switches the cascade precision, the record source
grows block by block, and a new family runs a cell with new files alone."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_testutil import BENCH_DIR, ROOT, TINY, TINY_SCAN, load_harness
from repro.util import atomic_write_text

PROGRAM_FILES = ("harness.py", "workload.py", "reference.py", "counts.py",
                 "trace.py")


@pytest.fixture(scope="module")
def tiny_cfg():
    cfg = json.loads((BENCH_DIR / "configs" / "synth3.json").read_text())
    cfg.update(TINY)
    return cfg


@pytest.fixture(scope="module")
def model(tiny_cfg):
    h = load_harness()
    return h.Model(tiny_cfg, h.Probe(False))


def test_mlp_family_is_the_default_and_matches_the_workload_it_wraps(
        tiny_cfg, model):
    """What the harness composed from ``workload.py`` before the seam:
    the record process, the UDFs trained on the model rows, their labels
    in tiles and in reference blocks, and the stream's block sampler."""
    h = load_harness()
    wl = h.wl
    fam = model.family
    assert "udf_family" not in tiny_cfg
    assert type(fam).__module__ == "chipbench_families_mlp"
    seed = tiny_cfg["model_seed"]
    proc, x_model, truth = wl.make_process(
        n_features=64, n_latent=16, n_columns=3, n_classes=4,
        correlation=0.8, label_noise=0.1, feature_noise=0.8,
        n_rows=TINY["model_rows"], seed=seed)
    assert np.array_equal(fam.x_model, x_model)
    assert np.array_equal(model.x_sample, x_model[:TINY["sample_rows"]])
    idx = np.random.RandomState(seed).choice(
        len(x_model), TINY["udf_train_rows"], replace=False)
    fwd = wl.UdfForward(wl.train_udfs(
        x_model[idx], truth[idx], hidden=256, depth=4, n_classes=4,
        steps=TINY["udf_train_steps"], seed=seed), 256)
    # rows across a block edge, drawn by the family and by the process
    ids = np.arange(40, 700)
    got = h.RecordSource(fam, 2**31 + 77, 512, block_rows=256).rows(ids)
    want = h.RecordSource(proc, 2**31 + 77, 512, block_rows=256).rows(ids)
    assert got.dtype == np.float32 and np.array_equal(got, want)
    for j in range(3):
        tiles = wl.labels_in_blocks(fwd, j, x_model, TINY["tile"])
        assert np.array_equal(fam.labels_for_query(x_model)[j], tiles)
        assert np.array_equal(fam.orig_labels(got)[j],
                              wl.labels_in_blocks(fwd, j, got, 1 << 14))
        assert np.array_equal(model.udfs[j].fn(got[:300]), fwd(j, got[:300]))
    assert np.array_equal(fam.proxy_inputs(got), got)
    assert fam.extra_checks(got, h.limits_of(tiny_cfg)) == {}


def test_mlp_family_keeps_the_query_and_the_counts(tiny_cfg, model):
    """The query's values at the tiny size, and the work counts the
    readers take from the family, as they read before the seam."""
    h = load_harness()
    assert model.query_values == [[(0, [3, 2, 0]), (1, [3, 2, 0]),
                                   (2, [3, 2])]]
    probe = h.Probe(False)
    probe.in_window = True
    probe.add("udf_rows", 1000)
    probe.add("score_rows.0", 4096)
    probe.add("score_calls.0", 4)
    ctx = h.ReaderContext(cfg=tiny_cfg, model=model, probe=probe,
                          scorer_hidden=[[1, 1, 1]])
    # 0.43 MFLOP a UDF row at F=64, MLP 256 x 4, four classes
    assert ctx.udf_flops() == 1000 * 428032
    assert model.family.n_proxy_features == 64
    assert ctx.cascade_work() == (
        h.counts.cascade_flops(4096, 64, [1, 1, 1]),
        h.counts.cascade_bytes(4096, 4, 64, [1, 1, 1]))
    assert ctx.cascade_work() == (1597440.0, 1064128.0)


def test_quant_dtype_from_the_configuration_switches_the_cascade():
    """An int8 cascade named by the configuration: the kernel comparison
    at the float32 configuration's stated precision then fails, as it does
    for ``run(quant_dtype="int8")``."""
    h = load_harness()
    res = h.run("synth3.scan", 2**31 + 77, 1.5, False, require_chip=False,
                config_override=dict(TINY, quant_dtype="int8"),
                traffic_override=TINY_SCAN)
    v = res["checks"]["kernel_flip_ppm"]
    assert not res["correct"]
    assert not h.passes(v["value"], v["op"], v["limit"])


def test_record_source_grows_without_copying_what_it_drew():
    h = load_harness()
    proc, _x, _t = h.wl.make_process(
        n_features=8, n_latent=4, n_columns=3, n_classes=4, correlation=0.8,
        label_noise=0.1, feature_noise=0.8, n_rows=500, seed=0)
    src = h.RecordSource(proc, 11, 100, block_rows=64)
    first = list(src.blocks)
    assert len(first) == 2
    src.rows(np.arange(300, 310))
    assert len(src.blocks) == 5
    assert all(a is b for a, b in zip(first, src.blocks))
    ids = np.array([299, 3, 64, 130, 63, 200, 0])
    assert np.array_equal(src.rows(ids), np.concatenate(src.blocks)[ids])
    # a block handed back column-major, as a TPU hands it, is kept row-major
    col_major = SimpleNamespace(block_sampler=lambda n: (
        lambda words, b: np.asfortranarray(np.full((n, 8), b, np.float32))))
    src = h.RecordSource(col_major, 11, 100, block_rows=64)
    assert all(blk.flags.c_contiguous for blk in src.blocks)
    assert np.array_equal(src.rows(np.array([5, 70]))[:, 0], [0, 1])


STUB_FAMILY = '''"""A stub UDF family: rows of ``width`` float32 features drawn with
numpy; UDF j labels a row by the quantile bin of its projection on a
fixed direction."""
import numpy as np


def build(cfg, load):
    return Stub(cfg)


class Stub:
    def __init__(self, cfg):
        rng = np.random.RandomState(cfg["model_seed"])
        self.width = int(cfg["width"])
        self.n_proxy_features = self.width
        ncls, ncol = int(cfg["n_classes"]), int(cfg["n_columns"])
        self.x_model = rng.standard_normal(
            (cfg["model_rows"], self.width)).astype(np.float32)
        self.dirs = rng.standard_normal((ncol, self.width)).astype(np.float32)
        cuts = np.linspace(0, 1, ncls + 1)[1:-1]
        self.edges = [np.quantile(self.x_model @ d, cuts) for d in self.dirs]
        self.udfs = [lambda x, j=j: self._label(j, x) for j in range(ncol)]

    def _label(self, j, x):
        return np.digitize(np.asarray(x) @ self.dirs[j], self.edges[j])

    def block_sampler(self, block_rows):
        def draw(words, b):
            rng = np.random.default_rng([int(w) for w in words] + [int(b)])
            return rng.standard_normal(
                (block_rows, self.width)).astype(np.float32)
        return draw

    def warm(self):
        pass

    def labels_for_query(self, x):
        return [u(x) for u in self.udfs]

    orig_labels = labels_for_query

    def proxy_inputs(self, rows):
        return rows

    def udf_flops(self, rows):
        return 2.0 * rows * self.width

    def extra_checks(self, x_window, limits):
        return {"stub_rows_labeled": (len(x_window), ">=", 1)}
'''

STUB_CONFIG = {
    "name": "stub", "udf_family": "stub", "serve": "single",
    "queries": [[0, 1]], "hosts": 1, "model_seed": 3, "width": 12,
    "n_columns": 2, "n_classes": 3, "model_rows": 3000, "sample_rows": 1500,
    "udf_declared_cost_ms": 20.0, "target_selectivity": 0.5,
    "accuracy_target": 0.9, "optimizer_mode": "core", "proxy_kind": "svm",
    "tile": 256, "limits": {"lost_max": 0, "dup_max": 0,
                            "false_emits_max": 0, "kernel_flip_ppm_max": 20.0}}

RUN_STUB = """
import json, sys
sys.path.insert(0, "benchmarks/chip")
import harness
res = harness.run("stub.scan", 2**31 + 5, 1.5, False, require_chip=False,
                  traffic_override=json.loads(sys.argv[1]))
print(json.dumps(res))
"""


def _digest(path):
    return hashlib.sha256(path.read_bytes()).hexdigest()


def test_a_new_family_runs_a_cell_by_new_files_alone(tmp_path):
    """A copy of the benchmark plus a family module, its configuration and
    the spec's entries runs to ``correct``, with every file the benchmark
    had left as it was."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    atomic_write_text(bench / "families" / "stub.py", STUB_FAMILY)
    atomic_write_text(bench / "configs" / "stub.json", json.dumps(STUB_CONFIG))
    spec["configs"].append({
        "name": "stub", "source": "https://arxiv.org/abs/2201.00309",
        "file": "benchmarks/chip/configs/stub.json", "reduced": [],
        "why": "a stub UDF family"})
    spec["workloads"].append({
        "name": "stub.scan", "config": "stub", "traffic": "scan", "chips": 1,
        "why": "the scan over the stub family"})
    atomic_write_text(tmp_path / "BENCHMARK.json", json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", RUN_STUB,
                          json.dumps(TINY_SCAN)], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["checks"]["stub_rows_labeled"]["value"] > 0
    assert res["checks"]["kernel_decisions"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    for name in PROGRAM_FILES:
        assert _digest(bench / name) == _digest(BENCH_DIR / name), name
    added = {p.relative_to(bench).as_posix() for p in bench.rglob("*.py")
             if "__pycache__" not in p.parts} \
        - {p.relative_to(BENCH_DIR).as_posix() for p in BENCH_DIR.rglob("*.py")}
    assert added == {"families/stub.py"}
