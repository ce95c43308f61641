"""The ``moonlight`` UDF family and its cell ``moonlight-cls.docscan``:
the configuration is the program's published Moonlight-16B-A3B with the
stated cut, the cell runs to ``correct`` on the CPU at a tiny width
through the harness as it is, the float8-weights control fails the logit
comparison, and the new readers read nothing where the program keeps no
per-call counts."""
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testutil import BENCH_DIR, ROOT, load_harness

CELL = "moonlight-cls.docscan"
NEW_METRICS = ("udf_roofline.docscan", "udf_pad_share.docscan",
               "serve_mfu.docscan")
# accepted readers of the harness's spans, plans and trace, which read any
# cell: the cell is appended to their lists
SHARED_METRICS = ("bnb_nodes", "optimizer_udf_rows", "engine_self_share.scan",
                  "scorer_share.scan", "cascade_roofline.scan",
                  "udf_share.scan", "device_idle_share.scan",
                  "udf_batch_fill.scan", "optimizer_label_share",
                  "optimizer_train_share", "optimizer_search_share")
# fewer rows and records than the file; off a TPU the family takes its
# ``REHEARSAL`` widths itself
TINY_MOONLIGHT = dict(
    model_rows=1024, sample_rows=1024, tile=16, logit_rows=64,
    limits=dict(json.loads((BENCH_DIR / "configs" / "moonlight-cls.json")
                           .read_text())["limits"], logit_rows_min=8))
TINY_DOCSCAN = {"chunk_rows": 64, "warmup_chunks": 1, "setup_rows": 1024}


def _config():
    return json.loads((BENCH_DIR / "configs" / "moonlight-cls.json").read_text())


def _family_module():
    return load_harness()._import_local("families/moonlight")


def test_configuration_is_the_published_model_with_the_stated_cut():
    from repro.configs.archs import MOONLIGHT_16B_A3B

    cfg = _config()
    got = _family_module().model_config(cfg)
    assert got == MOONLIGHT_16B_A3B.replace(
        name="moonlight-cls", source=cfg["source"], num_layers=5,
        vocab_size=20480)
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 163840}
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in spec["configs"] if c["name"] == "moonlight-cls"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_work_counts_at_the_published_widths():
    """About 474 MFLOP a token slot, the weights as the program holds them."""
    from repro.models import classifier

    cfg = _config()
    counts = load_harness()._import_local("counts_moonlight")
    s = cfg["record_tokens"]
    per_slot = (counts.token_flops(cfg) + counts.pair_flops(cfg) * (s + 1) / 2
                + counts.expert_flops(cfg) * 0.75 * 4)
    assert 470e6 < per_slot < 478e6
    mcfg = _family_module().model_config(cfg)
    shapes = jax.eval_shape(lambda k: classifier.init(
        k, mcfg, n_held=8, n_classes=4), jax.random.PRNGKey(0))
    bf16 = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(shapes)
               if a.dtype == jnp.bfloat16)
    assert counts.weight_bytes(cfg) == bf16
    assert 525e6 < bf16 / 2 < 527e6


@pytest.fixture(scope="module")
def tiny_result():
    h = load_harness()
    return h.run(CELL, 2**31 + 77, 1.5, False, require_chip=False,
                 config_override=TINY_MOONLIGHT, traffic_override=TINY_DOCSCAN)


def test_the_cell_runs_to_correct_at_a_tiny_width(tiny_result):
    res = tiny_result
    assert res["correct"], res["checks"]
    c = res["checks"]
    assert c["window_compiles"]["value"] == 0
    assert c["kernel_decisions"]["value"] > 0
    assert c["logit_rows"]["value"] >= 8
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"records_per_s", "optimize_s", "setup_s"}


def test_float8_weights_fail_the_logit_comparison_alone():
    h = load_harness()
    res = h.run(CELL, 2**31 + 77, 1.5, False, require_chip=False,
                config_override=dict(TINY_MOONLIGHT, fp8_weights=True),
                traffic_override=TINY_DOCSCAN)
    failed = {k for k, v in res["checks"].items()
              if not h.passes(v["value"], v["op"], v["limit"])}
    assert not res["correct"]
    assert failed and failed <= {"logit_err", "logit_argmax_flips"}


def test_the_cell_is_new_files_and_entries_alone():
    for name in ("harness.py", "reference.py", "counts.py", "trace.py",
                 "workload.py", "run_cell.py"):
        assert "moonlight" not in (BENCH_DIR / name).read_text(), name
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_harness().resolve_cell(spec, CELL)
    assert [m["name"] for m in cell["end_to_end"]] == [
        "records_per_s", "optimize_s", "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == list(
        SHARED_METRICS + NEW_METRICS)
    assert cell["cell"]["chips"] == 1


def test_new_readers_read_nothing_without_per_call_counts():
    """A family with no per-call counts (the ``mlp`` family, or this one
    on a program without a device UDF) and a run with no trace: every new
    reader returns None and none raises."""
    h = load_harness()
    probe = h.Probe(False)
    probe.spans.append(("bench.window", 0.0, 10.0))
    ctx = h.ReaderContext(cfg={}, model=SimpleNamespace(family=object()),
                          probe=probe, window_s=0.0, trace=None, chips=1,
                          peaks=None, scorer_hidden=[])
    for name in NEW_METRICS:
        assert h.load_reader(name)(ctx) is None, name
