"""A tiny CPU rehearsal of every cell: it reaches its check, passes it,
and prints no device metric off the chip; the command refuses to run
without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_testutil import ROOT, load_harness, tiny_run

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in SPEC["workloads"]]
DEVICE_METRICS = {m["name"] for m in SPEC["per_layer"]
                  if m["source"] == "device_trace"}


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_rehearsal_reaches_its_check(workload):
    h = load_harness()
    res = tiny_run(h, workload, traced=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert checks["window_compiles"]["value"] == 0
    assert checks["kernel_decisions"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    # off the chip: no device metric, no busy time, no trace breakdown
    assert not DEVICE_METRICS & set(res["metrics"])
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"], "host-side per-layer metrics are still read"


def test_command_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run_cell.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "not 'tpu'" in out.stderr
