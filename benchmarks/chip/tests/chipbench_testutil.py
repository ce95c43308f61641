"""Helpers of the chip benchmark's CPU tests.

Nothing here touches a TPU: the harness is loaded under its own module
name and driven with ``require_chip=False`` at a tiny size, with the
Pallas kernel in interpret mode.
"""
import importlib.util
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]

# a tiny cut of every configuration and mix: fewer rows and training
# steps, the same widths
TINY = dict(model_rows=3000, sample_rows=1500, udf_train_rows=1000,
            udf_train_steps=50, tile=256)
TINY_SCAN = {"chunk_rows": 512, "warmup_chunks": 1, "setup_rows": 4096}


def load_harness():
    key = "chipbench_harness"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH_DIR / "harness.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def tiny_run(harness, workload, *, seed=2**31 + 77, seconds=1.5, traced=False,
             **kw):
    """One tiny CPU run of ``workload``."""
    return harness.run(workload, seed, seconds, traced, require_chip=False,
                       config_override=TINY, traffic_override=TINY_SCAN, **kw)
