"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV per benchmark.  --full uses the
paper-scale query counts (slower).  Every suite runs even after another
raised; the run then lists the failures and exits 1.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import (  # noqa: E402
    bench_accuracy,
    bench_components,
    bench_correlation_impact,
    bench_qo_cost,
    bench_scalability,
    bench_time_reduction,
    roofline,
)
from repro.util import enable_compile_cache  # noqa: E402

SUITES = [
    ("correlation_impact (Fig 2, Fig 9)", bench_correlation_impact.run),
    ("time_reduction (Fig 10, Fig 11)", bench_time_reduction.run),
    ("qo_cost (Table 4)", bench_qo_cost.run),
    ("components (Fig 12, Table 5)", bench_components.run),
    ("scalability (Fig 13)", bench_scalability.run),
    ("accuracy_sweep (Fig 14, Table 6)", bench_accuracy.run),
    ("roofline (autotune sweep)",
     lambda quick: roofline.print_sweep(*roofline.cascade_sweep())),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale query counts")
    ap.add_argument("--only", default=None, help="substring filter on suite name")
    args = ap.parse_args()
    enable_compile_cache()
    t_all = time.time()
    failed = []
    print("name,us_per_call,derived")
    for name, fn in SUITES:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            fn(quick=not args.full)
        except Exception as e:  # noqa: BLE001 - the other suites still run
            print(f"bench_error_{name},0,{type(e).__name__}: {e}")
            failed.append(f"{name}: {type(e).__name__}: {e}")
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    print(f"# total {time.time()-t_all:.1f}s")
    for line in failed:
        print(f"# FAILED {line}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
