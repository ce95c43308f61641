"""Readings for the limits of the check: run one cell over many seeds in
one process (the configuration's model is built once) and print, per
seed, every number the check compares and the end-to-end metrics.

    python3 benchmarks/chip/tools/readings.py --workload synth3.scan \
        --seeds 1,2,3 --seconds 10 [--quant-dtype int8]

``--quant-dtype int8`` switches on the program's own int8 cascade path:
the control, whose readings set the upper end of ``kernel_gap``.  The
limits in the configuration are not applied here: every run prints its
numbers whatever they read.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--quant-dtype", default=None)
    args = ap.parse_args()

    import harness

    reuse = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        r = harness.run(args.workload, seed, args.seconds, False,
                        reuse=reuse, quant_dtype=args.quant_dtype)
        row = {"workload": args.workload, "seed": seed,
               "quant_dtype": args.quant_dtype, "correct": r["correct"],
               "checks": {k: v["value"] for k, v in r["checks"].items()},
               "metrics": {k: v["value"] for k, v in r["metrics"].items()},
               "wall_s": time.perf_counter() - t0}
        print("READING " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
