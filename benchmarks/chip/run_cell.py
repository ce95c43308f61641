"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py --workload synth3.scan \
        --seed 12345 --seconds 10 --trace 0

The cell, its configuration, traffic mix and metrics are found by name in
``BENCHMARK.json``.  Set-up (record process, UDF training, the optimizer,
the server and every shape the cell's traffic uses) comes first; then the
window measures for ``--seconds``; then the served output is checked
against the plain reference.  The check's numbers, each beside its
limit, are the last lines on standard error; the last line on standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
``checks`` last).  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
