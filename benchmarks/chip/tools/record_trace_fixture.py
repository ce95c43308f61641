"""Record the small trace the reduction's test reads: one short traced
run of a cell on the chip, its extracted events cut to the window and
to at most ``--max-ops`` device ops, written as JSON.

    python3 benchmarks/chip/tools/record_trace_fixture.py \
        --workload synth3.scan --out chiprun_out/trace_fixture.json
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--max-ops", type=int, default=400)
    args = ap.parse_args()

    import harness

    full = args.out + ".full"
    harness.run(args.workload, 3, args.seconds, True, keep_trace=full)
    raw = json.loads(Path(full).read_text())
    Path(full).unlink()
    lo, hi = harness.trace_mod.window_of(raw)
    dev = sorted(raw["devices"])[0]
    ops = [e for e in raw["devices"][dev]["ops"] if e[1] + e[2] > lo
           and e[1] < hi]
    cut = ops[:args.max_ops]
    hi = cut[-1][1] + cut[-1][2] + 1000.0 if len(ops) > len(cut) else hi
    keep = lambda evs: [e for e in evs if e[1] + e[2] > lo and e[1] < hi]
    spans = [s for s in raw["spans"] if s[2] > lo and s[1] < hi]
    spans = [(n, a, min(b, hi)) if n == harness.trace_mod.WINDOW_SPAN
             else (n, a, b) for n, a, b in spans]
    out = {"devices": {dev: {"ops": keep(cut),
                             "modules": keep(raw["devices"][dev]["modules"])}},
           "spans": spans}
    from repro.util import atomic_write_text

    atomic_write_text(args.out, json.dumps(out))
    print(f"{len(out['devices'][dev]['ops'])} ops, {len(spans)} spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
