"""Run one cell traced with the program's span recorder on, and print how
the window splits by program span.

    python3 benchmarks/chip/tools/program_spans.py --workload synth3.scan \
        --seeds 11,12,13 --seconds 10

Temporary: the harness does not read the program's spans yet, so this
tool patches it for the length of one run.  It goes when the harness
enables the recorder around the window and its readers compute these
shares themselves.

Each seed is one ``harness.run`` with ``--trace 1``, in which the
recorder (``repro.util.spans``) is on for the profiled window and the
trace keeps the program's spans (``engine.``, ``scorer.``) beside the
harness's, so ``breakdown.idle_gaps`` puts each idle gap down to the
innermost span of either kind.  One JSON line per seed: the run's result,
the recorder's totals over the window, and these shares of the window,
in percent:

* ``engine_enqueue_share``: self of ``engine.submit``
* ``engine_stage_share``: self of ``engine.pump`` + self of
  ``engine.stage``
* ``finalize_hook_share``: total of ``engine.finalize``
* ``scorer_launch_share``, ``scorer_fetch_share``: total of
  ``scorer.launch``, ``scorer.fetch``
* ``bench_generate_share``: the harness's ``bench.generate``

The recorder's own cost is inside every number here; ``records_per_s``
is on the run's ``window:`` line on standard error.
"""
import argparse
import contextlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))

PROGRAM_PREFIXES = ("engine.", "scorer.")


@contextlib.contextmanager
def _recording(harness, state: dict):
    """Patch the harness for one run: the recorder on for the profiled
    window, program spans kept in the trace."""
    import jax

    from repro.util import spans

    saved = (harness.Probe, harness.trace_mod.SPAN_PREFIX,
             jax.profiler.start_trace, jax.profiler.stop_trace)
    probe_cls, prefix, start, stop = saved

    class Probe(probe_cls):
        def __init__(self, traced):
            super().__init__(traced)
            state["probe"] = self

    def start_trace(*a, **kw):
        start(*a, **kw)
        spans.reset()
        spans.enable()

    def stop_trace():
        spans.disable()
        state["window"] = spans.snapshot()
        stop()

    harness.Probe = Probe
    harness.trace_mod.SPAN_PREFIX = (prefix,) + PROGRAM_PREFIXES
    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    try:
        yield
    finally:
        harness.Probe, harness.trace_mod.SPAN_PREFIX = saved[:2]
        jax.profiler.start_trace, jax.profiler.stop_trace = saved[2:]
        spans.disable()
        spans.reset()


def shares(window: dict, window_s: float) -> dict:
    """The program-span shares of one window, in percent, from the
    recorder's ``{name: (calls, total_s, self_s)}``; a share whose spans
    were not recorded is left out."""
    out = {}

    def put(key, seconds):
        if seconds is not None and window_s > 0:
            out[key] = 100.0 * seconds / window_s

    def part(name, i):
        return window[name][i] if name in window else None

    put("engine_enqueue_share", part("engine.submit", 2))
    if "engine.pump" in window:
        put("engine_stage_share",
            part("engine.pump", 2) + (part("engine.stage", 2) or 0.0))
    put("finalize_hook_share", part("engine.finalize", 1))
    put("scorer_launch_share", part("scorer.launch", 1))
    put("scorer_fetch_share", part("scorer.fetch", 1))
    return out


def measure(harness, workload: str, seed: int, seconds: float,
            **run_kw) -> dict:
    """One traced run of ``workload`` with the recorder on; the result of
    ``harness.run`` plus the program's window spans and shares."""
    state = {}
    with _recording(harness, state):
        result = harness.run(workload, seed, seconds, True, **run_kw)
    probe = state["probe"]
    window_s = probe.seconds_in("bench.window")
    program = shares(state["window"], window_s)
    program["bench_generate_share"] = (
        100.0 * probe.seconds_in("bench.generate") / window_s)
    return {"seed": seed, "window_s": window_s, "program": program,
            "window_spans": state["window"], "result": result}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = measure(harness, args.workload, seed, args.seconds)
        except harness.NoChip as e:
            print(f"program_spans: {e}", file=sys.stderr)
            return 2
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
