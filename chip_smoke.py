"""Bring-up smoke run of the CORE serving path on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # the K=4 host fleet, one host per chip

One process drives the normal serving path — ``CoreSession`` -> engine ->
fused ``cascade_score`` kernel (compiled, never Pallas interpret mode) ->
UDF — over a seeded synthetic stream: 120,000 served records of 64
features (the scale of one of the paper's image/video corpora), three
predicates whose UDFs are MLPs at the library's default width (hidden
256, depth 4), and a ``core``-mode plan at accuracy target 0.9.  Every
phase is checked against the ORIG plan (every UDF on every record):

  (a) one query served through ``CoreSession.serve()``, fp32 cascade;
  (b) the same query with int8 packed weights;
  (c) a 4-tenant session (``MultiQueryEngine``, one shared stacked scorer);
  (d) ``execute_plan(..., use_kernel=True, fused=True)``, the executor's
      on-device compaction path.

``--chips 4`` runs only the K=4 host fleet (``CoreSession.serve(hosts=4)``,
inline transport): host k serves on ``jax.devices()[k]``.

A phase passes when its served accuracy meets the target, every record
left the pipeline exactly once (emitted or rejected, nothing in flight),
every proxied stage was gated by the fused kernel, and the kernel ran
compiled.  The lines before the last report set-up facts (compile and
wall seconds, records served, accuracy, peak device memory, compile-cache
hits), not benchmark numbers.  The last line, printed only when every
phase passed, is one JSON object naming the device.  Without a TPU the
script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SEED = 0
N_SAMPLE = 6_000      # optimization sample: the launcher's 5% of the stream
N_SERVE = 120_000     # served records
N_FEATURES = 64
ACCURACY = 0.9
UDF_COST_MS = 20.0    # declared UDF cost: the paper's regime for the optimizer
TENANT_COLUMNS = ([0, 1, 2], [0, 1], [1, 2], [2, 0])
FLEET_HOSTS = 4


class CompileLog:
    """Backend compiles (count, seconds) and persistent-cache hits, read
    from ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


class Finalized:
    """Finalize hook: every record id that left an engine, and how."""

    def __init__(self, engine):
        self.emitted, self.rejected = [], []
        engine.add_finalize_hook(self)

    def __call__(self, emitted, rejected, _version):
        self.emitted.extend(emitted)
        self.rejected.extend(rejected)

    def conserved(self, engine, n: int) -> bool:
        ids = self.emitted + self.rejected
        return (engine.in_flight() == 0 and len(ids) == n
                and len(set(ids)) == n
                and engine.stats.emitted + engine.stats.rejected == n)


def served_accuracy(emitted, orig_set) -> float:
    return len(set(emitted) & orig_set) / max(len(orig_set), 1)


def engine_checks(engine, fin: Finalized, n: int, orig_set) -> dict:
    """The pass conditions for one single-query engine after a drain."""
    proxied = [si for si, s in enumerate(engine.plan.stages)
               if s.proxy is not None]
    acc = served_accuracy(engine.emitted, orig_set)
    return {
        "accuracy": acc,
        "checks": {
            "accuracy>=target": acc >= ACCURACY,
            "conservation": fin.conserved(engine, n),
            "fused kernel on every proxied stage": bool(proxied) and all(
                engine.stats.stage_used_kernel[si] for si in proxied),
            "interpret=False": (engine.cascade is not None
                                and engine.cascade.interpret is False),
        },
    }


def run_phase(name: str, fn, log: CompileLog, device) -> bool:
    c0, s0, h0 = log.snapshot()
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception as e:  # noqa: BLE001 - reported, then the run fails
        import traceback

        traceback.print_exc()
        print(f"phase {name}: FAILED with {type(e).__name__}: {e}")
        return False
    wall = time.perf_counter() - t0
    c1, s1, h1 = log.snapshot()
    stats = device.memory_stats() or {}
    failed = [k for k, ok in out["checks"].items() if not ok]
    print(f"phase {name}: served {out['served']} records, accuracy "
          f"{out['accuracy']} (target {ACCURACY}), wall {wall} s, "
          f"compile {s1 - s0} s over {c1 - c0} programs, compile-cache "
          f"hits {h1 - h0}, peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    for line in out.get("notes", []):
        print(f"  {line}")
    print(f"  checks: " + ", ".join(
        f"{k} {'ok' if ok else 'FAILED'}" for k, ok in out["checks"].items()))
    return not failed


def build_workload(n_queries: int):
    from repro.core import execute_plan, orig_plan
    from repro.data.synthetic import make_dataset, make_query, make_udfs

    ds = make_dataset(n=N_SAMPLE + N_SERVE, n_features=N_FEATURES,
                      n_columns=3, seed=SEED)
    udfs = make_udfs(ds, seed=SEED, declared_cost_ms=UDF_COST_MS)
    queries = [make_query(ds, udfs, columns=cols, target_selectivity=0.5,
                          accuracy_target=ACCURACY, seed=SEED + 1 + i)
               for i, cols in enumerate(TENANT_COLUMNS[:n_queries])]
    x_sample, x_serve = ds.x[:N_SAMPLE], ds.x[N_SAMPLE:]
    origs = [set(execute_plan(orig_plan(q), x_serve).passed.tolist())
             for q in queries]
    return x_sample, x_serve, queries, origs


def phase_single(query, x_sample, x_serve, orig_set, quant_dtype=None):
    from repro.core import CoreSession

    session = CoreSession(seed=SEED)
    session.register_query(query, x_sample, quant_dtype=quant_dtype)
    engine = session.serve()
    fin = Finalized(engine)
    session.run_stream(x_serve)
    out = engine_checks(engine, fin, len(x_serve), orig_set)
    out["served"] = len(x_serve)
    out["notes"] = [f"plan: {engine.plan.describe()}".replace("\n", " | "),
                    f"scorer dtype {engine.cascade.dtype}, block_m "
                    f"{engine.cascade.block_m}"]
    return out


def phase_tenants(queries, x_sample, x_serve, origs):
    from repro.core import CoreSession

    session = CoreSession(seed=SEED)
    for q in queries:
        session.register_query(q, x_sample)
    eng = session.serve()
    fins = [Finalized(srv) for srv in eng.servers]
    session.run_stream(x_serve)
    n = len(x_serve)
    checks, accs = {}, []
    for qid, (srv, fin, orig_set) in enumerate(zip(eng.servers, fins, origs)):
        out = engine_checks(srv, fin, n, orig_set)
        accs.append(out["accuracy"])
        for k, ok in out["checks"].items():
            if k != "interpret=False":  # tenants share the stacked scorer
                checks[f"q{qid} {k}"] = ok
    checks["session conserved"] = eng.conserved()[0]
    checks["interpret=False"] = (eng.scorer is not None
                                 and eng.scorer.interpret is False)
    return {"served": n * len(queries), "accuracy": min(accs),
            "checks": checks,
            "notes": [f"per-tenant accuracy {accs}",
                      f"session {eng.session_stats()['dedupe']}"]}


def phase_executor(query, x_sample, x_serve, orig_set):
    from repro.core import build_plan, execute_plan
    from repro.kernels.ops import interpret_default

    plan = build_plan(query, x_sample)
    res = execute_plan(plan, x_serve, use_kernel=True, fused=True)
    passed = res.passed.tolist()
    proxied = [si for si, s in enumerate(plan.stages) if s.proxy is not None]
    acc = served_accuracy(passed, orig_set)
    return {
        "served": len(x_serve), "accuracy": acc,
        "checks": {
            "accuracy>=target": acc >= ACCURACY,
            "conservation": (res.stages[0].n_in == len(x_serve)
                             and len(set(passed)) == len(passed)),
            "fused kernel on every proxied stage": bool(proxied) and all(
                res.stages[si].used_kernel for si in proxied),
            "interpret=False": interpret_default() is False,
        },
    }


def phase_fleet(query, x_sample, x_serve, orig_set):
    import jax

    from repro.core import CoreSession

    session = CoreSession(seed=SEED)
    session.register_query(query, x_sample)
    fleet = session.serve(hosts=FLEET_HOSTS, transport="inline")
    fins = [Finalized(h.engine) for h in fleet.hosts]
    stats = session.run_stream(x_serve)
    emitted = [i for host in fleet.emitted for i in host]
    ids = [i for f in fins for i in f.emitted + f.rejected]
    acc = served_accuracy(emitted, orig_set)
    notes, checks = [], {}
    for h in fleet.hosts:
        cascade = h.engine.cascade
        placed = sorted(d.id for d in cascade.w1.devices())
        notes.append(f"host {h.host_id}: device id {h.device.id} "
                     f"({h.device.device_kind}), scorer operands on device "
                     f"ids {placed}, submitted {h.submitted}")
        checks[f"host {h.host_id} on its device"] = placed == [h.device.id]
        checks[f"host {h.host_id} interpret=False"] = cascade.interpret is False
        proxied = [si for si, s in enumerate(h.engine.plan.stages)
                   if s.proxy is not None]
        checks[f"host {h.host_id} fused kernel"] = bool(proxied) and all(
            h.engine.stats.stage_used_kernel[si] for si in proxied)
    n_dev = min(FLEET_HOSTS, len(jax.devices()))
    checks["hosts on distinct devices"] = (
        len({h.device.id for h in fleet.hosts}) == n_dev)
    checks["accuracy>=target"] = acc >= ACCURACY
    checks["conservation"] = (
        stats.submitted == len(x_serve)
        and stats.emitted + stats.rejected == len(x_serve)
        and all(h.engine.in_flight() == 0 for h in fleet.hosts)
        and len(ids) == len(x_serve) and len(set(ids)) == len(x_serve))
    notes.append(f"{stats.swaps_committed} quorum swap(s), final epoch "
                 f"{stats.final_epoch}")
    return {"served": len(x_serve), "accuracy": acc, "checks": checks,
            "notes": notes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, FLEET_HOSTS), default=1,
                    help=f"1: phases (a)-(d) on one chip; {FLEET_HOSTS}: "
                         f"only the {FLEET_HOSTS}-host fleet, one host per "
                         f"chip")
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; this "
              f"script never falls back to the CPU", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.util import enable_compile_cache

    cache_dir = enable_compile_cache()
    log = CompileLog()
    device = jax.devices()[0]
    print(f"device_kind {device.device_kind}, platform {device.platform}, "
          f"{len(jax.devices())} device(s) visible; compile cache {cache_dir}")

    t0 = time.perf_counter()
    c0, s0, h0 = log.snapshot()
    fleet = args.chips == FLEET_HOSTS
    x_sample, x_serve, queries, origs = build_workload(
        1 if fleet else len(TENANT_COLUMNS))
    c1, s1, h1 = log.snapshot()
    print(f"set-up: {len(x_sample)} sample + {len(x_serve)} served records, "
          f"F={x_serve.shape[1]}, {len(queries)} queries over 3 UDFs "
          f"(hidden 256, depth 4) with ORIG references, wall "
          f"{time.perf_counter() - t0} s, compile {s1 - s0} s over "
          f"{c1 - c0} programs, compile-cache hits {h1 - h0}")

    q, orig = queries[0], origs[0]
    if fleet:
        phases = [(f"fleet K={FLEET_HOSTS}", lambda: phase_fleet(
            q, x_sample, x_serve, orig))]
    else:
        phases = [
            ("(a) fp32 CoreSession", lambda: phase_single(
                q, x_sample, x_serve, orig)),
            ("(b) int8 CoreSession", lambda: phase_single(
                q, x_sample, x_serve, orig, quant_dtype="int8")),
            ("(c) 4-tenant CoreSession", lambda: phase_tenants(
                queries, x_sample, x_serve, origs)),
            ("(d) fused executor", lambda: phase_executor(
                q, x_sample, x_serve, orig)),
        ]
    ok = all([run_phase(name, fn, log, device) for name, fn in phases])
    print(f"total: wall {time.perf_counter() - t0} s, compile {log.seconds} s "
          f"over {log.count} programs, compile-cache hits {log.cache_hits}")
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
