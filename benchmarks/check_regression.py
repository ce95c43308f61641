"""CI regression gate for the fused proxy-scoring hot path, the adaptive
serving loop, K=4 sharded serving, the fault-tolerance scenarios, the
quantized packed cascade, the SLO-aware serving front end, the
cross-query plan cache (including multi-donor warm-start blending), and
the multi-query CoreSession.

Runs the components benchmark's proxy-throughput measurement, the
drifting-stream adaptive-serving benchmark, the K=4 quorum-swap fleet
benchmark, the three fault-tolerance scenarios (coordinator failover
mid-epoch, straggler fencing, pooled-kappa² escalation), the
quantized-cascade benchmark (int8 bytes-moved speedup, decision-flip
parity, autotune sweep), the serving-front-end goodput benchmark
(SLO goodput under overload with backpressure on vs the no-backpressure
collapse control, plus conservation through a K=4 quorum swap), and the
plan-cache benchmark (warm-start node reduction at equal Eq. 3.1 cost,
exact-repeat replay ratio, dissimilarity fallback, byte-stable
persistence), writes ``BENCH_components.json`` at the repo
root plus the autotune sweep table under ``results/autotune_sweep.json``
(the nightly CI artifact), prints a unified **before/after delta table**
for every gated metric (baseline recorded value vs this run, floor,
margin, status), and exits nonzero when any ENFORCED gate regresses
against the checked-in baseline
(``benchmarks/baseline_components.json``).

Gate classes:

  * architectural invariants (speedups, protocol correctness booleans) —
    host-independent, always enforced;
  * absolute wall-clock floors — host-dependent, ADVISORY unless pinned
    via the corresponding ``REGRESSION_*`` env override.

Usage:
  python benchmarks/check_regression.py [--quick] [--update-baseline]

``--update-baseline`` rewrites the ``recorded_*`` fields of
``baseline_components.json`` from this run (floors and the comment are
preserved) — the intentional re-baselining path after a known perf
change, instead of hand-editing JSON.  With the flag set, gate failures
are reported but do not fail the process.

Env overrides: REGRESSION_MIN_ROWS_PER_S, REGRESSION_MIN_SPEEDUP,
REGRESSION_MIN_MLP_SPEEDUP, REGRESSION_MIN_ADAPTIVE_SPEEDUP,
REGRESSION_MIN_SHARDED_SPEEDUP, REGRESSION_MAX_CONSENSUS_MS,
REGRESSION_MIN_QUANT_SPEEDUP, REGRESSION_MIN_GOODPUT_RATIO,
REGRESSION_MIN_MULTIQUERY_SPEEDUP.
"""
from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.bench_adaptive import bench_adaptive_throughput  # noqa: E402
from benchmarks.bench_components import (  # noqa: E402
    BENCH_JSON,
    bench_mlp_throughput,
    bench_proxy_throughput,
    write_bench_json,
)
from benchmarks.bench_multiquery import bench_multiquery  # noqa: E402
from benchmarks.bench_plan_cache import (  # noqa: E402
    bench_multidonor,
    bench_plan_cache,
)
from benchmarks.bench_quant import SWEEP_JSON, bench_quant  # noqa: E402
from benchmarks.bench_serving_frontend import (  # noqa: E402
    bench_frontend_goodput,
    bench_frontend_sharded,
)
from benchmarks.bench_sharded import (  # noqa: E402
    bench_fault_tolerance,
    bench_sharded_throughput,
)
from repro.analysis.corelint import load_baseline, run_corelint  # noqa: E402
from repro.analysis.protocol_check import CheckConfig, check  # noqa: E402
from repro.util import atomic_write_text, enable_compile_cache  # noqa: E402

REPO_ROOT = Path(__file__).resolve().parent.parent
CORELINT_BASELINE = REPO_ROOT / "corelint_baseline.json"


def run_static_analysis() -> dict:
    """The lint-lane checks, as gated metrics: corelint must be clean
    (zero non-baselined findings over src/ + benchmarks/) and the strict
    swap-protocol model check must hold over a state space at least as
    large as the recorded one — a shrinking space means the enumeration
    silently lost reach, which would let a protocol regression hide."""
    lint = run_corelint([REPO_ROOT / "src", REPO_ROOT / "benchmarks"],
                        root=REPO_ROOT,
                        baseline=load_baseline(CORELINT_BASELINE))
    strict = check(CheckConfig(n_hosts=3))
    legacy = check(CheckConfig(n_hosts=3, legacy_acks=True))
    return {
        "lint_violations": len(lint.violations),
        "lint_suppressed": lint.suppressed,
        "lint_baselined": lint.baselined,
        "lint_files_scanned": lint.files_scanned,
        "protocol_safe": bool(strict.violation is None
                              and all(strict.witnesses.values())),
        "protocol_states_explored": strict.states_explored,
        "protocol_transitions": strict.transitions,
        "protocol_witnesses": strict.witnesses,
        # the checker must still FIND the pre-attempt-nonce bug, or it
        # has lost its teeth
        "protocol_teeth": bool(legacy.violation is not None),
    }

BASELINE = Path(__file__).resolve().parent / "baseline_components.json"


@dataclass
class Gate:
    """One gated metric: a current value checked against a floor (or
    ceiling), with the baseline's recorded value alongside for the
    before/after delta table."""

    name: str
    current: float
    floor: Optional[float]  # None = informational row, never fails
    recorded: Optional[float] = None  # baseline value (before)
    higher_is_better: bool = True
    enforced: bool = True  # False = advisory (warn, don't fail)
    fmt: str = "{:.2f}"
    record_key: Optional[str] = None  # baseline key --update-baseline rewrites

    @property
    def ok(self) -> bool:
        if self.floor is None:
            return True
        return (self.current >= self.floor if self.higher_is_better
                else self.current <= self.floor)

    @property
    def margin(self) -> Optional[float]:
        if self.floor is None:
            return None
        return (self.current - self.floor if self.higher_is_better
                else self.floor - self.current)

    @property
    def status(self) -> str:
        if self.floor is None:
            return "info"
        if self.ok:
            return "OK" if self.enforced else "OK (advisory)"
        return "FAIL" if self.enforced else "WARN (advisory)"


def _print_delta_table(gates: List[Gate]) -> None:
    header = (f"{'metric':<34} {'baseline':>12} {'current':>12} "
              f"{'floor':>10} {'margin':>10}  status")
    print("\n== regression gate delta table (baseline vs this run) ==")
    print(header)
    print("-" * len(header))
    for g in gates:
        def fv(v):
            return "-" if v is None else g.fmt.format(v)

        print(f"{g.name:<34} {fv(g.recorded):>12} {fv(g.current):>12} "
              f"{fv(g.floor):>10} {fv(g.margin):>10}  {g.status}")
    print("-" * len(header))


def _update_baseline(base: dict, gates: List[Gate]) -> None:
    for g in gates:
        if g.record_key:
            # count-valued gates (fmt {:.0f}) stay ints in the baseline —
            # every Gate.current is a float, so type-sniffing would churn
            # recorded counts to 2.0/1.0 on each re-baseline
            base[g.record_key] = (int(round(g.current))
                                  if g.fmt == "{:.0f}"
                                  else round(g.current, 4))
    atomic_write_text(BASELINE, json.dumps(base, indent=2) + "\n")
    print(f"baseline updated: {BASELINE} "
          f"({sum(1 for g in gates if g.record_key)} recorded values)")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    enable_compile_cache()
    quick = "--quick" in argv
    update_baseline = "--update-baseline" in argv
    throughput = bench_proxy_throughput(n_rows=24_576 if quick else 49_152)
    mlp = bench_mlp_throughput(n_rows=24_576 if quick else 49_152)
    # deliberately NOT shrunk by --quick: the 1.3x floor is an acceptance
    # invariant of the FULL drifting stream — a shorter drifted segment
    # dilutes the stale-plan span the adaptation amortizes against
    # (measured 1.25x at n_after=18k vs 1.38x at 30k), so a quick run
    # would fail the gate without any code regression
    adaptive = bench_adaptive_throughput()
    sharded = bench_sharded_throughput(
        n_before=1_500 if quick else 2_000,
        n_after=4_000 if quick else 6_000)
    # fixed-seed fixed-size scenarios: deterministic in --quick and full
    ft = bench_fault_tolerance()
    quant = bench_quant()
    # cost-model clock + seeded trace: deterministic per host; --quick
    # shortens the trace, both lengths sit well inside the gates
    fe = bench_frontend_goodput(n_req=32 if quick else 48)
    fes = bench_frontend_sharded()
    # fixed workload + seeds: node counts and costs deterministic per
    # environment, only the hit-ratio column is wall-clock
    pc = bench_plan_cache()
    md = bench_multidonor()
    # N=4 overlapping queries, one shared session vs 4 isolated servers;
    # all gated quantities ride the cost-model clock
    mq = bench_multiquery()
    sa = run_static_analysis()
    write_bench_json(throughput, adaptive, mlp, sharded, fault_tolerance=ft,
                     quant={k: v for k, v in quant.items()
                            if k != "sweep_rows"},
                     frontend={**fe, "sharded": fes},
                     plan_cache={**pc, "multidonor": md},
                     static_analysis=sa, multiquery=mq)
    print(f"wrote {BENCH_JSON}")
    SWEEP_JSON.parent.mkdir(parents=True, exist_ok=True)
    atomic_write_text(SWEEP_JSON, json.dumps(
        {"rows": quant["sweep_rows"],
         "wins": quant["autotune_wins"],
         "shapes": quant["autotune_shapes"]}, indent=1) + "\n")
    print(f"wrote {SWEEP_JSON}")

    base = json.loads(BASELINE.read_text())
    rows_env = os.environ.get("REGRESSION_MIN_ROWS_PER_S")
    min_rows = float(rows_env) if rows_env else float(base["min_fused_rows_per_s"])
    min_speedup = float(os.environ.get(
        "REGRESSION_MIN_SPEEDUP", base["min_speedup"]))
    min_mlp = float(os.environ.get(
        "REGRESSION_MIN_MLP_SPEEDUP", base["min_mlp_speedup"]))
    min_adaptive = float(os.environ.get(
        "REGRESSION_MIN_ADAPTIVE_SPEEDUP", base["min_adaptive_speedup"]))
    min_sharded = float(os.environ.get(
        "REGRESSION_MIN_SHARDED_SPEEDUP", base["min_sharded_speedup"]))
    consensus_env = os.environ.get("REGRESSION_MAX_CONSENSUS_MS")
    max_consensus = (float(consensus_env) if consensus_env
                     else float(base["advisory_max_consensus_ms"]))
    min_quant = float(os.environ.get(
        "REGRESSION_MIN_QUANT_SPEEDUP", base["min_quant_speedup"]))
    max_quant_acc_delta = float(base["max_quant_accuracy_delta"])
    min_goodput = float(os.environ.get(
        "REGRESSION_MIN_GOODPUT_RATIO", base["min_goodput_ratio"]))
    max_goodput_nobp = float(base["max_goodput_ratio_nobp"])
    max_hit_ratio = float(base["max_plan_cache_hit_ratio"])
    min_protocol_states = float(base["recorded_protocol_states"])
    min_multiquery = float(os.environ.get(
        "REGRESSION_MIN_MULTIQUERY_SPEEDUP", base["min_multiquery_speedup"]))
    min_mq_fairness = float(base["min_multiquery_fairness"])

    worst_consensus = max(sharded["consensus_ms_per_swap"] or [0.0])
    fo, strag, pooled = (ft["failover"], ft["straggler"], ft["pooled_kappa"])
    gates = [
        # ----- fused scoring hot path -----
        Gate("fused_rows_per_s", throughput["fused_rows_per_s"], min_rows,
             base.get("recorded_fused_rows_per_s"), fmt="{:.0f}",
             enforced=bool(rows_env), record_key="recorded_fused_rows_per_s"),
        Gate("fused_speedup", throughput["speedup"], min_speedup,
             base.get("recorded_speedup"), record_key="recorded_speedup"),
        Gate("fused_used_kernel", float(all(throughput["fused_used_kernel"])),
             1.0, 1.0, fmt="{:.0f}"),
        Gate("mlp_fused_speedup", mlp["mlp_fused_speedup"], min_mlp,
             base.get("recorded_mlp_fused_speedup"),
             record_key="recorded_mlp_fused_speedup"),
        Gate("mlp_used_kernel", float(all(mlp["fused_used_kernel"])),
             1.0, 1.0, fmt="{:.0f}"),
        # ----- adaptive serving -----
        Gate("adaptive_speedup", adaptive["adaptive_speedup"], min_adaptive,
             base.get("recorded_adaptive_speedup"),
             record_key="recorded_adaptive_speedup"),
        Gate("adaptive_accuracy", adaptive["adaptive_accuracy"],
             adaptive["accuracy_target"],
             base.get("recorded_adaptive_accuracy"), fmt="{:.3f}",
             record_key="recorded_adaptive_accuracy"),
        Gate("warm_bnb_nodes", float(adaptive["warm_nodes"]),
             float(adaptive["cold_nodes"] - 1),
             base.get("recorded_warm_nodes"), higher_is_better=False,
             fmt="{:.0f}", record_key="recorded_warm_nodes"),
        Gate("adaptive_plan_swaps", float(adaptive["plan_swaps"]), 1.0,
             None, fmt="{:.0f}"),
        # ----- sharded serving -----
        Gate("sharded_speedup", sharded["sharded_speedup"], min_sharded,
             base.get("recorded_sharded_speedup"),
             record_key="recorded_sharded_speedup"),
        Gate("sharded_swaps_committed", float(sharded["swaps_committed"]),
             1.0, base.get("recorded_sharded_swaps"), fmt="{:.0f}",
             record_key="recorded_sharded_swaps"),
        Gate("sharded_conserved", float(sharded["conserved"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("consensus_lag_records",
             float(sharded["consensus_lag_records"]), 0.0, 0.0,
             higher_is_better=False, fmt="{:.0f}"),
        Gate("worst_consensus_ms", worst_consensus, max_consensus,
             base.get("recorded_worst_consensus_ms"),
             higher_is_better=False, fmt="{:.1f}",
             enforced=bool(consensus_env),
             record_key="recorded_worst_consensus_ms"),
        # ----- fault tolerance: coordinator failover mid-epoch -----
        Gate("failover_count", float(fo["failovers"]), 1.0,
             base.get("recorded_failover_count"), fmt="{:.0f}",
             record_key="recorded_failover_count"),
        Gate("failover_swaps_committed", float(fo["swaps_committed"]), 1.0,
             base.get("recorded_failover_swaps"), fmt="{:.0f}",
             record_key="recorded_failover_swaps"),
        Gate("failover_conserved",
             float(fo["conserved"] and fo["epochs_agree"]), 1.0, 1.0,
             fmt="{:.0f}"),
        # ----- fault tolerance: straggler fencing -----
        Gate("straggler_commits_unblocked",
             float(strag["committed_while_fenced"]), 1.0, 1.0, fmt="{:.0f}"),
        Gate("straggler_resynced", float(strag["straggler_resynced"]), 1.0,
             base.get("recorded_straggler_resyncs"), fmt="{:.0f}",
             record_key="recorded_straggler_resyncs"),
        Gate("straggler_conserved",
             float(strag["conserved"] and strag["epochs_agree"]), 1.0, 1.0,
             fmt="{:.0f}"),
        # ----- fault tolerance: pooled kappa² escalation -----
        Gate("pooled_local_votes", float(pooled["votes_cast"]), 0.0, 0.0,
             higher_is_better=False, fmt="{:.0f}"),
        Gate("pooled_swaps_committed", float(pooled["pooled_swaps"]), 1.0,
             base.get("recorded_pooled_swaps"), fmt="{:.0f}",
             record_key="recorded_pooled_swaps"),
        Gate("pooled_escalated_bnb", float(pooled["all_bnb"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("pooled_conserved", float(pooled["conserved"]), 1.0, 1.0,
             fmt="{:.0f}"),
        # ----- quantized packed cascade (bytes-moved model; see
        # ----- bench_quant.py for why the speedup gate is modeled) -----
        Gate("quant_fused_speedup", quant["quant_fused_speedup"], min_quant,
             base.get("recorded_quant_speedup"),
             record_key="recorded_quant_speedup"),
        Gate("quant_parity_within_tol",
             float(quant["parity"]["flips_within_tol"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("quant_accuracy_delta", quant["accuracy_delta"],
             max_quant_acc_delta, base.get("recorded_quant_accuracy_delta"),
             higher_is_better=False, fmt="{:.4f}",
             record_key="recorded_quant_accuracy_delta"),
        Gate("quant_sel_delta", quant["parity"]["max_sel_delta"], None,
             None, fmt="{:.4f}"),
        Gate("quant_bytes_per_launch_kb",
             quant["bytes_quant"] / 1024.0, None, None, fmt="{:.0f}"),
        Gate("quant_mbu_advisory", quant["autotune_mbu"], None, None,
             fmt="{:.3f}"),
        Gate("autotune_beats_static_shapes", float(quant["autotune_wins"]),
             2.0, base.get("recorded_autotune_wins"), fmt="{:.0f}",
             record_key="recorded_autotune_wins"),
        Gate("autotune_cache_hit", float(quant["autotune_cache_hit"]),
             1.0, 1.0, fmt="{:.0f}"),
        # ----- SLO-aware serving front end (cost-model clock; see
        # ----- bench_serving_frontend.py for the trace construction) -----
        Gate("goodput_ratio", fe["goodput_ratio"], min_goodput,
             base.get("recorded_goodput_ratio"), fmt="{:.3f}",
             record_key="recorded_goodput_ratio"),
        Gate("goodput_ratio_nobp", fe["goodput_ratio_nobp"],
             max_goodput_nobp, base.get("recorded_goodput_ratio_nobp"),
             higher_is_better=False, fmt="{:.3f}",
             record_key="recorded_goodput_ratio_nobp"),
        Gate("frontend_conserved", float(fe["conserved"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("frontend_p95_latency_ms", fe["p95_latency_ms"], None, None,
             fmt="{:.0f}"),
        Gate("frontend_records_shed", float(fe["records_shed"]), None, None,
             fmt="{:.0f}"),
        Gate("frontend_sharded_swaps", float(fes["swaps_committed"]), 1.0,
             base.get("recorded_frontend_sharded_swaps"), fmt="{:.0f}",
             record_key="recorded_frontend_sharded_swaps"),
        Gate("frontend_sharded_conserved", float(fes["conserved"]), 1.0,
             1.0, fmt="{:.0f}"),
        # ----- cross-query plan cache (see bench_plan_cache.py) -----
        Gate("plan_cache_warm_nodes", float(pc["warm_nodes"]),
             float(pc["cold_nodes"] - 1),
             base.get("recorded_plan_cache_warm_nodes"),
             higher_is_better=False, fmt="{:.0f}",
             record_key="recorded_plan_cache_warm_nodes"),
        Gate("plan_cache_cold_nodes", float(pc["cold_nodes"]), None,
             base.get("recorded_plan_cache_cold_nodes"), fmt="{:.0f}",
             record_key="recorded_plan_cache_cold_nodes"),
        Gate("plan_cache_same_cost", float(pc["same_cost"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("plan_cache_hit_build_ratio", pc["hit_build_ratio"],
             max_hit_ratio, base.get("recorded_plan_cache_hit_ratio"),
             higher_is_better=False, fmt="{:.4f}",
             record_key="recorded_plan_cache_hit_ratio"),
        Gate("plan_cache_dissimilar_cold",
             float(pc["dissimilar_cold"]
                   and pc["dissimilar_accuracy_cached"]
                   >= pc["dissimilar_accuracy_uncached"] - 1e-9),
             1.0, 1.0, fmt="{:.0f}"),
        Gate("plan_cache_roundtrip_stable", float(pc["roundtrip_stable"]),
             1.0, 1.0, fmt="{:.0f}"),
        # ----- multi-donor warm-start blending (bench_plan_cache.py) -----
        Gate("multidonor_warm_le_single",
             float(md["multi_le_single"] and md["same_cost"]
                   and md["multi_path"] == "warm"), 1.0, 1.0, fmt="{:.0f}"),
        Gate("multidonor_warm_nodes", float(md["multi_donor_nodes"]),
             float(md["single_donor_nodes"]),
             base.get("recorded_multidonor_warm_nodes"),
             higher_is_better=False, fmt="{:.0f}",
             record_key="recorded_multidonor_warm_nodes"),
        Gate("multidonor_donors_used", float(md["multi_donors_used"]),
             2.0, None, fmt="{:.0f}"),
        # ----- multi-query session (see bench_multiquery.py) -----
        Gate("multiquery_speedup", mq["speedup"], min_multiquery,
             base.get("recorded_multiquery_speedup"),
             record_key="recorded_multiquery_speedup"),
        Gate("multiquery_conserved", float(mq["conserved"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("multiquery_emissions_match", float(mq["emissions_match"]),
             1.0, 1.0, fmt="{:.0f}"),
        Gate("multiquery_fairness", mq["fairness"], min_mq_fairness,
             base.get("recorded_multiquery_fairness"), fmt="{:.3f}",
             record_key="recorded_multiquery_fairness"),
        Gate("multiquery_dedupe_rate", mq["dedupe_rate"], None,
             base.get("recorded_multiquery_dedupe_rate"), fmt="{:.3f}",
             record_key="recorded_multiquery_dedupe_rate"),
        # ----- static analysis & protocol checking (lint lane, gated) -----
        Gate("lint_violations", float(sa["lint_violations"]), 0.0, 0.0,
             higher_is_better=False, fmt="{:.0f}"),
        Gate("protocol_safe", float(sa["protocol_safe"]), 1.0, 1.0,
             fmt="{:.0f}"),
        Gate("protocol_states_explored",
             float(sa["protocol_states_explored"]), min_protocol_states,
             base.get("recorded_protocol_states"), fmt="{:.0f}",
             record_key="recorded_protocol_states"),
        Gate("protocol_checker_has_teeth", float(sa["protocol_teeth"]),
             1.0, 1.0, fmt="{:.0f}"),
    ]

    _print_delta_table(gates)

    failures = [
        f"{g.name} {g.fmt.format(g.current)} vs floor {g.fmt.format(g.floor)}"
        for g in gates if not g.ok and g.enforced
    ]
    for g in gates:
        if not g.ok and not g.enforced:
            print(f"WARNING (advisory, host-dependent): {g.name} "
                  f"{g.fmt.format(g.current)} vs bound "
                  f"{g.fmt.format(g.floor)}")

    if update_baseline:
        _update_baseline(base, gates)
        if failures:
            print("NOTE: gates failing while re-baselining:",
                  *failures, sep="\n  ")
        return 0
    if failures:
        print("REGRESSION:", *failures, sep="\n  ")
        return 1
    print(
        f"OK: fused {throughput['fused_rows_per_s']:.0f} rows/s "
        f"({throughput['speedup']:.2f}x over per-stage); fused-MLP "
        f"{mlp['mlp_fused_speedup']:.2f}x; adaptive drift "
        f"{adaptive['adaptive_speedup']:.2f}x, accuracy "
        f"{adaptive['adaptive_accuracy']:.3f}; sharded K="
        f"{sharded['n_hosts']} {sharded['sharded_speedup']:.2f}x, "
        f"{sharded['swaps_committed']} quorum swap(s); failover "
        f"{fo['resolution']} ({fo['swaps_committed']} committed); "
        f"straggler fenced+resynced ({strag['fences']}/"
        f"{strag['straggler_resynced']}); pooled kappa² "
        f"{pooled['pooled_swaps']} bnb swap(s) on {pooled['votes_cast']} "
        f"votes; quant {quant['quant_fused_speedup']:.2f}x bytes-moved, "
        f"parity {'OK' if quant['parity']['flips_within_tol'] else 'FAIL'}, "
        f"autotune {quant['autotune_wins']}/{quant['autotune_shapes']} "
        f"shapes; frontend goodput {fe['goodput_ratio']:.3f} "
        f"(nobp {fe['goodput_ratio_nobp']:.3f}), sharded swaps "
        f"{fes['swaps_committed']} conserved={fes['conserved']}; "
        f"plan cache warm {pc['warm_nodes']}/{pc['cold_nodes']} nodes, "
        f"hit ratio {pc['hit_build_ratio']:.4f}, "
        f"roundtrip={int(pc['roundtrip_stable'])}; multidonor "
        f"{md['multi_donor_nodes']}<={md['single_donor_nodes']} nodes "
        f"({md['multi_donors_used']} donors); multiquery N="
        f"{mq['n_queries']} {mq['speedup']:.2f}x, fairness "
        f"{mq['fairness']:.3f}, dedupe {mq['dedupe_rate']:.3f}, "
        f"conserved={int(mq['conserved'])}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
