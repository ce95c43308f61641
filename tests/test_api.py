"""PR 10 API redesign: ``OptimizeOptions``/``ServeConfig`` threading,
``CoreSession.serve`` dispatch, the deprecated entry-point shims, and
the golden CLI flag round-trip (every ``launch/serve.py`` flag maps
onto a typed config field through ``FLAG_MAP``)."""
import numpy as np
import pytest

from repro.core import (
    CoreSession,
    OptimizeOptions,
    PlanCache,
    ServeConfig,
    build_plan,
    optimize,
    rebuild_plan,
    reoptimize,
)
from repro.data.synthetic import make_dataset, make_query, make_udfs
from repro.launch.serve import (
    _INVERTED,
    FLAG_MAP,
    build_arg_parser,
    config_from_args,
)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set in
    code; unset, the cache sits at the fixed ``<checkout>/.jax_cache``."""
    from pathlib import Path

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.util import enable_compile_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before[0]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = Path(__file__).resolve().parents[1]
        assert enable_compile_cache() == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
        compilation_cache.reset_cache()


def test_benchmark_runner_fails_on_a_failing_suite(monkeypatch, capsys):
    """Every suite still runs after one raised; the run then names the
    failure and exits non-zero."""
    from benchmarks import run

    ran = []

    def broken(quick):
        raise RuntimeError("boom")

    monkeypatch.setattr(run, "SUITES", [("broken", broken),
                                        ("fine", lambda quick: ran.append(quick))])
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", ["run"])
    assert run.main() == 1
    assert ran == [True]
    assert "# FAILED broken: RuntimeError: boom" in capsys.readouterr().out


@pytest.fixture(scope="module")
def workload():
    ds = make_dataset(n=4000, correlation=0.9, seed=17)
    udfs = make_udfs(ds, hidden=16, depth=1, train_rows=800, seed=17,
                     declared_cost_ms=10.0)
    q = make_query(ds, udfs, columns=[0, 1], seed=18)
    return ds, udfs, q


OPTS = OptimizeOptions(mode="core-a", step=0.05, seed=17)


# -------------------------------------------------------- deprecated shims
def test_optimize_shim_warns_and_matches_build_plan(workload):
    ds, _, q = workload
    x = ds.x[:800]
    with pytest.warns(DeprecationWarning, match="build_plan"):
        p_old = optimize(q, x, mode="core-a", step=0.05, seed=17)
    p_new = build_plan(q, x, OPTS)
    assert list(p_old.order) == list(p_new.order)
    assert p_old.est_total_cost == pytest.approx(p_new.est_total_cost)


def test_reoptimize_shim_warns_and_matches_rebuild_plan(workload):
    ds, _, q = workload
    x = ds.x[:800]
    base = build_plan(q, x, OPTS.replace(keep_state=True))
    with pytest.warns(DeprecationWarning, match="rebuild_plan"):
        p_old = reoptimize(base, x, mode="alloc", step=0.05, seed=17)
    p_new = rebuild_plan(base, x, OPTS.replace(reopt="alloc",
                                              keep_state=True))
    assert list(p_old.order) == list(p_new.order)
    assert p_old.est_total_cost == pytest.approx(p_new.est_total_cost)


def test_warm_optimize_shim_warns_and_delegates(workload):
    ds, _, q = workload
    x = ds.x[:800]
    cache = PlanCache()
    with pytest.warns(DeprecationWarning, match="optimize_query"):
        plan, info = cache.warm_optimize(q, x, mode="core-a", step=0.05,
                                         seed=17)
    assert info["path"] == "cold" and plan is not None
    # the shim wrote through to the same cache the new API reads
    hit_plan, hit = cache.optimize_query(q, x, OPTS.replace(seed=17))
    assert hit["path"] == "hit"
    assert list(hit_plan.order) == list(plan.order)


# ------------------------------------------------------------ options plumbing
def test_options_replace_returns_new_instance():
    opts = OptimizeOptions(step=0.05)
    o2 = opts.replace(step=0.1, keep_state=True)
    assert (o2.step, o2.keep_state) == (0.1, True)
    assert (opts.step, opts.keep_state) == (0.05, False)
    cfg = ServeConfig()
    c2 = cfg.replace(slo_ms=200.0, hosts=4)
    assert (c2.slo_ms, c2.hosts) == (200.0, 4)
    assert (cfg.slo_ms, cfg.hosts) == (None, 1)


def test_register_query_normalizes_quant_dtype(workload):
    ds, _, q = workload
    s = CoreSession(options=OPTS)
    h32 = s.register_query(q, ds.x[:800], quant_dtype="fp32")
    h8 = s.register_query(q, ds.x[:800], quant_dtype="int8")
    assert h32.options.quant_dtype is None
    assert h8.options.quant_dtype == "int8"


# --------------------------------------------------------- CLI golden tests
#: one non-default value per flag — a FLAG_MAP typo cannot hide behind a
#: default because the round-trip asserts every dest moved
NON_DEFAULT_ARGV = [
    "--n", "5000", "--correlation", "0.7", "--accuracy", "0.85",
    "--mode", "core-a", "--proxy-kind", "mlp", "--quant-dtype", "int8",
    "--preds", "3", "--tile", "512", "--udf-cost-ms", "12.5",
    "--seed", "9", "--adaptive", "--drift", "--hosts", "2",
    "--drift-skew", "0.4", "--transport", "thread",
    "--kill-coordinator-at", "prepare", "--straggler-host", "1",
    "--slo-ms", "250", "--arrival-rate", "80", "--request-rows", "64",
    "--no-backpressure", "--plan-cache", "/tmp/pc.bin",
    "--queries", "/tmp/q.json",
]


def test_flag_map_covers_every_cli_flag():
    parser = build_arg_parser()
    dests = {a.dest for a in parser._actions} - {"help"}
    assert dests == set(FLAG_MAP)


def test_every_cli_flag_round_trips_into_config():
    parser = build_arg_parser()
    args = parser.parse_args(NON_DEFAULT_ARGV)
    defaults = parser.parse_args([])
    cfg = config_from_args(args)
    sections = {"workload": cfg.workload, "optimize": cfg.optimize,
                "serve": cfg.serve}
    for dest, (sec, fld) in FLAG_MAP.items():
        want = getattr(args, dest)
        assert want != getattr(defaults, dest), \
            f"--{dest}: NON_DEFAULT_ARGV left it at its default"
        if dest in _INVERTED:
            want = not want
        got = getattr(sections[sec], fld)
        assert got == want, (dest, sec, fld, got, want)


def test_cli_normalization_rules():
    parser = build_arg_parser()
    # fp32 means "no quantization pass", backpressure defaults ON
    cfg = config_from_args(parser.parse_args([]))
    assert cfg.optimize.quant_dtype is None
    assert cfg.serve.backpressure is True
    # CORE workload modes feed the optimizer; baseline modes do not
    cfg = config_from_args(parser.parse_args(["--mode", "core-h",
                                              "--seed", "5"]))
    assert cfg.workload.mode == "core-h"
    assert cfg.optimize.mode == "core-h"
    assert (cfg.workload.seed, cfg.optimize.seed, cfg.serve.seed) == \
        (5, 5, 5)
    cfg = config_from_args(parser.parse_args(["--mode", "pp"]))
    assert cfg.workload.mode == "pp"
    assert cfg.optimize.mode != "pp"


# ------------------------------------------------------------ serve dispatch
def test_serve_dispatch(workload):
    from repro.serving.engine import CascadeServer
    from repro.serving.frontend import ServingFrontEnd
    from repro.serving.multiquery import MultiQueryEngine

    ds, udfs, q = workload
    x = ds.x[:800]
    cache = PlanCache()  # shared: later sessions warm-hit the first build

    # single query, no SLO -> bare CascadeServer
    s1 = CoreSession(options=OPTS, plan_cache=cache)
    s1.register_query(q, x)
    assert isinstance(s1.serve(), CascadeServer)
    with pytest.raises(RuntimeError, match="already built"):
        s1.serve()
    with pytest.raises(RuntimeError, match="precede serve"):
        s1.register_query(q, x)

    # single query + SLO -> deadline-aware front end
    s2 = CoreSession(options=OPTS, plan_cache=cache)
    s2.register_query(q, x)
    assert isinstance(s2.serve(slo=200.0), ServingFrontEnd)

    # >= 2 queries -> shared MultiQueryEngine; sharded multi-query is a
    # filed follow-up, not a silent misconfiguration
    q2 = make_query(ds, udfs, columns=[1, 2], seed=19)
    s3 = CoreSession(options=OPTS, plan_cache=cache)
    s3.register_query(q, x)
    s3.register_query(q2, x)
    with pytest.raises(ValueError, match="ROADMAP"):
        s3.serve(hosts=2)
    assert isinstance(s3.serve(), MultiQueryEngine)


def test_query_handle_end_to_end(workload):
    ds, _, q = workload
    s = CoreSession(options=OPTS)
    h = s.register_query(q, ds.x[:800])
    assert h.plan is None
    plan = h.optimize()
    assert plan is h.plan and plan is not None
    s.serve()
    s.run_stream(ds.x[800:2400], chunk=512)
    st = h.stats()
    assert st["emitted"] + st["rejected"] == 1600
    with pytest.raises(KeyError):
        s.query_stats(1)
