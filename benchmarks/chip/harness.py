"""One benchmark cell, run once: set-up, a measured window, the check.

A cell is a configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<mix>.json``), both named in ``BENCHMARK.json``; per-layer
metrics are read by ``metrics/<metric>.py``.  Nothing here names a cell.
Everything the configuration's UDFs decide (the record process and the
width and type of its rows, the served UDFs, their warm-up, their labels,
what the proxies see, their FLOPs and any check of their own) comes from
its UDF family, ``families/<udf_family>.py`` (``mlp`` where the key is
absent), and the proxy reference from the module its ``reference`` key
names (``reference.py`` where absent).

What the seed decides: the served records (fresh draws of the
configuration's record process, one per record id, never served
twice).  The record process, the UDF weights,
the queries and the optimization sample come from the configuration's
``model_seed``, so every run seed does the same work on different
records.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
KERNEL_OP = r"^%cascade_score(\.\d+)? = "
BLOCK_ROWS = 1 << 17        # records drawn per call of the stream's sampler


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ spec
def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve_cell(spec: dict, name: str) -> dict:
    """The cell, its configuration, its traffic mix and its metrics, all
    found by the names ``BENCHMARK.json`` gives."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def load_reader(metric: str):
    """``metrics/<metric>.py``'s ``read(ctx) -> float | None``."""
    return _import_local(f"metrics/{metric}").read


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """Generator for one use of the run seed (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), stream]))


# --------------------------------------------------------- instrumentation
class CompileLog:
    """Backend compiles (count, seconds) and persistent-cache hits, read
    from ``jax.monitoring`` events (as ``chip_smoke.py`` counts them)."""

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


class Probe:
    """The harness's spans and counters around each layer call.

    Spans are kept in memory as (name, start, end) on the host clock and,
    with ``traced``, written into the profiler trace as
    ``TraceAnnotation``s.  Counters are always kept."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: List[tuple] = []
        self.counters: Dict[str, float] = {}
        self.in_window = False

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.traced:
            yield
            return
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(name):
            yield
        if self.in_window:
            self.spans.append((name, t0, time.perf_counter()))

    def add(self, key: str, value: float = 1.0) -> None:
        if self.in_window:
            self.counters[key] = self.counters.get(key, 0.0) + value

    def seconds_in(self, name: str) -> float:
        return sum(b - a for n, a, b in self.spans if n == name)


# ---------------------------------------------------------------- model
def _import_local(name: str):
    """A module of this directory, under a name no other package uses."""
    key = "chipbench_" + name.replace("/", "_").replace(".", "_").replace("-", "_")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH_DIR / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


wl = _import_local("workload")
counts = _import_local("counts")
trace_mod = _import_local("trace")


class Model:
    """The configuration's UDF family (record process and UDFs), proxy
    reference, queries and optimization sample, built from
    ``model_seed``.

    A family module's ``build(cfg, load)`` returns an object with:
    ``block_sampler(block_rows)``, the stream's ``draw(words, b) -> rows``
    (it decides the rows' width and type); ``udfs``, the served callables
    ``rows -> labels``; ``x_model``, the rows the queries' values and the
    optimization sample come from; ``warm()``, every UDF batch shape the
    engine can send; ``labels_for_query(x)`` and ``orig_labels(x)``, every
    UDF's labels; ``proxy_inputs(rows)``, what the scorer sees;
    ``n_proxy_features``; ``udf_flops(rows)``; and
    ``extra_checks(x_window, limits) -> {name: (value, op, limit)}``."""

    def __init__(self, cfg: dict, probe: Probe):
        from repro.core.query import MLUDF, Predicate, Query

        self.cfg = cfg
        seed = int(cfg["model_seed"])
        self.family = _import_local(
            f"families/{cfg.get('udf_family', 'mlp')}").build(
                cfg, _import_local)
        self.reference = _import_local(
            cfg.get("reference", "reference.py").removesuffix(".py"))
        self.probe = probe
        x_model = self.family.x_model
        model_labels = self.family.labels_for_query(x_model)
        self.udfs = [MLUDF(name=f"{cfg['name']}.udf{j}",
                           fn=self._served_udf(j),
                           cost=float(cfg["udf_declared_cost_ms"]),
                           n_classes=int(cfg["n_classes"]))
                     for j in range(len(self.family.udfs))]
        self.queries = []
        self.query_values = []
        for qi, cols in enumerate(cfg["queries"]):
            vals = wl.choose_values([model_labels[j] for j in cols],
                                    cfg["n_classes"],
                                    cfg["target_selectivity"], seed + 1 + qi)
            self.query_values.append(list(zip(cols, vals)))
            self.queries.append(Query(
                predicates=[Predicate(udf=self.udfs[j], values=frozenset(v))
                            for j, v in zip(cols, vals)],
                accuracy_target=float(cfg["accuracy_target"])))
        self.x_sample = x_model[:cfg["sample_rows"]]

    def _served_udf(self, j: int):
        udf = self.family.udfs[j]

        def fn(x):
            probe = self.probe
            probe.add("udf_rows", len(x))
            with probe.span("bench.udf"):
                return udf(x)

        return fn


# ------------------------------------------------------------ sessions
def new_session(model: Model, quant_dtype: Optional[str]):
    from repro.core.api import CoreSession, OptimizeOptions

    cfg = model.cfg
    opts = OptimizeOptions(mode=cfg["optimizer_mode"], kind=cfg["proxy_kind"],
                           seed=int(cfg["model_seed"]))
    session = CoreSession(options=opts, seed=int(cfg["model_seed"]))
    for q in model.queries:
        session.register_query(q, model.x_sample, quant_dtype=quant_dtype)
    return session


def serve(session, cfg: dict):
    """The ``CascadeServer`` ``CoreSession.serve`` builds for one query on
    one chip."""
    from repro.core.api import ServeConfig

    if cfg["serve"] != "single" or int(cfg["hosts"]) != 1:
        raise ValueError("the harness drives one query served on one host")
    sc = ServeConfig(tile=int(cfg["tile"]), hosts=1, transport="inline",
                     seed=int(cfg["model_seed"]))
    return session.serve(config=sc)


def engines_of(server) -> list:
    """The ``CascadeServer`` behind ``server``."""
    return [getattr(server, "engine", server)]


# ------------------------------------------------------------- tracking
class Tracker:
    """Which window records each query finalized, and the gate masks the
    timed path produced for them."""

    def __init__(self, n_queries: int):
        self.nq = n_queries
        self.base = None
        self.cap = 0
        self.fin = np.zeros(0, np.int16)
        self.emitted: List[list] = [[] for _ in range(n_queries)]
        self.rejected: List[list] = [[] for _ in range(n_queries)]
        self.captures: List[tuple] = []   # (engine index, ids, masks)

    def open(self, base: int, expect: int) -> None:
        self.base = int(base)
        self.cap = max(1 << 16, int(expect))
        self.fin = np.zeros(self.cap, np.int16)

    def _grow(self, need: int) -> None:
        cap = self.cap
        while cap < need:
            cap *= 2
        fin = np.zeros(cap, np.int16)
        fin[:self.cap] = self.fin
        self.fin, self.cap = fin, cap

    def hook(self, q: int):
        def on_finalized(emitted, rejected, _version):
            if self.base is None:
                return
            for lst, ids in ((self.emitted[q], emitted),
                             (self.rejected[q], rejected)):
                if not ids:
                    continue
                a = np.asarray(ids, np.int64) - self.base
                a = a[a >= 0]
                if not len(a):
                    continue
                if a.max() >= self.cap:
                    self._grow(int(a.max()) + 1)
                np.add.at(self.fin, a, 1)
                lst.append(a)
        return on_finalized

    def finalized_by_all(self, n: int) -> int:
        return int(np.count_nonzero(self.fin[:n] >= self.nq))

    def ids(self, q: int, what: str) -> np.ndarray:
        lst = self.emitted[q] if what == "emitted" else self.rejected[q]
        return np.concatenate(lst) if lst else np.empty(0, np.int64)


def instrument(engines: list, scorers: list, tracker: Tracker,
               probe: Probe) -> None:
    """Finalize hooks on every engine; spans and mask capture around the
    scorer calls and the engine submissions of the timed path."""
    current = {"ids": None, "engine": None}
    for k, eng in enumerate(engines):
        eng.add_finalize_hook(tracker.hook(0))
        orig = eng.submit

        def submit(indices, rows, *, masks=None, margins=None,
                   _orig=orig, _k=k):
            current["ids"], current["engine"] = indices, _k
            try:
                return _orig(indices, rows, masks=masks, margins=margins)
            finally:
                current["ids"] = None
        eng.submit = submit

    def wrap(i, scorer, method):
        # always wrap the class's method, so a scorer shared by two runs
        # in one process is never wrapped twice
        orig = getattr(type(scorer), method).__get__(scorer)

        def call(x, *a, **kw):
            probe.add(f"score_rows.{i}", len(x))
            probe.add(f"score_calls.{i}", 1)
            with probe.span("bench.score"):
                out = orig(x, *a, **kw)
            if probe.in_window and current["ids"] is not None:
                masks = out[0] if isinstance(out, tuple) else out
                tracker.captures.append(
                    (current["engine"],
                     np.asarray(current["ids"], np.int64).copy(),
                     np.asarray(masks, bool).copy()))
            return out

        setattr(scorer, method, call)

    for i, sc in enumerate(scorers):
        wrap(i, sc, "score_masks")
        wrap(i, sc, "score_margins")


def scorer_hidden(engines: list) -> List[List[int]]:
    """Hidden width of every column of every scorer the timed path calls,
    one scorer per engine."""
    return [[counts.proxy_hidden_width(p) for p, _ in proxied_columns(e.plan)]
            for e in engines]


# ---------------------------------------------------------------- loops
class RecordSource:
    """The served stream: one fresh draw of the record process per
    globally unique record id, so no record is served twice.  Record id
    ``i`` is row ``i % block_rows`` of block ``i // block_rows``, and a
    block is drawn on the device from the run seed and its index alone,
    by ``process.block_sampler``, which decides the rows' width and type.
    Set-up draws the blocks of the first ``setup_rows`` ids; a window that
    outruns them draws the next block when it gets there (the same
    compiled sampler, so nothing compiles), and keeps the blocks drawn
    before as they are."""

    def __init__(self, process, seed: int, setup_rows: int,
                 block_rows: int = BLOCK_ROWS):
        self.block = int(block_rows)
        self._draw = process.block_sampler(self.block)
        self._words = np.random.SeedSequence(
            [seed % (1 << 64), 1]).generate_state(2, np.uint32)
        self.blocks: List[np.ndarray] = []
        self.next_id = 0
        self._cover(int(setup_rows))

    @property
    def x(self) -> np.ndarray:
        """Every row drawn so far, in id order (a copy of the blocks)."""
        return np.concatenate(self.blocks)

    def _cover(self, n: int) -> None:
        """Draw every block up to the one holding id ``n - 1``, each kept
        row-major: a TPU hands a block back column-major, and gathering
        rows from that is about 500 times slower."""
        for b in range(len(self.blocks), -(-n // self.block)):
            self.blocks.append(np.ascontiguousarray(
                self._draw(self._words, np.int32(b))))

    def rows(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        if not len(ids):
            return np.take(self.blocks[0], ids, axis=0)
        self._cover(int(ids.max()) + 1)
        blk, row = np.divmod(ids, self.block)
        first, last = int(blk.min()), int(blk.max())
        if first == last:
            return np.take(self.blocks[first], row, axis=0)
        out = np.empty((len(ids),) + self.blocks[0].shape[1:],
                       self.blocks[0].dtype)
        for b in range(first, last + 1):
            sel = blk == b
            out[sel] = np.take(self.blocks[b], row[sel], axis=0)
        return out

    def take(self, n: int):
        ids = np.arange(self.next_id, self.next_id + n, dtype=np.int64)
        self.next_id += n
        return ids, self.rows(ids)


def closed_chunks(session, server, src: RecordSource, probe: Probe,
                  chunk: int, until: float):
    """The scan: ``CoreSession.submit`` of ``chunk``-row chunks with
    unique ids, then ``pump()``, until ``until`` on the host clock."""
    n = 0
    while True:
        with probe.span("bench.generate"):
            ids, rows = src.take(chunk)
        with probe.span("bench.submit"):
            session.submit(ids, rows)
        with probe.span("bench.pump"):
            server.pump()
        n += chunk
        if time.perf_counter() >= until:
            return n


def drain(server) -> None:
    getattr(server, "engine", server).pump(drain=True)


# ----------------------------------------------------------------- check
def limits_of(cfg: dict) -> dict:
    lim = dict(cfg["limits"])
    lim["recall_min"] = float(cfg["accuracy_target"])
    return lim


def proxied_columns(plan) -> list:
    """(params, threshold) of each proxied stage, in stage order: the
    column layout of an engine's gate masks."""
    return [(s.proxy.params, float(s.threshold)) for s in plan.stages
            if s.proxy is not None]


def check(model: Model, src: RecordSource, tracker: Tracker, engines: list,
          plans_per_engine: list, n_window: int, limits: dict,
          bf16_operands: bool, info: dict) -> dict:
    """Compare what the timed path produced in the window with the plain
    reference.  Returns {name: (value, op, limit)}; numbers reported but
    not compared go to ``info``."""
    reference = model.reference
    ids_all = np.arange(n_window, dtype=np.int64)
    x_win = src.rows(ids_all + tracker.base)
    labels = model.family.orig_labels(x_win)
    x_proxy = model.family.proxy_inputs(x_win)
    out = {}
    # conservation: every window record finalized exactly once per query
    lost = dup = 0
    for q in range(tracker.nq):
        got = np.concatenate([tracker.ids(q, "emitted"),
                              tracker.ids(q, "rejected")])
        uniq = np.unique(got)
        lost = max(lost, n_window - int(np.count_nonzero(uniq < n_window)))
        dup = max(dup, len(got) - len(uniq))
    out["lost_records"] = (lost, "<=", limits["lost_max"])
    out["duplicate_finals"] = (dup, "<=", limits["dup_max"])
    out["in_flight"] = (sum(e.in_flight() for e in engines), "<=", 0)
    # every query's emitted set against ORIG at its accuracy target
    recalls, false = [], 0
    for q, values in enumerate(model.query_values):
        want = ids_all[reference.orig_pass([labels[j] for j, _ in values],
                                           [v for _, v in values])]
        em = np.unique(tracker.ids(q, "emitted"))
        em = em[em < n_window]
        hit = np.intersect1d(em, want, assume_unique=True)
        recalls.append(len(hit) / max(len(want), 1))
        false += len(em) - len(hit)
    out["recall_min"] = (min(recalls), ">=", limits["recall_min"])
    out["false_emits"] = (false, "<=", limits["false_emits_max"])
    # the fused kernel's gate decisions: against the scores computed at
    # the stated precision (compared), and against float64 (reported)
    gap = 0.0
    decisions = flips = 0
    cols_cache = {}
    for k, ids, masks in tracker.captures:
        if k not in cols_cache:
            cols = proxied_columns(plans_per_engine[k])
            ref = np.stack([reference.proxy_scores(p, x_proxy)
                            for p, _ in cols], axis=1)
            stated = np.stack([reference.stated_scores(p, x_proxy,
                                                       bf16_operands)
                               for p, _ in cols], axis=1)
            thr = np.asarray([t for _, t in cols], np.float64)
            spread = np.maximum(ref.std(axis=0), 1e-12)
            cols_cache[k] = (ref, stated, thr, spread)
        ref, stated, thr, spread = cols_cache[k]
        if masks.shape[1] != len(thr):
            raise ValueError(f"engine {k}: {masks.shape[1]} mask columns for "
                             f"{len(thr)} proxied stages")
        rel = ids - tracker.base
        if len(rel) and (rel.min() < 0 or rel.max() >= n_window):
            raise ValueError(f"engine {k}: gate masks captured in the window "
                             f"for records outside it")
        g = reference.decision_gaps(masks, ref[rel], thr, spread)
        gap = max(gap, float(g.max()) if g.size else 0.0)
        flips += reference.stated_flips(masks, stated[rel], thr)
        decisions += masks.size
    out["kernel_flip_ppm"] = (1e6 * flips / max(decisions, 1), "<=",
                              limits["kernel_flip_ppm_max"])
    out["kernel_decisions"] = (decisions, ">=", 1)
    info["kernel_gap_f64"] = gap
    out.update(model.family.extra_checks(x_win, limits))
    return out


def passes(value, op, limit) -> bool:
    return value <= limit if op == "<=" else value >= limit


# ----------------------------------------------------------------- run
def log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def device_info(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if jax.default_backend() != "tpu":
            raise NoChip(f"JAX backend is {jax.default_backend()!r}, not "
                         f"'tpu'; the benchmark never falls back to the CPU")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chip(s); JAX sees "
                         f"{len(devs)}")
    return devs


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        t_start: Optional[float] = None, require_chip: bool = True,
        config_override: Optional[dict] = None,
        traffic_override: Optional[dict] = None, reuse: Optional[dict] = None,
        quant_dtype: Optional[str] = None,
        keep_trace: Optional[str] = None) -> dict:
    """Run one cell once and return the result object (the last line).

    The keyword arguments serve the tools and the CPU tests: a chip-less
    rehearsal (``require_chip``), sizes other than the files'
    (``config_override``, ``traffic_override``), one model for
    several runs in one process (``reuse``), the program's own int8
    cascade as the control (``quant_dtype``, which otherwise comes from
    the configuration's ``quant_dtype`` key, float32 where absent), and
    the extracted trace written to a file (``keep_trace``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec()
    cell = resolve_cell(spec, workload)
    cfg = dict(cell["config"])
    cfg.update(config_override or {})
    traffic = dict(cell["traffic"])
    traffic.update(traffic_override or {})
    chips = int(cell["cell"]["chips"])
    devs = device_info(chips, require_chip)
    if quant_dtype is None:
        quant_dtype = cfg.get("quant_dtype")

    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.util import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    probe = Probe(traced)
    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(traced)}; "
        f"device {devs[0].device_kind} x{len(devs)}; compile cache {cache_dir}")

    # ---- set-up: model, optimizer (one untimed warm-up, one timed), server
    t_model = time.perf_counter()
    key = json.dumps(cfg, sort_keys=True)
    if reuse is not None and key in reuse:
        model = reuse[key]
        model.probe = probe
    else:
        model = Model(cfg, probe)
        if reuse is not None:
            reuse[key] = model
    t_opt = time.perf_counter()
    warm_session = new_session(model, quant_dtype)
    with probe.span("bench.optimize"):
        warm_session.optimize_all()
    session = new_session(model, quant_dtype)
    t0 = time.perf_counter()
    with probe.span("bench.optimize"):
        plans = session.optimize_all()
    optimize_s = time.perf_counter() - t0
    server = serve(session, cfg)
    engines = engines_of(server)
    scorers = [e.cascade for e in engines]
    if len(model.queries) != 1:
        raise ValueError("a cell serves one query")
    tracker = Tracker(1)
    instrument(engines, scorers, tracker, probe)
    src = RecordSource(model.family, seed, int(traffic["setup_rows"]))
    model.family.warm()
    t_warm = time.perf_counter()

    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    chunk = int(traffic["chunk_rows"])
    warm_until = time.perf_counter()  # warm-up is counted, not timed
    for _ in range(int(traffic["warmup_chunks"])):
        closed_chunks(session, server, src, probe, chunk, warm_until)
    drain(server)

    t_ready = time.perf_counter()
    log(f"set-up: process and imports {t_model - t_start} s, model "
        f"{t_opt - t_model} s, optimizer and server {t_warm - t_opt} s, "
        f"warm-up {t_ready - t_warm} s; compiles so far {compiles.count} "
        f"({compiles.seconds} s), compile-cache hits {compiles.cache_hits}")

    # ---- the measured window
    base = src.next_id
    tracker.open(base, expect=1 << 20)
    if traced:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c_before = compiles.count
    probe.in_window = True
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    with probe.span("bench.window"):
        closed_chunks(session, server, src, probe, chunk, t_win + seconds)
        window_s = time.perf_counter() - t_win
        finalized = tracker.finalized_by_all(src.next_id - base)
    probe.in_window = False
    window_compiles = compiles.count - c_before
    if traced:
        jax.profiler.stop_trace()
    # ---- after the window: finish what it started, untimed
    drain(server)
    n_window = src.next_id - base
    log(f"window: {window_s} s, {n_window} records submitted, {finalized} "
        f"finalized by every query, backend compiles inside the window "
        f"{window_compiles}")
    mem = [d.memory_stats() or {} for d in devs[:chips]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    # ---- end-to-end metrics
    e2e = {"setup_s": setup_s, "optimize_s": optimize_s,
           "records_per_s": finalized / window_s}
    attempted = n_window
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}

    # ---- per-layer metrics (traced run)
    breakdown = None
    busy = None
    metrics = {}
    if traced:
        import glob

        pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
        red = None
        planes = [f"/device:TPU:{d.id}" for d in devs[:chips]]
        if pbs and devs[0].platform == "tpu":
            raw = trace_mod.extract(sorted(pbs)[-1])
            if keep_trace:
                from repro.util import atomic_write_text

                atomic_write_text(keep_trace, json.dumps(raw))
            planes = [p for p in planes if p in raw["devices"]]
            red = trace_mod.reduce(raw, kernel_op=KERNEL_OP, devices=planes)
            log(f"trace: {len(raw['spans'])} harness spans in the trace, "
                f"{len(probe.spans)} kept in memory; kernel events "
                f"{red['kernel_events']}")
            busy = (red["busy_s"], red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = ReaderContext(
            cfg=cfg, model=model, plans=plans, probe=probe,
            window_s=window_s, trace=red, chips=chips,
            peaks=(counts.peaks_for(devs[0].device_kind)
                   if devs[0].platform == "tpu" else None),
            scorer_hidden=scorer_hidden(engines))
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}

    # ---- the check, after the window and the memory reading
    plans_per_engine = [e.plan for e in engines]
    info = {}
    checks = check(model, src, tracker, engines, plans_per_engine,
                   n_window, limits_of(cfg),
                   bf16_operands=devs[0].platform == "tpu", info=info)
    log(f"reported, not compared: {json.dumps(info)}")
    checks["window_compiles"] = (window_compiles, "<=", 0)
    correct = all(passes(*v) for v in checks.values())
    for name, (v, op, lim) in checks.items():
        log(f"check {name}: {v} (limit {op} {lim}) "
            f"{'ok' if passes(v, op, lim) else 'FAILED'}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if busy is not None:
        device["busy_s"], device["window_s"] = busy
    failed = checks["lost_records"][0]
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim, "op": op}
                        for k, (v, op, lim) in checks.items()}
    return result


class ReaderContext:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def seconds_in(self, span: str) -> float:
        return self.probe.seconds_in(span)

    def counter(self, key: str) -> float:
        return float(self.probe.counters.get(key, 0.0))

    def cascade_work(self):
        """(FLOPs, bytes) the window's scorer calls needed, from the
        plan's shapes."""
        f = int(self.model.family.n_proxy_features)
        flops = nbytes = 0.0
        for i, hidden in enumerate(self.scorer_hidden):
            rows = self.counter(f"score_rows.{i}")
            calls = self.counter(f"score_calls.{i}")
            flops += counts.cascade_flops(rows, f, hidden)
            nbytes += counts.cascade_bytes(rows, calls, f, hidden)
        return flops, nbytes

    def cascade_least_time(self):
        return counts.least_time_s(*self.cascade_work(), self.peaks)

    def udf_flops(self) -> float:
        return self.model.family.udf_flops(self.counter("udf_rows"))
