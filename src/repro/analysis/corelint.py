"""corelint — AST-based invariant lint for this repo (DESIGN.md §9).

Every rule here is distilled from a bug class this repo actually shipped
and later fixed; the ``origin`` field on each rule names the incident.
The engine is deliberately small: parse each file once, hand the tree to
every rule whose path scope matches, collect ``Violation``s, subtract
per-line ``# corelint: disable=RULE`` suppressions and the checked-in
JSON baseline, and report what is left.  CI (``scripts/ci.sh --lane
lint``) gates the leftover count to zero.

Suppression syntax (same line or the line directly above)::

    t0 = time.perf_counter()  # corelint: disable=wall-clock-decision
    # corelint: disable=identity-cache-key,unseeded-randomness
    key = id(params)

Baseline file: ``{"path/to/file.py": {"rule-id": count}}`` — masks the
first ``count`` findings per (path, rule), so historical findings do not
fail CI while any NEW finding in the same file still does.  The goal
state (and the checked-in state) is an EMPTY baseline: every historical
finding was either fixed or carries an explicit, justified suppression.
"""
from __future__ import annotations

import ast
import json
import re
from dataclasses import dataclass, field
from pathlib import Path, PurePosixPath
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

# --------------------------------------------------------------------------
# Core datatypes
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Violation:
    rule: str
    path: str  # repo-relative, posix separators
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class FileContext:
    """Everything a rule gets to look at for one file."""

    relpath: str  # posix repo-relative path
    tree: ast.Module
    lines: Sequence[str]

    @property
    def segments(self) -> Tuple[str, ...]:
        return PurePosixPath(self.relpath).parts

    @property
    def filename(self) -> str:
        return PurePosixPath(self.relpath).name


@dataclass
class Rule:
    id: str
    summary: str
    origin: str  # the historical bug this rule descends from
    applies: Callable[[FileContext], bool]
    check: Callable[[FileContext], List[Tuple[int, str]]]


@dataclass
class LintReport:
    violations: List[Violation] = field(default_factory=list)
    suppressed: int = 0
    baselined: int = 0
    files_scanned: int = 0
    parse_errors: List[str] = field(default_factory=list)


# --------------------------------------------------------------------------
# Shared AST helpers
# --------------------------------------------------------------------------

#: Path segments whose modules make scheduling / persistence / protocol
#: decisions.  Matching on segments (not prefixes) lets the lint fixture
#: tree under tests/lint_fixtures/serving/ exercise the same scopes.
DECISION_SEGMENTS = frozenset({"serving", "core", "distributed"})


def _in_decision_scope(ctx: FileContext) -> bool:
    return bool(DECISION_SEGMENTS & set(ctx.segments[:-1]))


def _name_of(node: ast.AST) -> Optional[str]:
    """Dotted name of an expression, e.g. ``np.random.seed`` -> that string."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _enclosing_scopes(tree: ast.Module) -> Dict[ast.AST, ast.AST]:
    """Map every node to its innermost enclosing function (or the module)."""
    owner: Dict[ast.AST, ast.AST] = {}

    def walk(node: ast.AST, scope: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            owner[child] = scope
            inner = scope
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = child
            walk(child, inner)

    walk(tree, tree)
    return owner


def _is_tempy(node: ast.AST) -> bool:
    """Heuristic: does this path expression look like a temp-file path?"""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id.lower().startswith(("tmp", "temp")):
            return True
        if isinstance(n, ast.Attribute) and n.attr.lower().startswith(("tmp", "temp")):
            return True
        if isinstance(n, ast.Constant) and isinstance(n.value, str) and ".tmp" in n.value:
            return True
    return False


def _scope_has_atomic_publish(scope: ast.AST) -> bool:
    """True if the scope ends with an atomic publish: ``os.replace(...)``
    or ``<tempy>.replace/rename(...)`` (pathlib spelling)."""
    for n in ast.walk(scope):
        if not isinstance(n, ast.Call) or not isinstance(n.func, ast.Attribute):
            continue
        fn = n.func
        if fn.attr == "replace" and isinstance(fn.value, ast.Name) and fn.value.id == "os":
            return True
        if fn.attr in ("replace", "rename") and _is_tempy(fn.value):
            return True
    return False


# --------------------------------------------------------------------------
# Rule: wall-clock-decision
# --------------------------------------------------------------------------

_WALL_CLOCK_ATTRS = frozenset(
    {"perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns", "time", "time_ns"}
)


#: ``repro.util.spans`` reads the clock on the same footing as
#: ``advisory_wall_ms``: a decision-path module may open spans, but the
#: span totals (``snapshot()``) are wall time and are for reports only.
_SPANS_MODULE = "repro.util.spans"


def _is_spans_read(node: ast.Attribute) -> bool:
    owner = _name_of(node.value)
    return node.attr == "snapshot" and owner is not None and (
        owner == "spans" or owner.endswith(".spans"))


def _check_wall_clock(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and _is_spans_read(node):
            out.append(
                (
                    node.lineno,
                    f"reading span totals `{_name_of(node)}` in a decision-path "
                    "module; span time is wall time, for reports only",
                )
            )
        elif isinstance(node, ast.Attribute):
            name = _name_of(node)
            if name and name.startswith("time.") and node.attr in _WALL_CLOCK_ATTRS:
                out.append(
                    (
                        node.lineno,
                        f"raw wall-clock read `{name}` in a decision-path module; "
                        "route it through repro.util.advisory_wall_ms()",
                    )
                )
            elif name in ("datetime.now", "datetime.datetime.now", "datetime.utcnow"):
                out.append((node.lineno, f"raw wall-clock read `{name}` in a decision-path module"))
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            bad = [a.name for a in node.names if a.name in _WALL_CLOCK_ATTRS]
            if bad:
                out.append(
                    (
                        node.lineno,
                        f"importing clock function(s) {bad} from time into a decision-path "
                        "module; use repro.util.advisory_wall_ms()",
                    )
                )
        elif isinstance(node, ast.ImportFrom) and node.module == _SPANS_MODULE:
            if any(a.name == "snapshot" for a in node.names):
                out.append(
                    (
                        node.lineno,
                        "importing span totals (`snapshot`) into a decision-path "
                        "module; span time is wall time, for reports only",
                    )
                )
    return out


# --------------------------------------------------------------------------
# Rule: identity-cache-key
# --------------------------------------------------------------------------


def _check_identity_cache_key(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "id"
            and len(node.args) == 1
            and not node.keywords
        ):
            out.append(
                (
                    node.lineno,
                    "id(obj) is an object-identity value — ids are recycled after gc, "
                    "so it must not key a cache or name an artifact; use a content "
                    "fingerprint (see core/compile_cache.py)",
                )
            )
    return out


# --------------------------------------------------------------------------
# Rule: atomic-persistence
# --------------------------------------------------------------------------

_WRITE_MODE_RE = re.compile(r"[wx]")


def _open_mode(call: ast.Call) -> Optional[str]:
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        if isinstance(call.args[1].value, str):
            return call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            if isinstance(kw.value.value, str):
                return kw.value.value
    return None


def _check_atomic_persistence(ctx: FileContext) -> List[Tuple[int, str]]:
    owner = _enclosing_scopes(ctx.tree)
    out: List[Tuple[int, str]] = []
    atomic_scopes: Dict[ast.AST, bool] = {}

    def scope_ok(node: ast.AST) -> bool:
        scope = owner.get(node, ctx.tree)
        if scope not in atomic_scopes:
            atomic_scopes[scope] = _scope_has_atomic_publish(scope)
        return atomic_scopes[scope]

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        target: Optional[ast.AST] = None
        what = ""
        if isinstance(node.func, ast.Name) and node.func.id == "open" and node.args:
            mode = _open_mode(node)
            if mode is None or not _WRITE_MODE_RE.search(mode):
                continue
            target, what = node.args[0], f'open(..., "{mode}")'
        elif isinstance(node.func, ast.Attribute) and node.func.attr in (
            "write_text",
            "write_bytes",
        ):
            target, what = node.func.value, f".{node.func.attr}(...)"
        else:
            continue
        if _is_tempy(target) or scope_ok(node):
            continue
        out.append(
            (
                node.lineno,
                f"{what} writes a shared path in place; publish via same-dir temp file "
                "+ os.replace (repro.util.atomic_write_text/bytes) so readers never "
                "see a torn file",
            )
        )
    return out


# --------------------------------------------------------------------------
# Rule: unseeded-randomness
# --------------------------------------------------------------------------

_NP_GLOBAL_RNG = frozenset(
    {
        "rand",
        "randn",
        "randint",
        "random",
        "random_sample",
        "ranf",
        "sample",
        "choice",
        "shuffle",
        "permutation",
        "normal",
        "uniform",
        "standard_normal",
        "beta",
        "binomial",
        "poisson",
        "exponential",
        "seed",
    }
)
_STDLIB_RNG = frozenset(
    {"random", "randint", "randrange", "uniform", "choice", "choices", "shuffle", "sample", "gauss"}
)


def _check_unseeded_randomness(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    imports_stdlib_random = any(
        isinstance(n, ast.Import) and any(a.name == "random" for a in n.names)
        for n in ast.walk(ctx.tree)
    )
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _name_of(node.func)
        if name is None:
            continue
        parts = name.split(".")
        if len(parts) == 3 and parts[0] in ("np", "numpy") and parts[1] == "random":
            if parts[2] in _NP_GLOBAL_RNG:
                out.append(
                    (
                        node.lineno,
                        f"`{name}` draws from the process-global numpy RNG; gated paths "
                        "must thread an explicit seeded Generator/RandomState",
                    )
                )
            elif parts[2] in ("RandomState", "default_rng") and not node.args and not node.keywords:
                out.append(
                    (node.lineno, f"`{name}()` without a seed is nondeterministic in a gated path")
                )
        elif (
            imports_stdlib_random
            and len(parts) == 2
            and parts[0] == "random"
            and parts[1] in _STDLIB_RNG
        ):
            out.append(
                (
                    node.lineno,
                    f"`{name}` uses the process-global stdlib RNG; thread an explicit "
                    "seeded random.Random",
                )
            )
    return out


# --------------------------------------------------------------------------
# Rule: wire-pack-outside-ops
# --------------------------------------------------------------------------


def _is_wire_ops_module(ctx: FileContext) -> bool:
    return ctx.filename == "ops.py" and "kernels" in ctx.segments


def _has_byteorder_arg(call: ast.Call) -> bool:
    """int.to_bytes/from_bytes carry a byteorder ("little"/"big") argument;
    container-serialization methods that merely share the name do not."""
    for arg in call.args:
        if isinstance(arg, ast.Constant) and arg.value in ("little", "big"):
            return True
    return any(kw.arg == "byteorder" for kw in call.keywords)


def _check_wire_pack(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _name_of(node.func)
        if name is None:
            continue
        leaf = name.rsplit(".", 1)[-1]
        if leaf in ("to_bytes", "from_bytes") and "." in name and _has_byteorder_arg(node):
            out.append(
                (
                    node.lineno,
                    f"raw integer wire packing `{name}` outside kernels/ops.py; use "
                    "ops.pack_le/unpack_le so COREWIRE field layout stays in one module",
                )
            )
        elif name.startswith("struct.") and leaf in ("pack", "unpack", "pack_into", "unpack_from"):
            out.append(
                (node.lineno, f"raw struct packing `{name}` outside kernels/ops.py (COREWIRE discipline)")
            )
    return out


# --------------------------------------------------------------------------
# Rule: wire-minor-exhaustive
# --------------------------------------------------------------------------


def _mentions_wire_minor(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id.startswith("WIRE_MINOR"):
            return True
        if isinstance(n, ast.Attribute) and n.attr.startswith("WIRE_MINOR"):
            return True
    return False


def _check_wire_minor_exhaustive(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        compares = [
            n
            for n in ast.walk(fn)
            if isinstance(n, (ast.Compare, ast.Match)) and _mentions_wire_minor(n)
        ]
        if not compares:
            continue
        if not any(isinstance(n, ast.Raise) for n in ast.walk(fn)):
            out.append(
                (
                    compares[0].lineno,
                    f"`{fn.name}` dispatches on a COREWIRE minor but never raises: an "
                    "unknown minor must fail loudly (WireFormatError), not fall through",
                )
            )
    return out


# --------------------------------------------------------------------------
# Rule: weights-travel
# --------------------------------------------------------------------------

#: ReservoirSample(indices, x, known_sigma, weights) — a call that fills
#: the first three but not `weights` silently reverts to uniform weighting
#: and un-corrects the IPW audit (the PR 4 bug).
_SAMPLE_CTORS = {"ReservoirSample": 4}


def _check_weights_travel(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _name_of(node.func)
        if name is None:
            continue
        leaf = name.rsplit(".", 1)[-1]
        if leaf not in _SAMPLE_CTORS:
            continue
        if any(kw.arg is None for kw in node.keywords):  # **kwargs: can't see inside
            continue
        if any(kw.arg == "weights" for kw in node.keywords):
            continue
        if len(node.args) >= _SAMPLE_CTORS[leaf]:
            continue
        out.append(
            (
                node.lineno,
                f"`{leaf}(...)` without `weights=`: IPW weights must travel with the "
                "sample or the merged audit silently reverts to uniform (PR 4 bug)",
            )
        )
    return out


# --------------------------------------------------------------------------
# Rule: host-sync-hot-path
# --------------------------------------------------------------------------


def _in_proxy_score_scope(ctx: FileContext) -> bool:
    return ctx.filename.startswith("proxy_score")


def _check_host_sync(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _name_of(node.func)
        if name is None:
            continue
        leaf = name.rsplit(".", 1)[-1]
        if leaf == "item" and not node.args and not node.keywords:
            what = f"`{name}()`"
        elif name in ("np.asarray", "numpy.asarray", "np.array", "numpy.array", "jax.device_get"):
            what = f"`{name}(...)`"
        elif leaf == "block_until_ready":
            what = f"`{name}()`"
        else:
            continue
        out.append(
            (
                node.lineno,
                f"{what} forces a device→host sync inside the scoring hot path; keep "
                "values on device until the survivor gather",
            )
        )
    return out


# --------------------------------------------------------------------------
# Rule: print-in-protocol
# --------------------------------------------------------------------------


def _check_print_in_protocol(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            file_kw = next((kw for kw in node.keywords if kw.arg == "file"), None)
            if file_kw is not None and _name_of(file_kw.value) != "sys.stdout":
                continue
            out.append(
                (
                    node.lineno,
                    "print() to stdout inside a distributed protocol module: the process "
                    "transport multiplexes stdout pipes for RPC framing — stray prints "
                    "corrupt it; write to sys.stderr or a logger",
                )
            )
    return out


# --------------------------------------------------------------------------
# Rule: deprecated-entry-point
# --------------------------------------------------------------------------

#: The PR-10 API redesign left ``optimize`` / ``reoptimize`` /
#: ``warm_optimize`` as DeprecationWarning shims for external callers;
#: INTERNAL code must use the ``core.api`` surface.  ``optimize`` is only
#: flagged as a bare name: the attribute form (``handle.optimize()``) is
#: the NEW session API, while ``cache.warm_optimize()`` /
#: ``x.reoptimize()`` have no non-deprecated reading.
_DEPRECATED_BARE = frozenset({"optimize", "reoptimize", "warm_optimize"})
_DEPRECATED_ATTR = frozenset({"reoptimize", "warm_optimize"})
_API_REPLACEMENT = {
    "optimize": "repro.core.api.build_plan(query, x, OptimizeOptions(...))",
    "reoptimize": "repro.core.api.rebuild_plan(plan, x, options)",
    "warm_optimize": "PlanCache.optimize_query(query, x, options)",
}


def _in_entry_point_scope(ctx: FileContext) -> bool:
    """Decision-path modules plus the launch veneers (the CLI is where a
    stray deprecated call would teach users the old surface)."""
    return bool((DECISION_SEGMENTS | {"launch"}) & set(ctx.segments[:-1]))


def _check_deprecated_entry_point(ctx: FileContext) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name) and node.func.id in _DEPRECATED_BARE:
            leaf = node.func.id
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _DEPRECATED_ATTR):
            leaf = node.func.attr
        else:
            continue
        out.append(
            (
                node.lineno,
                f"`{leaf}()` is a deprecated shim kept for external callers "
                f"only; internal code must call "
                f"{_API_REPLACEMENT[leaf]}",
            )
        )
    return out


# --------------------------------------------------------------------------
# Rule registry
# --------------------------------------------------------------------------

RULES: List[Rule] = [
    Rule(
        id="wall-clock-decision",
        summary="no raw wall-clock or span-total reads in decision-path modules",
        origin="PR 7: wall-clock fused_score_ms nearly fed scheduling; decisions must run "
        "on the cost-model clock (advisory_wall_ms is the one sanctioned read)",
        applies=_in_decision_scope,
        check=_check_wall_clock,
    ),
    Rule(
        id="identity-cache-key",
        summary="no id()/object-identity cache keys or artifact names",
        origin="PR 4: id()-keyed scorer compile cache returned a stale kernel after gc "
        "recycled the address; caches must key on content fingerprints",
        applies=lambda ctx: True,
        check=_check_identity_cache_key,
    ),
    Rule(
        id="atomic-persistence",
        summary="shared-path writes must publish via temp file + os.replace",
        origin="PR 7: concurrent autotune runs tore the shared disk cache mid-write; "
        "kernels/autotune.py now publishes atomically and so must every shared path",
        applies=lambda ctx: True,
        check=_check_atomic_persistence,
    ),
    Rule(
        id="unseeded-randomness",
        summary="no process-global / unseeded RNG in gated paths",
        origin="gated benches and tier-1 tests must be bit-reproducible; a module-level "
        "np.random call made BENCH_components.json drift run-to-run",
        applies=_in_decision_scope,
        check=_check_unseeded_randomness,
    ),
    Rule(
        id="wire-pack-outside-ops",
        summary="COREWIRE byte packing lives only in kernels/ops.py",
        origin="PR 8: COREPLNC hand-packed container fields; two packers drifted on "
        "endianness assumptions until unified behind ops helpers",
        applies=lambda ctx: not _is_wire_ops_module(ctx),
        check=_check_wire_pack,
    ),
    Rule(
        id="wire-minor-exhaustive",
        summary="COREWIRE minor dispatch must raise on unknown minors",
        origin="PR 6: COREWIRE v1.2 added the quant minor; a silent fall-through would "
        "deserialize quantized payloads as fp32 garbage instead of failing",
        applies=lambda ctx: True,
        check=_check_wire_minor_exhaustive,
    ),
    Rule(
        id="weights-travel",
        summary="reservoir/audit samples must carry their IPW weights",
        origin="PR 4: Reservoir.sample() dropped IPW weights; the merged audit silently "
        "reverted to uniform weighting and biased selectivity estimates",
        applies=lambda ctx: True,
        check=_check_weights_travel,
    ),
    Rule(
        id="host-sync-hot-path",
        summary="no device→host syncs inside the fused scoring kernel path",
        origin="PR 1: per-stage host bouncing was the original 3-6x slowdown the fused "
        "kernel removed; .item()/np.asarray in proxy_score.py reintroduces it",
        applies=_in_proxy_score_scope,
        check=_check_host_sync,
    ),
    Rule(
        id="print-in-protocol",
        summary="no stdout prints in distributed protocol modules",
        origin="PR 5: the one-host-per-subprocess transport frames RPCs over pipes; a "
        "debug print interleaved with a reply and desynced the channel",
        applies=lambda ctx: "distributed" in ctx.segments[:-1],
        check=_check_print_in_protocol,
    ),
    Rule(
        id="deprecated-entry-point",
        summary="internal code must not call the deprecated optimizer shims",
        origin="PR 10: the api_redesign left optimize/reoptimize/warm_optimize as "
        "DeprecationWarning shims; an internal caller silently keeps the old kwarg "
        "surface alive and the shims can never be retired",
        applies=_in_entry_point_scope,
        check=_check_deprecated_entry_point,
    ),
]

RULE_IDS = frozenset(r.id for r in RULES)


# --------------------------------------------------------------------------
# Suppressions
# --------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(r"#\s*corelint:\s*disable=([\w\-,\s]+)")


def _suppressions(lines: Sequence[str]) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = {tok.strip() for tok in m.group(1).split(",") if tok.strip()}
    return out


def _is_suppressed(rule_id: str, line: int, supp: Dict[int, Set[str]]) -> bool:
    for ln in (line, line - 1):
        ids = supp.get(ln)
        if ids and (rule_id in ids or "all" in ids):
            return True
    return False


# --------------------------------------------------------------------------
# Baseline
# --------------------------------------------------------------------------


def load_baseline(path) -> Dict[str, Dict[str, int]]:
    p = Path(path)
    if not p.exists():
        return {}
    data = json.loads(p.read_text())
    return {str(f): {str(r): int(c) for r, c in rules.items()} for f, rules in data.items()}


def write_baseline(path, violations: Iterable[Violation]) -> Dict[str, Dict[str, int]]:
    counts: Dict[str, Dict[str, int]] = {}
    for v in violations:
        counts.setdefault(v.path, {})
        counts[v.path][v.rule] = counts[v.path].get(v.rule, 0) + 1
    payload = json.dumps(counts, indent=2, sort_keys=True) + "\n"
    # Import here (not module level) so corelint has no repro-runtime deps
    # when vendored into other tooling.
    from repro.util import atomic_write_text

    atomic_write_text(path, payload)
    return counts


def apply_baseline(
    violations: List[Violation], baseline: Dict[str, Dict[str, int]]
) -> Tuple[List[Violation], int]:
    """Mask the first N findings per (path, rule); return (new, masked)."""
    budget = {
        (path, rule): count for path, rules in baseline.items() for rule, count in rules.items()
    }
    fresh: List[Violation] = []
    masked = 0
    for v in sorted(violations, key=lambda v: (v.path, v.rule, v.line)):
        key = (v.path, v.rule)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            masked += 1
        else:
            fresh.append(v)
    return fresh, masked


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def lint_source(
    source: str, relpath: str, enabled: Optional[Set[str]] = None
) -> Tuple[List[Violation], int]:
    """Lint one file's source text; returns (violations, suppressed_count)."""
    tree = ast.parse(source, filename=relpath)
    lines = source.splitlines()
    ctx = FileContext(relpath=relpath, tree=tree, lines=lines)
    supp = _suppressions(lines)
    violations: List[Violation] = []
    suppressed = 0
    for rule in RULES:
        if enabled is not None and rule.id not in enabled:
            continue
        if not rule.applies(ctx):
            continue
        for line, message in rule.check(ctx):
            if _is_suppressed(rule.id, line, supp):
                suppressed += 1
            else:
                violations.append(Violation(rule.id, relpath, line, message))
    violations.sort(key=lambda v: (v.path, v.line, v.rule))
    return violations, suppressed


def iter_py_files(paths: Sequence[Path], root: Path) -> Iterable[Tuple[Path, str]]:
    seen: Set[Path] = set()
    for p in paths:
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            f = f.resolve()
            if f in seen or "__pycache__" in f.parts:
                continue
            seen.add(f)
            try:
                rel = f.relative_to(root).as_posix()
            except ValueError:
                rel = f.as_posix()
            yield f, rel


def run_corelint(
    paths: Sequence,
    root=None,
    baseline: Optional[Dict[str, Dict[str, int]]] = None,
    enabled: Optional[Set[str]] = None,
) -> LintReport:
    root = Path(root) if root is not None else Path.cwd()
    report = LintReport()
    all_violations: List[Violation] = []
    for f, rel in iter_py_files([Path(p) for p in paths], root):
        try:
            source = f.read_text(encoding="utf-8")
            violations, suppressed = lint_source(source, rel, enabled=enabled)
        except (SyntaxError, UnicodeDecodeError) as e:
            report.parse_errors.append(f"{rel}: {e}")
            continue
        report.files_scanned += 1
        report.suppressed += suppressed
        all_violations.extend(violations)
    if baseline:
        all_violations, masked = apply_baseline(all_violations, baseline)
        report.baselined = masked
    report.violations = all_violations
    return report
