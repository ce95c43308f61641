"""corelint engine tests: every rule covered by a fixture triple.

For each rule the fixture directory holds a violating file (exact rule id
and line asserted), a suppressed twin (the inline ``# corelint: disable``
must silence exactly that finding), and a clean twin (the idiomatic
rewrite must be silent).  A rule disabled via the ``enabled=`` set must
stop reporting — this is what makes each fixture a regression test for
the *rule*, not just for the fixture text.
"""
from pathlib import Path

import pytest

from repro.analysis.corelint import (
    RULE_IDS,
    RULES,
    apply_baseline,
    lint_source,
    run_corelint,
    write_baseline,
)

FIXTURES = Path(__file__).parent / "lint_fixtures"

#: fixture stem -> (relpath-under-lint_fixtures, rule id, violating line)
EXPECTED = {
    "wall-clock-decision": ("serving/wall_clock_bad.py", 6),
    "unseeded-randomness": ("serving/rng_bad.py", 6),
    "print-in-protocol": ("distributed/print_bad.py", 5),
    "host-sync-hot-path": ("hotpath/proxy_score_bad.py", 5),
    "identity-cache-key": ("generic/id_key_bad.py", 7),
    "atomic-persistence": ("generic/persist_bad.py", 6),
    "wire-pack-outside-ops": ("generic/wire_pack_bad.py", 5),
    "wire-minor-exhaustive": ("generic/wire_minor_bad.py", 7),
    "weights-travel": ("generic/weights_bad.py", 6),
    "deprecated-entry-point": ("serving/deprecated_bad.py", 6),
}


def _lint_fixture(rel, **kw):
    p = FIXTURES / rel
    # the relpath fed to the engine keeps the fixture's scope segments
    # (serving/, distributed/, ...) so path-scoped rules fire
    return lint_source(p.read_text(), f"tests/lint_fixtures/{rel}", **kw)


def test_every_rule_has_a_fixture():
    assert set(EXPECTED) == set(RULE_IDS)
    assert len(RULES) >= 8


@pytest.mark.parametrize("rule_id", sorted(EXPECTED))
def test_rule_fires_at_exact_line(rule_id):
    rel, line = EXPECTED[rule_id]
    violations, suppressed = _lint_fixture(rel)
    assert [(v.rule, v.line) for v in violations] == [(rule_id, line)]
    assert suppressed == 0


@pytest.mark.parametrize("rule_id", sorted(EXPECTED))
def test_disabling_the_rule_silences_it(rule_id):
    rel, _line = EXPECTED[rule_id]
    violations, _ = _lint_fixture(rel, enabled=RULE_IDS - {rule_id})
    assert violations == []


@pytest.mark.parametrize("rule_id", sorted(EXPECTED))
def test_inline_suppression_silences_exactly_one(rule_id):
    rel, _line = EXPECTED[rule_id]
    supp_rel = rel.replace("_bad.py", "_suppressed.py")
    violations, suppressed = _lint_fixture(supp_rel)
    assert violations == []
    assert suppressed == 1


@pytest.mark.parametrize("rule_id", sorted(EXPECTED))
def test_clean_twin_is_silent(rule_id):
    rel, _line = EXPECTED[rule_id]
    clean_rel = rel.replace("_bad.py", "_clean.py")
    violations, suppressed = _lint_fixture(clean_rel)
    assert violations == []
    assert suppressed == 0


#: fixtures beyond the per-rule triples: (relpath, rule id, violating line)
EXTRA_BAD = [("serving/spans_snapshot_bad.py", "wall-clock-decision", 6)]
EXTRA_CLEAN = ["serving/spans_snapshot_clean.py"]


def test_run_corelint_over_fixture_tree():
    report = run_corelint([FIXTURES], root=FIXTURES.parent.parent)
    assert report.files_scanned == 3 * len(EXPECTED) + len(EXTRA_BAD) + len(EXTRA_CLEAN)
    assert report.parse_errors == []
    got = {(v.path.split("lint_fixtures/")[1], v.rule) for v in report.violations}
    assert got == {(rel, rid) for rid, (rel, _l) in EXPECTED.items()} | {
        (rel, rid) for rel, rid, _l in EXTRA_BAD}
    assert report.suppressed == len(EXPECTED)


@pytest.mark.parametrize("rel,rule_id,line", EXTRA_BAD)
def test_span_totals_read_in_a_decision_module_is_a_finding(rel, rule_id, line):
    violations, _ = _lint_fixture(rel)
    assert [(v.rule, v.line) for v in violations] == [(rule_id, line)]
    for clean in EXTRA_CLEAN:
        assert _lint_fixture(clean) == ([], 0)


@pytest.mark.parametrize("src", [
    "from repro.util.spans import snapshot\n",
    "import repro.util.spans\nx = repro.util.spans.snapshot()\n",
])
def test_span_totals_are_read_freely_outside_decision_modules(src):
    inside, _ = lint_source(src, "src/repro/core/mod.py")
    assert [v.rule for v in inside] == ["wall-clock-decision"]
    outside, _ = lint_source(src, "benchmarks/chip/tools/mod.py")
    assert outside == []


# ---------------------------------------------------------------- baseline


def test_baseline_masks_old_findings_not_new(tmp_path):
    old = '"""old"""\nx = id(object())\n'
    report_old, _ = lint_source(old, "pkg/mod.py")
    assert [v.rule for v in report_old] == ["identity-cache-key"]
    baseline = write_baseline(tmp_path / "base.json", report_old)
    # same file later grows a SECOND violation of the same rule
    new = '"""old"""\nx = id(object())\ny = id(object())\n'
    report_new, _ = lint_source(new, "pkg/mod.py")
    fresh, masked = apply_baseline(report_new, baseline)
    assert masked == 1
    assert [(v.rule, v.line) for v in fresh] == [("identity-cache-key", 3)]


def test_baseline_does_not_leak_across_rules_or_files(tmp_path):
    src = '"""m"""\nx = id(object())\n'
    violations, _ = lint_source(src, "pkg/a.py")
    baseline = write_baseline(tmp_path / "base.json", violations)
    other, _ = lint_source(src, "pkg/b.py")
    fresh, masked = apply_baseline(other, baseline)
    assert masked == 0
    assert len(fresh) == 1


def test_shipped_baseline_is_empty():
    import json

    shipped = Path(__file__).parent.parent / "corelint_baseline.json"
    assert json.loads(shipped.read_text()) == {}


# ---------------------------------------------------------------- the tree


def test_repo_tree_is_corelint_clean():
    """src/ and benchmarks/ lint clean with no baseline crutch."""
    root = Path(__file__).parent.parent
    report = run_corelint([root / "src", root / "benchmarks"], root=root)
    assert report.parse_errors == []
    assert [v.format() for v in report.violations] == []
