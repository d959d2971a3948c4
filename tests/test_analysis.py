"""Static contract checker (PR 9): lint rules against their checked-in
known-bad/known-clean fixtures, trace contracts against toy specimens
that deliberately break them, and the repo tree itself staying clean."""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import render_json, render_report, rule_counts
from repro.analysis.lint import run_lint
from repro.analysis.tracecheck import check_specimen
from repro.core.engine import TraceSpecimen
from repro.core.spec import (CombineSpec, registry_snapshot,
                             resolve_combiner)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def _lint_one(relpath):
    violations, _ = run_lint(paths=[os.path.join(FIXTURES, relpath)])
    return violations


# ---------------------------------------------------------------------------
# lint rules: one known-bad + one known-clean fixture per rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule,bad,clean", [
    ("RPR001", "rpr001_bad.py", "rpr001_clean.py"),
    ("RPR002", "rpr002_bad.py", "rpr002_clean.py"),
    ("RPR003", "rpr003_bad.py", "rpr003_clean.py"),
    ("RPR004", "rpr004_bad.py", "rpr004_clean.py"),
    ("RPR005", "rpr005_bad.py", "rpr005_clean.py"),
    ("RPR006", "rpr006_bad", "rpr006_clean"),
])
def test_lint_rule_fixtures(rule, bad, clean):
    fired = _lint_one(bad)
    assert fired, f"{rule} known-bad fixture produced no violations"
    assert {v.rule for v in fired} == {rule}
    assert _lint_one(clean) == []


def test_rpr002_bad_fires_both_directions():
    rules = [v.message for v in _lint_one("rpr002_bad.py")]
    assert any("never registered" in m for m in rules)
    assert any("dead registration" in m for m in rules)


def test_waiver_suppresses_and_is_counted(tmp_path):
    p = tmp_path / "waived.py"
    p.write_text(
        "import numpy as np\n\n\n"
        "def jitter(n):\n"
        "    # repro: allow(RPR004): demo-only jitter, never in a run\n"
        "    return np.random.randn(n)\n")
    violations, checked = run_lint(paths=[str(p)])
    assert violations == []
    assert checked["lint_waived"] == 1


def test_waiver_is_rule_specific(tmp_path):
    p = tmp_path / "wrong_rule.py"
    p.write_text(
        "import numpy as np\n\n\n"
        "def jitter(n):\n"
        "    # repro: allow(RPR001): wrong rule — must not suppress\n"
        "    return np.random.randn(n)\n")
    violations, _ = run_lint(paths=[str(p)])
    assert [v.rule for v in violations] == ["RPR004"]


def test_repo_tree_is_lint_clean():
    violations, checked = run_lint()
    assert violations == [], render_report(violations, checked)
    assert checked["lint_files"] > 50


# ---------------------------------------------------------------------------
# trace contracts: toy specimens that deliberately break them
# ---------------------------------------------------------------------------

def test_tracecheck_flags_broken_donation():
    # the donated buffer cannot back ANY output (no output of matching
    # byte size exists), so the runtime drops the donation and copies —
    # exactly the TRC001 "donated but copied" regression class
    def bad(x):
        return (x * 2.0)[:1]

    sp = TraceSpecimen(
        name="toy/broken_donation",
        fn=jax.jit(bad, donate_argnums=(0,)),
        args=(jnp.zeros(8),),
        donate=(0,), min_barriers=0, expect_scan=False)
    rules = {v.rule for v in check_specimen(sp)}
    assert "TRC001" in rules


def test_tracecheck_passes_honored_donation():
    def ok(x):
        return x * 2.0

    sp = TraceSpecimen(
        name="toy/honored_donation",
        fn=jax.jit(ok, donate_argnums=(0,)),
        args=(jnp.zeros(8),),
        donate=(0,), min_barriers=0, expect_scan=False)
    assert check_specimen(sp) == []


def test_tracecheck_flags_missing_scan_and_barriers():
    def flat(x):
        return x + 1.0

    sp = TraceSpecimen(
        name="toy/flat",
        fn=jax.jit(flat),
        args=(jnp.zeros(4),),
        donate=(), min_barriers=1, expect_scan=True)
    rules = [v.rule for v in check_specimen(sp)]
    assert rules.count("TRC004") == 2   # no barrier AND no scan


def test_tracecheck_flags_float64_conversion():
    def promote(x):
        return jax.lax.convert_element_type(x, jnp.float64)

    sp = TraceSpecimen(
        name="toy/promote",
        fn=jax.jit(promote),
        args=(jnp.zeros(4),),
        donate=(), min_barriers=0, expect_scan=False)
    # the promotion only materializes under x64 — exactly the implicit
    # weak-type blowup TRC003 exists to catch
    with jax.enable_x64():
        assert "TRC003" in {v.rule for v in check_specimen(sp)}


# ---------------------------------------------------------------------------
# registry coverage: every registered combiner is constructible — this
# also keeps the FedAvg alternatives ("mean", "masked_mean") referenced,
# so RPR002's dead-registration side stays honest
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mean", "masked_mean", "max_abs"])
def test_registered_combiners_resolve(name):
    assert name in registry_snapshot()["combiner"]
    assert callable(resolve_combiner(name))
    CombineSpec(combiner=name)   # constructs without raising


# ---------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------

def test_report_rendering_and_counts():
    violations = _lint_one("rpr004_bad.py")
    counts = rule_counts(violations)
    assert counts == {"RPR004": 1}
    human = render_report(violations, {"lint_files": 1})
    assert "RPR004" in human and "[checked]" in human
    js = render_json(violations, {"lint_files": 1})
    assert '"ok": false' in js
    clean = render_json([], {"lint_files": 1})
    assert '"ok": true' in clean
