"""fedlint: every rule fires on its bad fixture, stays silent on the
good one, respects suppressions — plus the repo itself stays clean and
the determinism sanitizer holds on both engines."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import run_analysis
from repro.analysis.core import load_baseline

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "fedlint"


def lint(targets, root, rules=None, baseline=None):
    violations, _ = run_analysis(
        [Path(t) for t in targets], root=Path(root), rules=rules,
        baseline=baseline)
    return violations


def active(violations):
    return [v for v in violations if not v.suppressed]


# ---------------------------------------------------------------- rules
def test_spec_hygiene_fires_on_bad_fixture():
    vs = active(lint([FIXTURES / "spec_hygiene_bad.py"], FIXTURES,
                     rules=["spec-hygiene"]))
    symbols = {v.symbol for v in vs}
    assert "MutableSpec" in symbols          # non-frozen dataclass
    assert "LopsidedSchedule" in symbols     # __eq__ without __hash__
    assert "IdentitySpec" in symbols         # no eq machinery at all
    assert "SharedDefaultSpec" in symbols    # shared default instance
    assert "LiteralDefaultSpec" in symbols   # class-level [] default
    assert len(vs) >= 5


def test_spec_hygiene_silent_on_good_fixture():
    assert active(lint([FIXTURES / "spec_hygiene_good.py"], FIXTURES,
                       rules=["spec-hygiene"])) == []


def test_jit_purity_fires_on_bad_fixture():
    vs = active(lint([FIXTURES / "jit_purity_bad.py"], FIXTURES,
                     rules=["jit-purity"]))
    msgs = " | ".join(v.message for v in vs)
    assert "time.time" in msgs               # clock in @jax.jit
    assert "print" in msgs                   # print in vmapped fn
    assert "np.random.rand" in msgs          # unseeded draw
    assert "helper" in msgs                  # one call level deep
    assert "global" in msgs                  # global mutation
    assert "without a seed" in msgs          # unseeded default_rng in scan
    assert "TraceAnnotation" in msgs         # profiler span in @jax.jit
    assert len(vs) >= 7


def test_jit_purity_silent_on_good_fixture():
    assert active(lint([FIXTURES / "jit_purity_good.py"], FIXTURES,
                       rules=["jit-purity"])) == []


def test_parity_surface_fires_on_bad_fixture():
    vs = active(lint([FIXTURES / "parity_bad"], FIXTURES / "parity_bad",
                     rules=["parity-surface"]))
    by_symbol = {v.symbol: v for v in vs}
    assert "ScenarioReport.sim_only_counter" in by_symbol
    assert "sim engine path" in \
        by_symbol["ScenarioReport.sim_only_counter"].message
    assert "ScenarioReport.never_written" in by_symbol
    # bytes_moved is written on both sides: no violation for it
    assert "ScenarioReport.bytes_moved" not in by_symbol


def test_parity_surface_silent_on_good_fixture():
    assert active(lint([FIXTURES / "parity_good"],
                       FIXTURES / "parity_good",
                       rules=["parity-surface"])) == []


def test_x64_scoping_fires_on_bad_fixture():
    vs = active(lint([FIXTURES / "kernels" / "x64_bad.py"], FIXTURES,
                     rules=["x64-scoping"]))
    msgs = " | ".join(v.message for v in vs)
    assert "global jax_enable_x64" in msgs
    assert "jnp.float64" in msgs
    assert 'dtype="float64"' in msgs
    assert len(vs) >= 3


def test_x64_scoping_silent_on_good_fixture():
    assert active(lint([FIXTURES / "kernels" / "x64_good.py"], FIXTURES,
                       rules=["x64-scoping"])) == []


def test_x64_scoping_only_applies_to_kernels(tmp_path):
    # same bad source outside kernels/ is out of the rule's scope
    src = (FIXTURES / "kernels" / "x64_bad.py").read_text()
    other = tmp_path / "host_code.py"
    other.write_text(src)
    assert active(lint([other], tmp_path, rules=["x64-scoping"])) == []


def test_deprecation_hygiene_fires_on_bad_fixture():
    vs = active(lint([FIXTURES / "deprecation_bad.py"], FIXTURES,
                     rules=["deprecation-hygiene"]))
    msgs = " | ".join(v.message for v in vs)
    assert "ClientPlane" in msgs and "sneaky_internal_caller" in msgs
    assert "stacklevel" in msgs
    assert len(vs) == 2


def test_deprecation_hygiene_silent_on_good_fixture():
    assert active(lint([FIXTURES / "deprecation_good.py"], FIXTURES,
                       rules=["deprecation-hygiene"])) == []


# --------------------------------------------------------- suppressions
def test_inline_suppressions_same_line_and_above():
    vs = lint([FIXTURES / "suppressed.py"], FIXTURES,
              rules=["spec-hygiene"])
    by_symbol = {v.symbol: v for v in vs}
    assert by_symbol["QuietSpec"].suppressed_by == "inline"
    assert by_symbol["AboveLineSpec"].suppressed_by == "inline"
    # naming a different rule does not silence this one
    assert by_symbol["LoudSpec"].suppressed_by is None


def test_baseline_suppression_requires_reason(tmp_path):
    good = tmp_path / "fedlint.toml"
    good.write_text(textwrap.dedent('''\
        [[suppress]]
        rule = "spec-hygiene"
        file = "spec_hygiene_bad.py"
        symbol = "MutableSpec"
        reason = "fixture: demonstrates the failure mode"
    '''))
    vs = lint([FIXTURES / "spec_hygiene_bad.py"], FIXTURES,
              rules=["spec-hygiene"], baseline=good)
    by_symbol = {v.symbol: v for v in vs}
    assert by_symbol["MutableSpec"].suppressed_by == "baseline"
    assert by_symbol["LopsidedSchedule"].suppressed_by is None

    bad = tmp_path / "bad.toml"
    bad.write_text('[[suppress]]\nrule = "spec-hygiene"\n'
                   'file = "x.py"\nreason = ""\n')
    with pytest.raises(ValueError, match="justified"):
        load_baseline(bad)

    incomplete = tmp_path / "incomplete.toml"
    incomplete.write_text('[[suppress]]\nrule = "spec-hygiene"\n')
    with pytest.raises(ValueError, match="missing"):
        load_baseline(incomplete)


# ------------------------------------------------------------- CLI + repo
def test_cli_strict_exit_codes(tmp_path):
    env_path = str(REPO / "src")
    bad = FIXTURES / "spec_hygiene_bad.py"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict", str(bad)],
        capture_output=True, text=True, cwd=tmp_path,
        env={"PYTHONPATH": env_path, "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "spec-hygiene" in proc.stdout

    good = FIXTURES / "spec_hygiene_good.py"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict", str(good)],
        capture_output=True, text=True, cwd=tmp_path,
        env={"PYTHONPATH": env_path, "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_repo_is_clean_under_strict():
    """The acceptance bar: zero unsuppressed violations in src/repro."""
    vs = lint([REPO / "src" / "repro"], REPO,
              baseline=REPO / "fedlint.toml")
    assert active(vs) == [], "\n".join(v.render() for v in active(vs))
    # and the baseline file itself stays reviewed: every entry justified
    entries = load_baseline(REPO / "fedlint.toml")
    assert all(e.reason.strip() for e in entries)
    # every baseline entry still matches a real (suppressed) violation —
    # stale entries are creep in the other direction
    suppressed = [v for v in vs if v.suppressed_by == "baseline"]
    for e in entries:
        assert any(e.matches(v) for v in suppressed), \
            f"stale fedlint.toml entry: {e}"


# ------------------------------------------------------------- sanitizer
def test_sanitizer_double_replay_and_shuffle():
    from repro.analysis.sanitize import run_sanitizer
    rows = run_sanitizer(quick=True)
    checks = {(c, s) for c, s, _ in rows}
    # both engines double-replayed
    assert ("double-replay", "sanitize-storm/analytic") in checks
    assert ("double-replay", "sanitize-storm/sim") in checks
    # shuffled same-timestamp insertion proven order-independent
    assert any(c == "shuffled-insertion" for c, _, _ in rows)


def test_sanitizer_catches_order_dependence():
    """The shuffle check must actually be able to fail: feed it a
    workload with distinct timestamps and it refuses (nothing to
    prove); feed it divergent reports and it raises."""
    import dataclasses as dc

    from repro.analysis.sanitize import (SanitizeFailure,
                                         check_shuffled_insertion,
                                         default_specs)
    from repro.core import WorkloadSpec

    spec = next(s for s in default_specs(quick=True)
                if s.engine == "sim" and s.outages is None
                and isinstance(s.workload, WorkloadSpec)
                and s.workload.kind == "storm")
    spread = dc.replace(
        spec, workload=dc.replace(spec.workload, jitter=1e6, seed=3))
    with pytest.raises(ValueError, match="same-timestamp"):
        check_shuffled_insertion(spread)
    with pytest.raises(ValueError, match="simulator"):
        check_shuffled_insertion(dc.replace(spec, engine="analytic"))
    assert isinstance(SanitizeFailure(), AssertionError)
