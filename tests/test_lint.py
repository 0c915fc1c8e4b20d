"""Tests for the minoslint contract checker (ISSUE 10 tentpole).

Three layers:

* **fixtures** — each ``tests/lint_fixtures/bad_*.py`` snippet must make
  the CLI exit non-zero with exactly the expected rule family, and its
  ``good_*.py`` twin must exit 0 (the fixtures carry ``minoslint: path=``
  pragmas so scoped rules apply);
* **tree** — ``python -m repro.lint`` exits 0 on the merged tree, with
  every suppression counted in the JSON report;
* **regressions** — deleting one ``_journal`` call (fleet retire) or one
  replay handler (session RETIRE case) from the *real* sources must trip
  the write-ahead / exhaustiveness pass, which is the acceptance
  criterion that the checker guards the architecture, not just the
  fixtures.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.lint import RULES, run
from repro.lint.core import (LintContext, SourceFile, discover_files,
                             load_context)

REPO = Path(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = REPO / "tests" / "lint_fixtures"

#: bad fixture -> rule ids it must (exactly) trigger
BAD_FIXTURES = {
    "bad_writeahead.py": {"W101"},
    "bad_record_kinds.py": {"W201", "W202", "W203"},
    "bad_determinism.py": {"W301", "W302", "W303", "W304"},
    "bad_layering.py": {"W401", "W403"},
    "bad_facade.py": {"W402"},
    "bad_floatcontract.py": {"W501", "W502"},
}

GOOD_FIXTURES = [
    "good_writeahead.py", "good_record_kinds.py", "good_determinism.py",
    "good_layering.py", "good_facade.py", "good_floatcontract.py",
]


def _cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.lint", *args],
        cwd=REPO, env=env, capture_output=True, text=True)


# -- fixtures ------------------------------------------------------------

def test_every_rule_has_a_bad_fixture():
    covered = set().union(*BAD_FIXTURES.values())
    assert covered == set(RULES), (
        f"rules without fixture coverage: {set(RULES) - covered}")


@pytest.mark.parametrize("name,expected",
                         sorted(BAD_FIXTURES.items()))
def test_bad_fixture_fails(name, expected):
    proc = _cli("--format", "json", str(FIXTURES / name))
    assert proc.returncode != 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    got = {f["rule"] for f in report["findings"]}
    assert got == expected, f"{name}: expected {expected}, got {got}"


@pytest.mark.parametrize("name", GOOD_FIXTURES)
def test_good_fixture_passes(name):
    proc = _cli(str(FIXTURES / name))
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- tree ----------------------------------------------------------------

def test_tree_is_clean():
    """Zero non-suppressed findings on the merged tree — the state every
    PR must restore before landing."""
    proc = _cli("--format", "json")
    report = json.loads(proc.stdout)
    assert proc.returncode == 0, "\n".join(
        f"{f['path']}:{f['line']}: {f['rule']} {f['message']}"
        for f in report["findings"])
    assert report["ok"] is True
    assert report["counts"]["findings"] == 0


def test_suppressions_are_counted():
    """Inline ``minoslint: disable=`` pragmas are visible in the report —
    suppression is auditable, not silent."""
    proc = _cli("--format", "json")
    report = json.loads(proc.stdout)
    assert report["counts"]["suppressed"] >= 3  # the justified sites
    rules = {f["rule"] for f in report["suppressed"]}
    assert {"W301", "W304"} <= rules
    for f in report["suppressed"]:
        assert f["path"] and f["line"] > 0


def test_report_artifact_written(tmp_path):
    out = tmp_path / "lint_report.json"
    proc = _cli("--format", "json", "--output", str(out))
    assert proc.returncode == 0
    assert json.loads(out.read_text()) == json.loads(proc.stdout)


def test_fixtures_excluded_from_default_scan():
    scanned = {p.relative_to(REPO).as_posix()
               for p in discover_files(REPO)}
    assert not any(p.startswith("tests/lint_fixtures/") for p in scanned)
    assert "tests/test_lint.py" in scanned
    assert "src/repro/fleet/controller.py" in scanned


# -- regressions against the real sources --------------------------------

def _ctx_with_replacement(path: str, old: str, new: str) -> LintContext:
    files = []
    replaced = False
    for p in discover_files(REPO):
        rel = p.relative_to(REPO).as_posix()
        text = p.read_text()
        if rel == path:
            assert old in text, f"expected snippet missing from {path}"
            text = text.replace(old, new)
            replaced = True
        files.append(SourceFile(rel, text))
    assert replaced, f"{path} not in the default scan"
    return LintContext(files, root=str(REPO))


def _active(findings):
    return [f for f in findings if not f.suppressed]


def test_deleting_a_journal_call_trips_writeahead():
    """Remove fleet retire's write-ahead record: the following
    ``self.jobs.pop`` becomes an unjournaled mutation (W101), and the
    RETIRE replay handler goes dead (W202)."""
    ctx = _ctx_with_replacement(
        "src/repro/fleet/controller.py",
        "self._journal(kinds.RETIRE, job_id=job_id)", "pass")
    rules = {f.rule for f in _active(run(ctx))}
    assert "W101" in rules
    assert "W202" in rules


def test_deleting_a_replay_handler_trips_exhaustiveness():
    """Remove the RETIRE case from ``_apply_record``: the kind is still
    emitted, so resume would silently drop it — W201."""
    ctx = _ctx_with_replacement(
        "src/repro/api/session.py",
        '            case kinds.RETIRE:\n'
        '                self.retire(data["job_id"])\n', "")
    findings = _active(run(ctx))
    assert any(f.rule == "W201" and "retire" in f.message
               for f in findings)


def test_emitting_an_unregistered_kind_trips_registry():
    """A new emit site with a kind missing from store/kinds.py -> W203."""
    ctx = _ctx_with_replacement(
        "src/repro/fleet/controller.py",
        "self._journal(kinds.RETIRE, job_id=job_id)",
        'self._journal("vanish", job_id=job_id)')
    rules = {f.rule for f in _active(run(ctx))}
    assert "W203" in rules
    assert "W201" in rules  # and nothing replays it either


def test_clean_tree_via_api():
    """API parity with the CLI: load_context + run on the real tree."""
    findings = _active(run(load_context(REPO)))
    assert findings == [], "\n".join(f.render() for f in findings)
