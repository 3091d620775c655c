"""repro-lint self-tests: every rule fires on its planted fixture (the CI
acceptance gate — a planted violation per rule must fail the build),
negatives stay silent, the real package scans clean with an empty baseline,
the policy's kind set tracks the live stage registry, and the behaviour
fixed by the linter's findings stays fixed (recorder passivity, wid-ordered
lifecycle transitions)."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import repro
from repro.analysis.lint import ALL_RULES, run_lint
from repro.analysis.lint.policy import DEFAULT_POLICY
from repro.core import stages
from repro.obs.trace import TraceRecorder
from repro.serving.lifecycle import DEAD, HEALTHY, WorkerRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "lint_fixtures")


@pytest.fixture(scope="module")
def fixture_report():
    return run_lint([os.path.join(FIXTURES, "repro")], root=FIXTURES)


@pytest.fixture(scope="module")
def repo_report():
    pkg_dir = os.path.abspath(list(repro.__path__)[0])
    return run_lint([pkg_dir], root=os.path.dirname(pkg_dir))


# ---------------------------------------------------------------------------
# The CI acceptance gate: one planted violation per rule must be caught
# ---------------------------------------------------------------------------


def test_every_rule_fires_on_its_fixture(fixture_report):
    fired = {f.rule for f in fixture_report.findings}
    assert fired == set(ALL_RULES)


EXPECTED = {
    ("repro/core/bad_clock.py", "determinism/wall-clock"): 1,
    ("repro/core/bad_rng.py", "determinism/unseeded-rng"): 2,
    ("repro/core/bad_set_iter.py", "determinism/set-iteration"): 3,
    ("repro/serving/bad_kind.py", "registry/kind-branch"): 3,
    ("repro/obs/bad_hook.py", "hooks/obs-mutation"): 3,
    ("repro/core/wavefront.py", "hooks/unguarded-hook"): 1,
    ("repro/core/owned.py", "ownership/cross-domain-write"): 1,
    ("repro/core/owned.py", "ownership/cross-domain-call"): 1,
    ("repro/serving/bad_ingress.py", "determinism/wall-clock"): 1,
    ("repro/serving/bad_ingress.py", "ownership/cross-domain-write"): 1,
    ("repro/serving/bad_ingress.py", "ownership/cross-domain-call"): 1,
}


def test_exact_fixture_finding_counts(fixture_report):
    got: dict = {}
    for f in fixture_report.findings:
        got[(f.path, f.rule)] = got.get((f.path, f.rule), 0) + 1
    assert got == EXPECTED


def test_negative_files_stay_silent(fixture_report):
    silent = ("repro/core/stages.py", "repro/util/ok_clock.py",
              "repro/serving/ingress.py")
    assert not [f for f in fixture_report.findings if f.path in silent]


def test_inline_suppression_is_honoured(fixture_report):
    # bad_clock.py line 7 carries `# repro-lint: disable=wall-clock`
    assert not [f for f in fixture_report.findings
                if f.path == "repro/core/bad_clock.py" and f.line == 7]
    assert [f for f in fixture_report.suppressed
            if f.path == "repro/core/bad_clock.py" and f.line == 7]


def test_findings_are_sorted_and_json_stable(fixture_report):
    keys = [(f.path, f.line, f.col, f.rule) for f in fixture_report.findings]
    assert keys == sorted(keys)
    d = fixture_report.to_dict()
    assert d["schema_version"] == 1
    assert sum(d["summary"]["by_rule"].values()) == len(
        fixture_report.findings)
    assert json.loads(fixture_report.to_json()) == d


# ---------------------------------------------------------------------------
# The repo itself is clean (the hard CI gate) with an empty baseline
# ---------------------------------------------------------------------------


def test_repo_scans_clean(repo_report):
    assert repo_report.ok, repo_report.render_text()
    assert repo_report.findings == []


def test_repo_suppressions_are_justified(repo_report):
    # the only sanctioned suppression is the tracer's one clock read, which
    # times the wall spans and RealBackend's measured charges; anything new
    # forces a review through this pin
    assert [(f.rule, f.path) for f in repo_report.suppressed] == [
        ("determinism/wall-clock", "repro/obs/trace.py")]


def test_policy_kinds_match_live_registry():
    assert set(DEFAULT_POLICY.stage_kinds) == set(stages.STAGE_REGISTRY)


# ---------------------------------------------------------------------------
# CLI surface (what CI invokes)
# ---------------------------------------------------------------------------


def _cli(*args):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(HERE), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *args],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(HERE))


def test_cli_clean_repo_exits_zero(tmp_path):
    report = tmp_path / "repro-lint-report.json"
    proc = _cli("--format", "json", "--report", str(report))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(report.read_text())
    assert data["findings"] == []


def test_cli_fixture_violations_exit_one(tmp_path):
    report = tmp_path / "report.json"
    proc = _cli("--root", FIXTURES, "--report", str(report),
                os.path.join(FIXTURES, "repro"))
    assert proc.returncode == 1
    data = json.loads(report.read_text())
    assert {f["rule"] for f in data["findings"]} == set(ALL_RULES)


def test_cli_list_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    assert proc.stdout.split() == list(ALL_RULES)


# ---------------------------------------------------------------------------
# Regressions pinned by the linter's real findings on this repo
# ---------------------------------------------------------------------------


def test_trace_recorder_never_mutates_job_dicts():
    """The attribution span/row stash lives in recorder-owned side tables,
    not on the scheduler's job dicts (the hooks/obs-mutation finding this
    linter was built to catch)."""
    rec = TraceRecorder()
    req = SimpleNamespace(request_id=1, arrival_us=0.0, slo_us=0.0,
                          graph=SimpleNamespace(name="wf"), state={})
    job = {"reqs": [req], "n_steps": 4, "end": 100.0}
    before = dict(job)
    rec.gen_job(job, now=0.0)
    assert job == before  # record-only: no keys added, none changed
    assert id(job) in rec._job_spans and id(job) in rec._job_rows


def test_lifecycle_transitions_are_wid_ordered():
    """tick() reports transitions in canonical wid order even when workers
    were registered out of wid order (the set-iteration/ordering class of
    bug the determinism rule polices)."""
    reg = WorkerRegistry(0)
    for wid in (7, 2, 9, 0):
        reg.register(0.0, wid=wid)
    assert all(reg.state_of(w) == HEALTHY for w in (7, 2, 9, 0))
    plan = SimpleNamespace(crash_at=lambda wid: 0.0, stalls=[],
                           heartbeat_pause_start=lambda wid, now: None)
    out = reg.tick(1e9, plan)
    assert [t[0] for t in out] == [0, 2, 7, 9]
    assert all(t[2] == DEAD for t in out)
