"""Tier-1 gate + analyzer self-tests for the static-analysis pass.

Two layers:

  - the GATE: every checker over the whole repo must come back clean
    (modulo the justified suppressions in ``analysis/baseline.toml``,
    none of which may be stale), in well under the 30 s budget;
  - the ANALYZERS: fixture trees under ``tests/analysis_fixtures/``
    carry one known-bad construct per rule next to known-good
    counterparts, with ``# EXPECT: RULE`` comments on the offending
    lines — each test asserts the checker fires EXACTLY the declared
    (rule, line) set, so both detection and non-detection are pinned.

The analysis package is stdlib-only (AST, no imports of the code
under analysis), so this module stays cheap even cold.
"""

from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path

import pytest

from actor_critic_algs_on_tensorflow_tpu import analysis
from actor_critic_algs_on_tensorflow_tpu.analysis.core import (
    CHECKERS,
    expected_findings,
)

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "analysis_fixtures"
CHECK = ROOT / "scripts" / "check.py"


# --- the gate --------------------------------------------------------

def test_full_repo_gate_is_green_and_fast():
    t0 = time.monotonic()
    findings = analysis.run_checkers(ROOT)
    sups = analysis.load_baseline(analysis.default_baseline_path(ROOT))
    kept, quiet, stale = analysis.apply_baseline(findings, sups)
    elapsed = time.monotonic() - t0
    assert not kept, (
        "static analysis found unsuppressed violations:\n"
        + "\n".join(f.format() for f in kept)
    )
    assert not stale, (
        "stale baseline suppressions (matched nothing — delete them):\n"
        + "\n".join(f"{s.rule} in {s.file}: {s.reason}" for s in stale)
    )
    assert elapsed < 30.0, f"full-repo pass took {elapsed:.1f}s"


def test_baseline_suppressions_are_justified():
    sups = analysis.load_baseline(analysis.default_baseline_path(ROOT))
    for s in sups:
        # load_baseline already rejects empty reasons; require real
        # prose, not a placeholder.
        assert len(s.reason) >= 30, (
            f"suppression {s.rule} in {s.file} needs a substantive "
            f"reason, got {s.reason!r}"
        )


def test_every_rule_is_owned_by_exactly_one_checker():
    seen = {}
    for name, chk in CHECKERS.items():
        for rule in chk.rules:
            assert rule not in seen, (
                f"rule {rule} claimed by both {seen[rule]} and {name}"
            )
            seen[rule] = name
    assert len(seen) >= 18  # the catalogue only grows


# --- the analyzers, against fixtures ---------------------------------

def _run_fixture(subdir: str, checker: str):
    root = FIXTURES / subdir
    files = sorted(p for p in root.rglob("*") if p.is_file())
    findings = CHECKERS[checker].run(root, files)
    actual = {(f.rule, f.file, f.line) for f in findings}
    expected = set()
    for p in files:
        if p.suffix in (".py", ".ini"):
            relp = p.resolve().relative_to(root.resolve()).as_posix()
            expected |= {
                (rule, relp, line) for rule, line in expected_findings(p)
            }
    return actual, expected, findings


@pytest.mark.parametrize(
    "subdir,checker",
    [
        ("wire", "wire"),
        ("jit", "jit"),
        ("lock", "lock"),
        ("drift", "drift"),
        ("drift_span", "drift"),
        ("markers", "markers"),
    ],
)
def test_fixture_rules_fire_exactly_as_declared(subdir, checker):
    actual, expected, findings = _run_fixture(subdir, checker)
    missing = expected - actual
    extra = actual - expected
    assert not missing and not extra, (
        f"{checker}: expected-but-silent {sorted(missing)}; "
        f"fired-but-undeclared {sorted(extra)}\nall findings:\n"
        + "\n".join(f.format() for f in findings)
    )
    # Every finding carries a usable anchor and a fix hint.
    for f in findings:
        assert f.line > 0 and f.file and f.hint


def test_bench_schema_fixtures():
    root = FIXTURES / "bench"
    files = sorted(root.glob("*.json"))
    findings = CHECKERS["bench-schema"].run(root, files)
    by_file = {}
    for f in findings:
        by_file.setdefault(f.file, []).append(f.rule)
    # Good ledgers: silent.
    assert "BENCH_good.json" not in by_file
    assert "MULTICHIP_good.json" not in by_file
    # BENCH_bad: missing cmd + parsed missing vs_baseline + replay
    # missing e2e_steps_per_sec and the PR-17 pipelined keys (one
    # finding listing them all) + elastic missing desyncs + promotion
    # missing promote_p99_ms + tenancy missing p99_isolation_ratio
    # (BENCH001), rc / parsed.value / replay.ingest_tps /
    # replay.overlap_frac / elastic.epochs_monotonic /
    # promotion.promote_p50_ms / promotion.late_publish_fenced /
    # tenancy.tenants / tenancy.flood_frames_shed mistyped
    # (BENCH002), cpu_limited int (BENCH003).
    assert sorted(by_file["BENCH_bad.json"]) == [
        "BENCH001", "BENCH001", "BENCH001", "BENCH001", "BENCH001",
        "BENCH001",
        "BENCH002", "BENCH002", "BENCH002", "BENCH002", "BENCH002",
        "BENCH002", "BENCH002", "BENCH002", "BENCH002",
        "BENCH003",
    ]
    # MULTICHIP_bad: missing skipped (BENCH001), ok mistyped (BENCH002).
    assert sorted(by_file["MULTICHIP_bad.json"]) == [
        "BENCH001", "BENCH002",
    ]


def test_repo_bench_ledgers_pass_schema():
    files = [p for p in ROOT.glob("*.json")
             if p.name.startswith(("BENCH_", "MULTICHIP_"))]
    assert files, "bench ledgers missing from the repo root"
    findings = CHECKERS["bench-schema"].run(ROOT, files)
    assert not findings, "\n".join(f.format() for f in findings)


# --- the runner ------------------------------------------------------

def test_check_script_exits_zero_on_clean_tree():
    res = subprocess.run(
        [sys.executable, str(CHECK), "--quiet"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr


def test_check_script_reports_violations_with_anchor_and_rule():
    # --no-baseline exposes the deliberately-suppressed finding (the
    # shard_count topology echo), exercising the failure path: exit 1
    # and a file:line [RULE] report — the same shape any reintroduced
    # fixture-style violation produces.
    res = subprocess.run(
        [sys.executable, str(CHECK), "--no-baseline",
         "--checker", "drift"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "[DRIFT004]" in res.stdout
    assert "utils/metric_names.py:" in res.stdout  # file:line anchor


def test_check_script_changed_mode_is_fast():
    t0 = time.monotonic()
    res = subprocess.run(
        [sys.executable, str(CHECK), "--changed", "--quiet"],
        cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    elapsed = time.monotonic() - t0
    assert res.returncode == 0, res.stdout + res.stderr
    # Interactive budget is <5 s (measured ~1.2 s); the assert leaves
    # headroom for a fully-contended CI core.
    assert elapsed < 15.0, f"--changed took {elapsed:.1f}s (budget 5s)"
