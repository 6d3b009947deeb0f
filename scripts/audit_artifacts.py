"""Artifact-ledger audit (VERDICT r4 next#5).

The ledger is the product: every artifact the docs cite must either
exist under ``runs/`` or be explicitly marked cycled with a
regeneration pointer. This script enforces that, so stale references
(like r4's ``runs/pong21-serve``) can't rot silently:

1. every literal ``runs/NAME`` in PERF.md / PERF_HISTORY.md / README.md /
   ARCHITECTURE.md
   resolves to a directory on disk, or the word "cycled" appears within
   3 lines of the reference (trailing sentence punctuation is stripped
   from the captured name before the file-vs-artifact heuristic, so
   ``runs/foo.`` at the end of a sentence is the artifact ``foo``, not
   a dotted filename);
2. every row of a markdown table whose header column is ``artifact``
   names a directory that exists, or carries a "cycled" marker
   anywhere in the row OR in the footnote window just below the table
   (the ``*cycled = ...`` legend convention);
3. no STALE interrupted-save droppings (``*.orbax-checkpoint-tmp``
   older than ~10 minutes) exist under ``runs/`` — a young tmp dir is
   a healthy in-flight async save, not a problem (flagging those made
   the audit flaky against live training runs).

Run directly (exit 0 = green) or via tests/test_artifact_audit.py.
"""

from __future__ import annotations

import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOCS = ("PERF.md", "PERF_HISTORY.md", "README.md", "ARCHITECTURE.md")

# Rule 3: an *.orbax-checkpoint-tmp younger than this is an in-flight
# save (async checkpointing is the default), not a stale dropping.
TMP_STALE_AFTER_S = 600.0


def _footnote_window(lines: list, i: int, span: int = 4) -> str:
    """The first few non-table lines after the table containing row
    ``i`` — where the ``*cycled = ...`` legend lives."""
    j = i
    while j < len(lines) and lines[j].lstrip().startswith("|"):
        j += 1
    return "\n".join(lines[j: j + span])


def audit(repo: Path = REPO, *, now: float | None = None) -> list:
    problems = []
    now = time.time() if now is None else now
    run_dirs = {
        p.name for p in (repo / "runs").iterdir() if p.is_dir()
    } if (repo / "runs").is_dir() else set()

    for doc in DOCS:
        path = repo / doc
        if not path.exists():
            continue
        lines = path.read_text().splitlines()

        # 1. literal runs/NAME references
        for i, line in enumerate(lines):
            for m in re.finditer(r"runs/([A-Za-z0-9_.-]+)", line):
                # Sentence periods are not part of the name: strip them
                # BEFORE the "has a dot = it's a file" heuristic.
                name = m.group(1).rstrip(".")
                if not name:
                    continue
                if name in run_dirs or "." in name:  # files like .log are not artifacts
                    continue
                context = "\n".join(lines[max(0, i - 3): i + 4]).lower()
                if "cycled" not in context:
                    problems.append(
                        f"{doc}:{i + 1}: `runs/{name}` missing on disk "
                        "and not marked cycled"
                    )

        # 2. rows of "| artifact |" tables
        in_table = False
        for i, line in enumerate(lines):
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if not line.lstrip().startswith("|"):
                in_table = False
                continue
            if cells and cells[0].lower() == "artifact":
                in_table = True
                continue
            if not in_table or set(line) <= {"|", "-", " "}:
                continue
            first = cells[0]
            name = first.split()[0].strip("`*").rstrip(".") if first else ""
            if not re.fullmatch(r"[a-z0-9][a-z0-9_.-]*", name):
                continue
            if name in run_dirs:
                continue
            # The cycled marker may sit in ANY cell of the row (a
            # status column) or in the footnote legend under the table.
            marked = "cycled" in line.lower() or (
                "cycled" in _footnote_window(lines, i).lower()
                and "*" in first
            )
            if not marked:
                problems.append(
                    f"{doc}:{i + 1}: artifact `{name}` missing on disk "
                    "and row not marked cycled"
                )

    # 3. STALE interrupted orbax saves (mtime-gated: in-flight healthy
    # async saves also look like *-tmp dirs for a few seconds).
    for tmp in (repo / "runs").glob("**/*orbax-checkpoint-tmp*"):
        try:
            age = now - tmp.stat().st_mtime
        except OSError:
            continue  # vanished mid-scan: the save just finalized
        if age >= TMP_STALE_AFTER_S:
            problems.append(
                f"stale interrupted save: {tmp.relative_to(repo)} "
                f"(age {age / 60:.0f} min)"
            )

    return problems


def main() -> int:
    problems = audit()
    for p in problems:
        print(p)
    print(f"artifact audit: {'GREEN' if not problems else f'{len(problems)} problem(s)'}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
