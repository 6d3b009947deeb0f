#!/usr/bin/env python3
"""Runs of cells, one process each, as the driver makes them; for
measuring spreads before setting a bound.

    python3 perfbench/tools/series.py --out chiprun_out/pb/a.jsonl \\
        [--seconds S] [--sets 2 --runs 6] [--alternate] [--first] \\
        <cell>[:trace] ...

For each cell: with ``--first`` one run that is set apart (it compiles),
then ``--sets`` sets of ``--runs`` runs, every run with another seed.
Each result line goes to ``--out`` with its cell, set, seed and exit
code; the spreads (distance between the quartiles over the median, per
set) are printed at the end. This process never touches JAX, so each
child has the chip to itself. ``perfbench_out/`` is copied beside
``--out`` so that a chip call brings the per-run files back.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def one(cell, trace, seed, seconds):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cmd = bench["command"] + [
        "--workload", cell, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", str(trace),
    ]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    result = None
    if p.returncode == 0 and lines:
        result = json.loads(lines[-1])
    else:
        sys.stdout.write(p.stdout[-3000:] + p.stderr[-6000:])
    return {"cell": cell, "trace": trace, "seed": seed, "rc": p.returncode,
            "wall_s": round(time.time() - t0, 1), "result": result,
            "run_file": _run_file(cell, trace) if result else None,
            "log": [l for l in lines if l.startswith("[perfbench]")]}


def _run_file(cell, trace):
    """What the run wrote beside its result and the next run overwrites:
    where set-up went, the whole-window rate, the row intervals, the
    pauses."""
    path = os.path.join(ROOT, "perfbench_out", cell,
                        f"run_trace{trace}.json")
    try:
        run = json.load(open(path))
    except (OSError, ValueError):
        return None
    keep = {k: run.get(k) for k in ("setup_phases_s", "backend_init_s")}
    keep.update({k: run["window"].get(k) for k in (
        "elapsed_s", "whole_window_env_steps_per_s_per_chip",
        "pause_share_pct", "row_interval_ms", "row_times_s",
    )})
    return keep


def spread(values):
    """Distance between the quartiles over the median, the quartiles as
    the driver takes them: at (n + 1) / 4 and 3 (n + 1) / 4, so that ONE
    low run among six moves the lower quartile by a quarter of its
    distance. (The refusal of PR 23 read 575 env-steps/s/chip in a set
    where one `impala-pong` run had lost 0.435 %, a quarter of that
    run's 2,302; the inclusive quartiles this tool first used read 0
    there, and PERF.md's first table understated every spread that had
    one outlier.)"""
    q = statistics.quantiles(values, n=4, method="exclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--runs", type=int, default=1)
    p.add_argument("--first", action="store_true")
    p.add_argument("--alternate", action="store_true",
                   help="run the sets' runs in turn (1, 2, 1, 2, ...), as "
                        "a check's pairs are, instead of one set after "
                        "the other")
    p.add_argument("--seed0", type=int, default=100)
    p.add_argument("cells", nargs="+")
    a = p.parse_args()
    seconds = a.seconds or json.load(
        open(os.path.join(ROOT, "BENCHMARK.json"))
    )["run_seconds"]
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    seed, rows = a.seed0, []
    with open(a.out, "a") as out:
        def record(row, set_name):
            row["set"] = set_name
            rows.append(row)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row)[:1500], flush=True)

        for spec in a.cells:
            cell, _, trace = spec.partition(":")
            trace = int(trace or 0)
            if a.first:
                seed += 1
                record(one(cell, trace, seed, seconds), "first")
            order = [(s, r) for s in range(a.sets) for r in range(a.runs)]
            if a.alternate:
                order.sort(key=lambda sr: (sr[1], sr[0]))
            for s, _ in order:
                seed += 1
                record(one(cell, trace, seed, seconds), f"set{s + 1}")
    print("\ncell set metric n median spread")
    keys = sorted({(r["cell"], r["set"]) for r in rows if r["result"]})
    for cell, set_name in keys:
        got = [r["result"] for r in rows
               if r["result"] and (r["cell"], r["set"]) == (cell, set_name)]
        for name in got[0]["metrics"]:
            vals = [g["metrics"][name]["value"] for g in got
                    if name in g["metrics"]]
            sp = spread(vals) if len(vals) >= 2 else float("nan")
            print(cell, set_name, name, len(vals),
                  repr(statistics.median(vals)), f"{sp:.6f}")
        print(cell, set_name, "correct", [g["correct"] for g in got],
              "failed", [g["failed"] for g in got],
              "attempted", [g["attempted"] for g in got])
    src = os.path.join(ROOT, "perfbench_out")
    if os.path.isdir(src):
        dst = os.path.join(os.path.dirname(os.path.abspath(a.out)),
                           "perfbench_out")
        shutil.copytree(src, dst, dirs_exist_ok=True)
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
