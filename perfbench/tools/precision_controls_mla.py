#!/usr/bin/env python3
"""``tools/precision_controls.py`` for a family that brings its own
controls and limits: what each step below the stated precision reads in
the family's comparison, at the timed sizes — the evidence for the
limits of ``runners/ppo_seq_mla.py``, for setting them and for checking
that they still tell the precisions apart after a change to the model.

    python3 perfbench/tools/precision_controls_mla.py --seed N \\
        [--workload ppo-kimivl-recall] [--grads all_bfloat16,...] \\
        [--out chiprun_out/controls_mla.jsonl]

That tool reads ``ppo_seq.CONTROLS``, ``judge_rollout`` and
``compare_loss_and_grads`` by name; this one reads them from the module
the cell's runner is defined in, and is otherwise the same: one process,
which holds the chip; the cell's set-up and two iterations as a run
makes them, then ``collect`` once, and through the family's own
``judge_rollout``

* ``program``: what the rollout stored, against the reference at the
  stated precision (what ``verify`` judges), which has to be ``ok``;
* each of the family's ``CONTROLS``: the reference computed that way
  against the reference at the stated precision, over all envs, which
  has to come out NOT ``ok``; and each of its ``REPORTED`` (steps the
  family says its comparison cannot tell apart), judged and printed the
  same way and bound to no verdict;

and through ``compare_loss_and_grads`` on the check's minibatch,
``program`` (``block_grads``) and each control named by ``--grads``
against the reference's loss and gradient at the stated precision. A
control is told apart where either verdict is not ``ok``. One JSON line
a row; exit 1 where the program fails or a control passes both.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ppo-kimivl-recall")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--grads", default="all_bfloat16")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from perfbench.harness import driver, spec

    cell = spec.load_cell(args.workload)
    return run(driver.load_runner(cell.family)(cell, args.seed),
               [g for g in args.grads.split(",") if g], args.out)


def run(runner, grads=(), out=None) -> int:
    import jax

    family = sys.modules[type(runner).__module__]

    @contextlib.contextmanager
    def no_span(name):
        yield

    def emit(row):
        print(json.dumps(row), flush=True)
        if out:
            os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")

    runner.setup()
    window = runner.measure(0.0, lambda: None, lambda: None, no_span)
    emit({"row": "window", "log_rows": window["log_rows"]})
    params, traj = runner.collected()
    stated = runner.reference_outputs(params, traj)
    rows = [{"row": "program", "rollout": family.judge_rollout(
        *family.errors((traj.log_probs, traj.values), stated)
    )}]
    reported = getattr(family, "REPORTED", {})
    controls = {**family.CONTROLS, **reported}
    for name, lower in controls.items():
        rows.append({"row": name, "rollout": family.judge_rollout(
            *family.errors(
                runner.reference_outputs(params, traj, **lower), stated
            )
        )})
        emit({"row": name, "partial": rows[-1]["rollout"]})

    block = runner.check_block(traj)
    want, scale = runner.reference_grads(params, block)
    want = jax.device_get(want)  # off the device before the next tree
    loss, _, got = runner.fns.block_grads(params, block)
    rows[0]["grads"] = family.compare_loss_and_grads(
        (loss, jax.device_get(got)), want, scale
    )
    del got
    for row in rows[1:]:
        if row["row"] in grads:
            got, _ = runner.reference_grads(
                params, block, **controls[row["row"]]
            )
            row["grads"] = family.compare_loss_and_grads(
                jax.device_get(got), want, scale
            )
            del got
    told_apart = True
    for row in rows:
        verdicts = [row[k]["ok"] for k in ("rollout", "grads") if k in row]
        row["ok"] = all(verdicts)
        if row["row"] not in reported:
            told_apart &= row["ok"] == (row["row"] == "program")
        emit(row)
    return 0 if told_apart else 1


if __name__ == "__main__":
    sys.exit(main())
