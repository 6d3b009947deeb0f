#!/usr/bin/env python3
"""What each step below the stated precision reads in the ``ppo_seq``
family's comparison, at the timed sizes: the evidence for that family's
limits (``runners/ppo_seq.py``), for setting them and for checking that
they still tell the precisions apart after a change to the model.

    python3 perfbench/tools/precision_controls.py --seed N \\
        [--workload ppo-qwen3next-recall] [--grads all_bfloat16,...] \\
        [--out chiprun_out/controls.jsonl]

One process, which holds the chip: the cell's set-up and two iterations
as a run makes them, then ``collect`` once, and through the runner's
own ``judge_rollout``

* ``program``: what the rollout stored, against the reference at the
  stated precision (what ``verify`` judges), which has to be ``ok``;
* each of ``CONTROLS`` (the DeltaNet state, the router's softmax, the
  norms, and everything, in bfloat16): the reference computed that way
  against the reference at the stated precision, over all envs, which
  has to come out NOT ``ok``;

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
    ap.add_argument("--workload", default="ppo-qwen3next-recall")
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

    from perfbench.runners import ppo_seq

    @contextlib.contextmanager
    def no_span(name):
        yield

    runner.setup()
    window = runner.measure(0.0, lambda: None, lambda: None, no_span)
    print(json.dumps({"row": "window", "log_rows": window["log_rows"]}),
          flush=True)
    params, traj = runner.collected()
    stated = runner.reference_outputs(params, traj)
    rows = [{"row": "program", "rollout": ppo_seq.judge_rollout(
        *ppo_seq.errors((traj.log_probs, traj.values), stated)
    )}]
    for name, lower in ppo_seq.CONTROLS.items():
        rows.append({"row": name, "rollout": ppo_seq.judge_rollout(
            *ppo_seq.errors(
                runner.reference_outputs(params, traj, **lower), stated
            )
        )})

    block = runner.check_block(traj)
    want, scale = runner.reference_grads(params, block)
    want = jax.device_get(want)  # off the device before the next tree
    loss, _, got = runner.fns.block_grads(params, block)
    rows[0]["grads"] = ppo_seq.compare_loss_and_grads(
        (loss, jax.device_get(got)), want, scale
    )
    del got
    for row in rows[1:]:
        if row["row"] in grads:
            got, _ = runner.reference_grads(
                params, block, **ppo_seq.CONTROLS[row["row"]]
            )
            row["grads"] = ppo_seq.compare_loss_and_grads(
                jax.device_get(got), want, scale
            )
            del got
    told_apart = True
    for row in rows:
        verdicts = [row[k]["ok"] for k in ("rollout", "grads") if k in row]
        row["ok"] = all(verdicts)
        told_apart &= row["ok"] == (row["row"] == "program")
        print(json.dumps(row), flush=True)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0 if told_apart else 1


if __name__ == "__main__":
    sys.exit(main())
