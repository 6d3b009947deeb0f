"""Family ``ppo_seq_ssm``: the ``ppo_seq`` family for a sequence core
with state-space layers and NO expert layer
(``torso="granite_hybrid"``): token-level PPO, one episode one sequence.

It is ``runners/ppo_seq.py`` — its set-up, its window, its two checks
at the timed sizes (``reference_rollout``, ``reference_block_grads``),
its ``check_block``, ``errors``, ``error_stats`` and
``compare_loss_and_grads`` — but for what follows, and nothing else:

* the reference is ``reference/granite_hybrid.py`` (the recurrence's
  unrolled sum: no chunks, no state, no cache);
* ``_check_model`` knows no expert keys: the held layers are
  ``layer_types``;
* the window's counters are ``COUNTERS = ("mamba_state_retention",)``:
  that file reads four ``moe_*`` keys off every iteration, which a dense
  core does not report. In place of ``moe_dispatch_dropless`` the
  window's own check is ``mamba_state_retained``: the mean of
  ``exp(Delta A)`` over the rollout's steps, heads and Mamba-2 layers
  strictly between 0 and 1 in every iteration (at 0 a step forgets
  everything and the rollout's comparison through the state would test
  nothing; at 1 nothing decays);
* its own ``CONTROLS``, ``REPORTED`` and limits.

Which reference, and why: as in ``ppo_seq``, the program is held to the
reference AT THE STATED PRECISION (``reference.forward(...,
products=bfloat16)``: bfloat16 inputs to every matrix product of a
weight or of attention, float32 sums; float32 norms, convolution,
``Delta``, decays, state, scan products, softmax and heads); the float32
reading is taken and reported (``rollout["against_float32"]``) with no
limit.

The steps below the stated precision (``CONTROLS``; the reference
computes them, ``tools/precision_controls_ssm.py`` sends each through
this module's ``judge_rollout`` and ``compare_loss_and_grads`` at the
timed sizes): the state-space state held in bfloat16
(``state_bfloat16``: the recurrence a token at a time, its state rounded
after every step), the scan's products with bfloat16 inputs
(``scan_bfloat16``), the norms in bfloat16 (``norms_bfloat16``) and
everything, parameters included, in bfloat16 (``all_bfloat16``). Each
has to come out as not correct by at least one limit (``CONTROLS``), or
stand under ``REPORTED`` with its reason: the last two are told apart,
the first two are not (below).

Limits, each the geometric middle of the largest the program read and
what the weakest control that is told apart (``norms_bfloat16``) read
(my chip runs, PR 37: the controls at 32 envs, seed 2147400011, through
``tools/precision_controls_ssm.py``, read again at seed 2147420099 within
3 %; the program over 17 seeds, PERF.md section 6, PR 37; log-prob /
value; 1.37-1.4 x of room on either side). Logits are ``h E^T / 8`` of a unit-norm ``h``, so every
error here is small in absolute terms: the program stands 0.6 x as far
from the stated reference as that stands from the float32 one (90th
percentile 0.00139 / 0.0093 against 0.00214 / 0.0133).

* 90th percentile of the absolute error, ``0.0019 / 0.0130``: program
  0.00132-0.00139 / 0.0078-0.0093; norms 0.00263 / 0.0180, all 0.0163 /
  0.1136.
* 99th percentile, ``0.0030 / 0.0205``: program 0.00208-0.00219 /
  0.0124-0.0146; norms 0.00410 / 0.0285, all 0.0339 / 0.2273.
* the largest median of any one env, ``0.00088 / 0.0058``: program
  0.00059-0.00063 / 0.0035-0.0041; norms 0.00121 / 0.0079, all 0.0053 /
  0.0352. For a fault in one env of 32, which no percentile of all
  tokens sees.
* loss, cosine and norm of the gradient: the harness's own (3 %, 0.995,
  10 %; ``harness/checks.py``). ``block_grads`` read 1 - cosine 0.8e-4
  to 1.2e-4, loss within 0.02 % of its summands, norm within 0.02 %;
  the all-bfloat16 control 1.6e-2 (fails), 0.14 %, 0.12 %.

``REPORTED``, which these limits cannot tell from the program, and why:
``state_bfloat16`` reads 0.00163 / 0.0109 at the 90th percentile and
``scan_bfloat16`` 0.00157 / 0.0105, 1.15-1.2 x the program's own
distance from the stated reference (99th percentile 0.00253 / 0.0171
and 0.00245 / 0.0166 against 0.00219 / 0.0146; largest env median
0.00074 / 0.0049 and 0.00072 / 0.0047 against 0.00063 / 0.0041). The
scan's output is rounded to bfloat16 where the next product (``out_proj``
behind the gated norm) takes it, as the stated precision has it: a
state or a chunk product held in bfloat16 adds one rounding of the same
size to a value that is rounded there anyway, and a decay of ~0.84 a
step forgets a state's rounding in some six steps. A limit that failed
these two readings would sit within 10 % of the sound program's. (A
PROGRAM with a bfloat16 state would stand from the stated reference by
its own distance and the control's together, ~0.0021 / 0.014 if they add
as independent errors, which the limits above do fail; the tool judges
the control's reading alone.)
"""

from __future__ import annotations

import dataclasses
import math

from perfbench.harness import checks
from perfbench.harness.spec import SpecError
from perfbench.rules import scope_lowering
from perfbench.runners import ppo as ppo_family
from perfbench.runners import ppo_seq
from perfbench.runners.ppo_seq import (  # noqa: F401  (the family's tools)
    compare_loss_and_grads,
    error_stats,
    errors,
)

# One program, the `ppo` family's fused iteration, built the same way.
scope_lowering._LOWER.setdefault("ppo_seq_ssm", scope_lowering._LOWER["ppo"])

# On the absolute error over envs x steps (`ppo_seq.error_stats`), for
# log-probs and for values: the 90th and the 99th percentile of all
# tokens, and the largest median of any one env.
ROLLOUT_LIMITS = {
    "log_prob": {"p90": 1.9e-3, "p99": 3.0e-3, "env_p50_max": 8.8e-4},
    "value": {"p90": 1.30e-2, "p99": 2.05e-2, "env_p50_max": 5.8e-3},
}
COUNTERS = ("mamba_state_retention",)
# `reference.forward`'s precision arguments for each step below the
# stated precision, over the stated ones (`Runner.precision`): the ones
# the limits have to fail.
CONTROLS = {
    "norms_bfloat16": {"lower": ("norms",)},
    "all_bfloat16": {"dtype": "bfloat16", "products": None},
}
# Steps below the stated precision that this comparison cannot tell from
# the program (the docstring says why); the tool reports them all the
# same.
REPORTED = {
    "state_bfloat16": {"lower": ("state",)},
    "scan_bfloat16": {"lower": ("scan",)},
}


def judge_rollout(log_prob_err, value_err) -> dict:
    """``ppo_seq.judge_rollout`` under this family's limits."""
    report = {"log_prob": error_stats(log_prob_err),
              "value": error_stats(value_err)}
    report["ok"] = all(
        math.isfinite(report[k]["max"]) and report[k][name] <= limit
        for k, limits in ROLLOUT_LIMITS.items()
        for name, limit in limits.items()
    )
    return report


class Runner(ppo_seq.Runner):
    mamba_state_retention = None  # the window's counter

    def _check_model(self, cfg) -> None:
        """The preset's model is the configuration file's: every
        published key the program's dataclass has, at the published
        value or, for what is cut, at the held one."""
        model = self.cell.config["model"]
        published, held = model["published"], model["held"]
        stated = dict(
            published, num_hidden_layers=held["num_hidden_layers"],
            layer_types=held["layer_types"], vocab_size=held["vocab_size"],
        )
        def plain(x):  # a JSON list is the dataclass's tuple
            return list(x) if isinstance(x, (list, tuple)) else x

        for field in dataclasses.fields(cfg.seq_model):
            got = plain(getattr(cfg.seq_model, field.name))
            if field.name in stated and got != plain(stated[field.name]):
                raise SpecError(
                    f"cell {self.cell.name!r}: the configuration states "
                    f"{field.name}={stated[field.name]!r}, the preset's "
                    f"model has {got!r}"
                )

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        import jax

        self._reported.clear()
        # (the `ppo` family's window: `ppo_seq`'s reads the expert
        # layer's counters off every iteration)
        window = ppo_family.Runner.measure(
            self, seconds, on_start, on_stop, span
        )
        rows = [
            {k: float(m[k]) for k in COUNTERS + ("loss",)}
            for m in jax.device_get(self._reported)
        ]
        window["failed"] = sum(not math.isfinite(r["loss"]) for r in rows)
        window["checks"]["mamba_state_retained"] = (
            len(rows) == window["iterations"]
            and all(0.0 < r["mamba_state_retention"] < 1.0 for r in rows)
        )
        # One row an iteration with the program's counter, and the
        # benchmark's clock over them (`rules/log_counter.py`).
        ends = window["row_times_s"]
        window["log_rows"] = rows
        window["log_window_s"] = ends[-1] - ends[0]
        self.mamba_state_retention = sum(
            r["mamba_state_retention"] for r in rows
        ) / max(len(rows), 1)
        window["mamba_state_retention"] = self.mamba_state_retention
        return window

    def verify(self) -> dict:
        import jax

        params, traj = self.collected()
        stated = self.reference_outputs(params, traj)
        plain = self.reference_outputs(params, traj, products=None)
        system = (traj.log_probs, traj.values)
        rollout = judge_rollout(*errors(system, stated))
        rollout["against_float32"] = dict(zip(  # reported, not judged
            ("log_prob", "value"), map(error_stats, errors(system, plain))
        ))
        block = self.check_block(traj)
        loss_s, _, grads_s = self.fns.block_grads(params, block)
        grads_s = jax.device_get(grads_s)  # off the device before the next
        self.report = compare_loss_and_grads(
            (loss_s, grads_s), *self.reference_grads(params, block)
        )
        self.report["rollout"] = rollout
        return {"reference_rollout": rollout["ok"],
                "reference_block_grads": self.report["ok"]}

    def reference_outputs(self, params, traj, **precision):
        """The reference's log-probs at the taken actions and its
        values, ``[T, B]`` each on the host, over the collected tokens
        in blocks of a few envs."""
        import jax
        import numpy as np

        from perfbench.reference import granite_hybrid as reference

        model = self.cell.config["model"]
        precision = self.precision(**precision)

        # (parameters are arguments, never closed over.)
        @jax.jit
        def outputs(params, tokens, actions):
            logits, values = reference.forward(
                params, tokens, model["published"], model["held"], **precision
            )
            return reference.categorical(logits, actions)[0], values

        b = int(self.cell.config["reference_check"]["rollout_block_envs"])
        with jax.default_matmul_precision("highest"):
            blocks = [
                jax.device_get(outputs(
                    params, traj.obs[:, i:i + b], traj.actions[:, i:i + b]
                ))
                for i in range(0, traj.obs.shape[1], b)
            ]
        return tuple(np.concatenate(x, 1) for x in zip(*blocks))

    def reference_grads(self, params, block, **precision):
        """``((loss, gradients), loss_scale)`` of the reference on
        ``block``, taken in equal parts of a few envs, advantages
        whitened over the whole block first: the parts' means are the
        block's."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import granite_hybrid as reference

        cfg, model = self.cfg, self.cell.config["model"]
        precision = self.precision(**precision)
        hp = {"clip_eps": cfg.clip_eps, "vf_coef": cfg.vf_coef,
              "ent_coef": cfg.ent_coef}
        block = {k: v for k, v in block.items()
                 if k not in ("resets", "core")}
        block["advantages"] = reference.whiten(block["advantages"])
        mb = block["obs"].shape[1]
        part = int(self.cell.config["reference_check"]["grad_part_envs"])

        @jax.jit
        def part_grads(params, blk):
            return jax.value_and_grad(reference.ppo_loss, has_aux=True)(
                params, blk, hp, model["published"], model["held"],
                whitened=True, **precision
            )

        def add(total, new):
            return jax.tree_util.tree_map(
                lambda t, x: t + x * (part / mb), total, new
            )

        add, total = jax.jit(add, donate_argnums=0), None
        with jax.default_matmul_precision("highest"):
            for i in range(0, mb, part):
                new = part_grads(params, {
                    k: v[:, i:i + part] for k, v in block.items()
                })
                total = add(
                    jax.tree_util.tree_map(jnp.zeros_like, new)
                    if total is None else total, new,
                )
        (loss, parts), grads = total
        return (loss, grads), checks.loss_scale(parts, hp)
