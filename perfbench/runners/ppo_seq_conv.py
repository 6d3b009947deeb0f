"""Family ``ppo_seq_conv``: the ``ppo_seq`` family for a core whose
mixers are mostly gated short convolutions, with grouped-query
attention and the expert layer (``torso="lfm2"``, ``models/lfm2.py``):
token-level PPO, one episode one sequence.

It is ``runners/ppo_seq.py`` — its set-up, its window and the expert
layer's counters (``moe_dispatch_dropless``), its two checks at the
timed sizes (``reference_rollout``: the rollout, step by step through
the convolutions' tails and the key/value cache, against the
reference's full forward in blocks of envs; ``reference_block_grads``:
``block_grads`` on one timed minibatch against the reference's loss and
gradient), its ``check_block``, ``errors``, ``error_stats`` and
``compare_loss_and_grads`` — but for what follows, and nothing else:

* the reference is ``reference/lfm2.py`` (the convolution an explicit
  sum of taps, attention one causal softmax with repeated key/value
  heads, every token through every held expert: no tail, no cache),
  named inside the two methods that run it, as the parent names its own
  (ROADMAP C8);
* ``_check_model`` reads the held share of THIS configuration: the
  layers of ``layer_types``, the leading dense ones, the experts, the
  vocabulary;
* one more counter read off every iteration of the window (``COUNTERS``:
  ``ppo_seq``'s and ``shortconv_tail_share``) and one more check of it,
  ``shortconv_tail_used``: the tail share finite and strictly above 0 in
  every iteration. At 0 the rollout's steps read nothing from their
  tails, and the comparison through the tails would test nothing of
  them;
* its own ``CONTROLS``, ``REPORTED`` and limits.

The stated precision: bfloat16 inputs to every matrix product of a
weight or of attention with float32 sums; norms, the two gates, the
convolution and its tails, router, softmax and heads in float32; the
key/value cache, an input of attention's products, in bfloat16. The
program is held to the reference AT THAT PRECISION (``ppo_seq.py`` says
why), the float32 reading reported beside it with no limit.

The steps below it (``tools/precision_controls_conv.py`` sends each
through this module's ``judge_rollout`` and ``compare_loss_and_grads``
at the timed sizes): the router in bfloat16 (``router_bfloat16``), the
norms in bfloat16 (``norms_bfloat16``) and everything, parameters
included, in bfloat16 (``all_bfloat16``), which the limits fail
(``CONTROLS``); and the convolutions' tails held in bfloat16
(``tails_bfloat16``: the terms on earlier tokens read their ``u``
rounded), which they cannot tell apart (``REPORTED``, below).

Limits, each the geometric middle of the largest the program read and
what the weakest control that is told apart read (chip runs on one TPU
v5e, 128 envs x 512 tokens: the program at seven seeds, 2147440001,
2147440031-34 through ``verify`` and 2147440002, 2147440035 through the
tool; the controls at 2147440002 and 2147440035; log-prob / value;
PERF.md section 6 has every number):

* 90th percentile of the absolute error, ``0.0098 / 0.0076``: program
  0.00431-0.00442 / 0.00244-0.00297; norms 0.0218-0.0221 /
  0.0197-0.0200, all 0.0300-0.0303 / 0.0273-0.0278 (router
  0.0088-0.0103 / 0.0080-0.0088, under 3 x the program's: the 99th
  percentile is what fails it). 2.2 x of room over the log-probs', 2.6 x
  over the values'.
* 99th percentile, ``0.0287 / 0.0246``: program 0.00827-0.00912 /
  0.00560-0.00733; norms 0.0901-0.0938 / 0.0826-0.0898, router
  0.0981-0.0993 / 0.0927-0.0932, all 0.1034-0.1050 / 0.0961-0.1028 (a
  bfloat16 router flips near-ties of the top-4 of 64, and a flipped
  route moves a whole expert term: that is this percentile).
* the largest median of any one env, ``0.00431 / 0.00339``: program
  0.00192-0.00205 / 0.00113-0.00136; norms 0.00908-0.00923 /
  0.00843-0.00867, all 0.01275-0.01299 / 0.01142-0.01217 (router
  0.0022 / 0.0013: the percentiles are what fail it). For a fault in
  one env of 128, which no percentile of all tokens sees.
* ``block_grads`` (``GRAD_LIMITS``), 1 - the cosine of the two
  gradients, ``3.0e-4``: program 4.9e-5 to 1.14e-4; norms 7.8e-4, router
  8.2e-4, all 1.10e-3 and 1.28e-3. The loss's error and the norm's are
  numbers the precision hardly moves: the program read up to 4.7e-4
  and 2.7e-4, the all-bfloat16 control 1.0e-4 and 3.9e-4 of the loss,
  1.35e-3 and 2.8e-6 of the norm (the router 7.0e-4 and 1.0e-3, the
  norms 1.6e-4 and 4.3e-4). No limit between those readings tells a
  control apart at every seed, and a limit a few times over the
  program's reading would fail a sound seed now and then (the norm's
  error spans 1.3e-5 to 2.7e-4 over seven seeds), so the two keep the
  harness's (3 % and 10 %; ``harness/checks.py``), far above the
  program's.

``REPORTED``, which these limits cannot tell from the program, and why:
``tails_bfloat16`` reads 0.00379 / 0.00237 at the 90th percentile, 0.86
x the program's own distance from the stated reference (99th 0.00673 /
0.00424, largest env median 0.00182 / 0.00114). A tail holds ``u = B
x~``, whose two carried terms enter ``v`` beside the current one and are
then multiplied by ``C`` and rounded to bfloat16 where ``W_out`` takes
them, as the stated precision has it: rounding the tail adds one
rounding of the same size to a value rounded there anyway, smaller than
what the program's own accumulation order and bfloat16 products leave.
A limit that failed it would fail the sound program. What the tails
themselves must hold is tested where it can be seen: the step form
against the sequence pass in float32 (``tests/test_lfm2.py``: 2e-5, and
a step form that drops its tail is 1e-3 away), and the window's
``shortconv_tail_used`` (0.81 on the chip) that the rollout reads them.
"""

from __future__ import annotations

import dataclasses
import math

from perfbench.harness import checks
from perfbench.harness.spec import SpecError
from perfbench.rules import scope_lowering
from perfbench.runners import ppo_seq
from perfbench.runners.ppo_seq import (  # noqa: F401  (the family's tools)
    error_stats,
    errors,
)

# One program, the `ppo` family's fused iteration, built the same way.
scope_lowering._LOWER.setdefault("ppo_seq_conv", scope_lowering._LOWER["ppo"])

# On the absolute error over envs x steps (`ppo_seq.error_stats`), for
# log-probs and for values: the 90th and the 99th percentile of all
# tokens, and the largest median of any one env.
ROLLOUT_LIMITS = {
    "log_prob": {"p90": 9.8e-3, "p99": 2.87e-2, "env_p50_max": 4.31e-3},
    "value": {"p90": 7.6e-3, "p99": 2.46e-2, "env_p50_max": 3.39e-3},
}
# On ``block_grads`` against the reference's loss and gradient
# (`ppo_seq.compare_loss_and_grads`'s three numbers): the loss's error
# over its summands' magnitudes, 1 - the cosine of the two gradients,
# and the relative error of the gradient's norm.
GRAD_LIMITS = {"loss_err": checks.LOSS_TOL, "one_minus_cosine": 3.0e-4,
               "grad_norm_err": checks.GRAD_NORM_RTOL}
# The program's counter of the convolutions, read off every iteration
# of the window beside the expert layer's (`models/lfm2.py` names it).
TAIL_SHARE = "shortconv_tail_share"
COUNTERS = ppo_seq.COUNTERS + (TAIL_SHARE,)
# `reference.forward`'s precision arguments for each step below the
# stated precision, over the stated ones (`Runner.precision`): the ones
# the limits have to fail.
CONTROLS = {
    "router_bfloat16": {"lower": ("router",)},
    "norms_bfloat16": {"lower": ("norms",)},
    "all_bfloat16": {"dtype": "bfloat16", "products": None},
}
# Steps below the stated precision that this comparison cannot tell from
# the program (the docstring says why); the tool reports them all the
# same.
REPORTED = {
    "tails_bfloat16": {"lower": ("tails",)},
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


def compare_loss_and_grads(system, reference, scale) -> dict:
    """``ppo_seq.compare_loss_and_grads``'s numbers under this family's
    ``GRAD_LIMITS``."""
    report = ppo_seq.compare_loss_and_grads(system, reference, scale)
    readings = {"loss_err": report["loss_err"],
                "one_minus_cosine": 1.0 - report["grad_cosine"],
                "grad_norm_err": report["grad_norm_err"]}
    report["ok"] = all(
        math.isfinite(x) and x <= GRAD_LIMITS[k] for k, x in readings.items()
    )
    return report


class Runner(ppo_seq.Runner):
    def _check_model(self, cfg) -> None:
        """The preset's model is the configuration file's: every
        published key the program's dataclass has, at the published
        value or, for what is cut, at the held one."""
        model = self.cell.config["model"]
        published, held = model["published"], model["held"]
        stated = dict(published, **{
            k: held[k] for k in (
                "num_hidden_layers", "num_dense_layers", "layer_types",
                "vocab_size", "first_expert", "experts_held",
                "capacity_factor",
            )
        })
        if published["num_experts"] != held["router_width"]:
            raise SpecError("the router is not the published width")

        def plain(name, x):  # the dataclass's tuples are JSON's lists
            if name == "rope_parameters":   # and dicts
                return dict(x)
            return list(x) if isinstance(x, (list, tuple)) else x

        for field in dataclasses.fields(cfg.seq_model):
            got = plain(field.name, getattr(cfg.seq_model, field.name))
            if field.name in stated and got != plain(
                field.name, stated[field.name]
            ):
                raise SpecError(
                    f"cell {self.cell.name!r}: the configuration states "
                    f"{field.name}={stated[field.name]!r}, the preset's "
                    f"model has {got!r}"
                )

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        import jax

        window = super().measure(seconds, on_start, on_stop, span)
        shares = [float(m[TAIL_SHARE])
                  for m in jax.device_get(self._reported)]
        for row, share in zip(window["log_rows"], shares, strict=True):
            row[TAIL_SHARE] = share
        window["checks"]["shortconv_tail_used"] = bool(shares) and all(
            math.isfinite(s) and s > 0.0 for s in shares
        )
        window[TAIL_SHARE] = sum(shares) / max(len(shares), 1)
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

        from perfbench.reference import lfm2 as reference

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

        from perfbench.reference import lfm2 as reference

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
