"""Family ``ppo_seq_mla``: the ``ppo_seq`` family — ``make_ppo``'s
recurrent path with a sequence core, its window, its counters, its two
checks through ``IterationFns.collect`` and ``.block_grads`` at the
timed sizes — for the core whose carry is a cache of latents
(``torso="kimi_vl"``, ``models/kimi_vl.py``).

What differs from ``runners/ppo_seq.py``, and is all this file holds:
the plain reference (``reference/kimi_vl.py``: the EXPANDED form only,
so that ``reference_rollout`` holds the rollout's absorbed decode over
the cache to the other form of the same layer), the configuration's
published names in ``_check_model``, the steps below the stated
precision (``CONTROLS``) and the limits set from them. ``ppo_seq.py``
names its reference module inside three methods and its limits at
module level, so those methods are written again here; a ``benchmark``
PR that lets the class carry both would shrink this file to its data
(PERF.md section 7).

The stated precision is the Qwen3-Next configuration's: bfloat16 inputs
to every matrix product of a weight or of attention with float32 sums,
and float32 norms, router, softmax and heads; the cache of latents is an
input of such products and so bfloat16. The program is held to the
reference AT THAT PRECISION (``ppo_seq.py`` says why), the float32
reading is reported beside it with no limit.

The steps below it (``tools/precision_controls_mla.py`` sends each
through this module's own ``judge_rollout`` and
``compare_loss_and_grads`` at the timed sizes). ``CONTROLS``, which the
limits have to fail: the cache of latents in 8 bits (float8 e4m3), the
norms in bfloat16, and everything, parameters included, in bfloat16.
``REPORTED``, which they cannot: the router's sigmoid, bias and top-k in
bfloat16, and the attention's softmax in bfloat16. The program's
rollout is the ABSORBED form and the reference the EXPANDED one: two
formulations with bfloat16 roundings of their own (the query carried
into the latent space and the weighted sum of latents here, keys and
values there), so the program stands a rounding's worth from the
reference whatever it does, and that is where those two controls stand
too. At seeded normal(0.02) weights the scores are ~0.4 and attention
near uniform, so a bfloat16 softmax moves a probability by what the
product's own input rounding moves it; and a bfloat16 router flips the
near-ties of the top-6 of 64, which the upstream roundings flip as
often (one flip moves a whole expert term of weight ~0.4: that tail is
the 99th percentile of every row below). Keeping both intermediates in
float32 does not close it (PERF.md section 6, PR 31).

Limits (my chip runs, PR 31; the program over 12 seeds through
``verify``, the controls at 128 envs, seed 2147400047, through the
tool; log-prob / value; PERF.md section 6 has every number):

* 90th percentile of the absolute error, ``0.021 / 0.0185``: program
  0.0165-0.0167 / 0.0137-0.0148; norms 0.0261 / 0.0232, all 0.0343 /
  0.0312, cache 0.1152 / 0.1061 (router 0.0082 / 0.0064, softmax 0.0169
  / 0.0148). A limit each, because the values' error is an eighth
  smaller than the log-probs' in every row: one limit for both would
  leave 1.18 x on each side, two leave 1.25 x.
* 99th percentile, ``0.20 / 0.19``: program 0.1328-0.1396 / 0.1180-
  0.1331; cache 0.2754 / 0.2537. Every bfloat16 row reads 0.14-0.18
  here (the flipped routes): this limit is for a fault, not a precision.
* the largest median of any one env, ``0.0105 / 0.0095``: program
  0.0068-0.0074 / 0.0060-0.0063; all 0.0137 / 0.0124, cache 0.0451 /
  0.0419 (norms 0.0111 / 0.0099: the 90th percentile is what fails it).
  For a fault in one env of 128, which no percentile of all tokens sees.
* loss, cosine and norm of the gradient: the harness's own (3 %, 0.995,
  10 %; ``harness/checks.py``). ``block_grads`` read 1 - cosine 5.8e-4
  to 1.7e-3, loss within 0.12 % of its summands, norm within 0.12 %; the
  cache control 0.16, 0.84 %, 14.5 % (fails); all-bfloat16 4.0e-3, norms
  3.0e-3, router 2.8e-3, softmax 2.0e-3 (pass: the update's own
  backward rounds its cotangents to bfloat16, 1e-3 of cosine by itself).
"""

from __future__ import annotations

import dataclasses
import math

from perfbench.harness import checks
from perfbench.harness.spec import SpecError
from perfbench.rules import scope_lowering
from perfbench.runners import ppo_seq
from perfbench.runners.ppo_seq import (  # noqa: F401  (the family's tools)
    compare_loss_and_grads,
    error_stats,
    errors,
)

# One program, the `ppo` family's fused iteration, built the same way.
scope_lowering._LOWER.setdefault("ppo_seq_mla", scope_lowering._LOWER["ppo"])

# On the absolute error over envs x steps (`ppo_seq.error_stats`), for
# log-probs and for values: the 90th and the 99th percentile of all
# tokens, and the largest median of any one env.
ROLLOUT_LIMITS = {
    "log_prob": {"p90": 2.1e-2, "p99": 0.20, "env_p50_max": 1.05e-2},
    "value": {"p90": 1.85e-2, "p99": 0.19, "env_p50_max": 0.95e-2},
}
# `reference.forward`'s precision arguments for each step below the
# stated precision, over the stated ones (`Runner.precision`): the ones
# the limits have to fail.
CONTROLS = {
    "cache_float8": {"lower": ("cache",)},
    "norms_bfloat16": {"lower": ("norms",)},
    "all_bfloat16": {"dtype": "bfloat16", "products": None},
}
# Steps below the stated precision that this comparison cannot tell from
# the program (the docstring says why); the tool reports them all the
# same.
REPORTED = {
    "router_bfloat16": {"lower": ("router",)},
    "softmax_bfloat16": {"lower": ("softmax",)},
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
    def _check_model(self, cfg) -> None:
        """The preset's model is the configuration file's: every
        published key the program's dataclass has, at the published
        value or, for what is cut, at the held one."""
        model = self.cell.config["model"]
        published, held = model["published"], model["held"]
        stated = dict(
            published, num_hidden_layers=held["num_hidden_layers"],
            vocab_size=held["vocab_size"], first_expert=held["first_expert"],
            experts_held=held["experts_held"],
            capacity_factor=held["capacity_factor"],
        )
        if published["n_routed_experts"] != held["router_width"]:
            raise SpecError("the router is not the published width")
        for field in dataclasses.fields(cfg.seq_model):
            if field.name in stated and (
                getattr(cfg.seq_model, field.name) != stated[field.name]
            ):
                raise SpecError(
                    f"cell {self.cell.name!r}: the configuration states "
                    f"{field.name}={stated[field.name]!r}, the preset's "
                    f"model has {getattr(cfg.seq_model, field.name)!r}"
                )

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

        from perfbench.reference import kimi_vl as reference

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

        from perfbench.reference import kimi_vl as reference

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
