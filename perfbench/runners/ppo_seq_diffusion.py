"""Family ``ppo_seq_diffusion``: the ``ppo_seq`` family — ``make_ppo``'s
recurrent path with a sequence core, its window, its counters, its two
checks through ``IterationFns.collect`` and ``.block_grads`` at the
timed sizes — for the core that generates by diffusion over blocks
(``torso="sdar"``, ``models/sdar.py``): an env step is one pass of the
model over a block of positions, observations and actions are blocks,
and the key/value cache in the carry is extended by commit passes only.

What differs from ``runners/ppo_seq.py``, and is all this file holds:

* the plain reference (``reference/sdar.py``: the sequence form only,
  one pass over a trajectory's ``T x block`` positions under the
  block-diffusion mask, so that ``reference_rollout`` holds the
  rollout's passes through the cache — denoising passes whose rows the
  next pass overwrites, commit passes whose rows stay — to the full
  forward pass), with ``BlockReveal``'s log-probability written out
  plainly there;
* log-probabilities are compared on the DENOISING passes: a commit pass
  scores nothing and reads 0 on both sides, and a third of the passes
  at error 0 would only thin every percentile;
* the configuration's published names in ``_check_model``, the sampler's
  three numbers among them;
* the sampler's counters beside the expert layer's in the window's rows
  (``diffusion_passes_per_token`` reads one), and the check
  ``sampler_schedule``: every iteration ran the passes a committed token
  that the env's schedule states (``rollout_length /
  tokens_per_episode``);
* the steps below the stated precision (``CONTROLS``) and the limits set
  from them. ``ppo_seq.py`` names its reference module inside three
  methods and its limits at module level, so those methods are written
  again here, as in ``ppo_seq_mla.py`` (PERF.md section 7).

The stated precision is the other two language-model configurations':
bfloat16 inputs to every matrix product of a weight or of attention
with float32 sums, and float32 norms, router, softmax and heads; keys
and values in the cache are inputs of such products and so bfloat16.
The program is held to the reference AT THAT PRECISION (``ppo_seq.py``
says why), the float32 reading is reported beside it with no limit.

The steps below it (``tools/precision_controls_diffusion.py`` sends
each through this module's own ``judge_rollout`` and
``compare_loss_and_grads`` at the timed sizes), ``CONTROLS``, every one
of which the limits have to fail: keys and values in 8 bits (float8
e4m3), the norms in bfloat16, the router's softmax and top-k in
bfloat16, the attention's softmax in bfloat16, and everything,
parameters included, in bfloat16. There is no ``REPORTED`` row here as
in ``ppo_seq_mla``: the rollout's step form and the reference compute
the SAME per-head products from the same bfloat16 keys and values
(there the rollout is an absorbed form with roundings of its own), so
the program stands a quarter as far from the reference as that cell's
does, and a bfloat16 router or softmax, which there drowned in the
distance, reads 1.5 to 3 times the program here.

Limits (my chip runs, PR 33; the program over 14 seeds through
``verify``, the controls at 128 envs, seed 2147480011, through the tool;
log-prob over the 96 denoising passes / value over all 144; PERF.md
section 6 has every number):

* 90th percentile of the absolute error, ``0.0055 / 0.0024``: program
  0.0039-0.0040 / 0.0015-0.0017; router 0.0059 / 0.0035, softmax 0.0076
  / 0.0046, norms 0.0112 / 0.0079, all 0.0132 / 0.0096, cache 0.0181 /
  0.0138. A limit each: the values' error is 0.4 of the log-probs'.
* 99th percentile, ``0.0115 / 0.0058``: program 0.0069-0.0082 / 0.0035-
  0.0041; router 0.0145 / 0.0077, softmax 0.0156 / 0.0089, norms 0.0209
  / 0.0137, all 0.0229 / 0.0174, cache 0.0307 / 0.0227.
* the largest median of any one env, ``0.0045 / 0.0045``: program
  0.0023-0.0030 / 0.0011-0.0026 (the largest of 128 moves by a factor
  of two with the seed: an env whose early route flipped carries that
  on through its cache); router 0.0045 / 0.0056, softmax 0.0050 /
  0.0077, norms 0.0071 / 0.0125, all 0.0075 / 0.0187, cache 0.0107 /
  0.0194. For a fault in one env of 128, which no percentile sees; the
  router control is told apart by the percentiles, hardly by this.
* loss, cosine and norm of the gradient: the harness's own (3 %, 0.995,
  10 %; ``harness/checks.py``). ``block_grads`` read 1 - cosine 0.9e-5
  to 2.3e-5, loss within 0.03 % of its summands, norm within 0.07 %;
  the cache control 0.51 (fails), all-bfloat16 1.9e-4, norms 1.4e-4
  (pass: the rollout limits are what tells those apart).
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
)

# One program, the `ppo` family's fused iteration, built the same way.
scope_lowering._LOWER.setdefault(
    "ppo_seq_diffusion", scope_lowering._LOWER["ppo"]
)

# On the absolute error (`ppo_seq.error_stats`) of log-probs over envs x
# denoising passes and of values over envs x passes: the 90th and the
# 99th percentile, and the largest median of any one env.
ROLLOUT_LIMITS = {
    "log_prob": {"p90": 5.5e-3, "p99": 1.15e-2, "env_p50_max": 4.5e-3},
    "value": {"p90": 2.4e-3, "p99": 5.8e-3, "env_p50_max": 4.5e-3},
}
# `reference.forward`'s precision arguments for each step below the
# stated precision, over the stated ones (`Runner.precision`): the
# limits have to fail every one.
CONTROLS = {
    "cache_float8": {"lower": ("cache",)},
    "norms_bfloat16": {"lower": ("norms",)},
    "router_bfloat16": {"lower": ("router",)},
    "softmax_bfloat16": {"lower": ("softmax",)},
    "all_bfloat16": {"dtype": "bfloat16", "products": None},
}
# The sampler's counters in an iteration's metrics, kept in the rows.
DIFFUSION_COUNTERS = (
    "diffusion_passes_per_committed_token",
    "diffusion_revealed_per_denoise_pass",
    "diffusion_scored_position_share",
)


def errors(got, want):
    """``(log-prob errors, value errors)`` of two ``(log-probs, values)``
    ``[T, B]``: values over every pass, log-probs over the passes in
    which either side scored something in some env (a commit pass reads
    exactly 0 on both, and is left out)."""
    import numpy as np

    log_prob, value = ppo_seq.errors(got, want)
    scored = (np.asarray(got[0]) != 0.0) | (np.asarray(want[0]) != 0.0)
    return log_prob[scored.any(1)], value


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
        value or, for what is cut, at the held one; and the sampler's
        block, passes and mask id."""
        model = self.cell.config["model"]
        published, held = model["published"], model["held"]
        stated = dict(published, **{
            k: held[k] for k in (
                "num_hidden_layers", "vocab_size", "first_expert",
                "experts_held", "capacity_factor", "block_length",
                "denoising_steps", "mask_token_id",
            )
        })
        if published["num_experts"] != held["router_width"]:
            raise SpecError("the router is not the published width")
        for field in dataclasses.fields(cfg.seq_model):
            have = getattr(cfg.seq_model, field.name)
            want = stated.get(field.name, have)
            if isinstance(want, list):
                want = tuple(want)
            if have != want:
                raise SpecError(
                    f"cell {self.cell.name!r}: the configuration states "
                    f"{field.name}={want!r}, the preset's model has {have!r}"
                )

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        import jax

        window = super().measure(seconds, on_start, on_stop, span)
        counted = jax.device_get([
            {k: m[k] for k in DIFFUSION_COUNTERS} for m in self._reported
        ])
        for row, more in zip(window["log_rows"], counted, strict=True):
            row.update({k: float(v) for k, v in more.items()})
        env = self.cfg.env_params
        window["checks"]["sampler_schedule"] = all(
            math.isclose(
                row["diffusion_passes_per_committed_token"],
                env.episode_length / env.tokens_per_episode, rel_tol=1e-6,
            )
            for row in window["log_rows"]
        )
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
        """The reference's log-probs of what each pass revealed and its
        values, ``[T, B]`` each on the host, over the collected blocks
        in groups of a few envs."""
        import jax
        import numpy as np

        from perfbench.reference import sdar as reference

        model = self.cell.config["model"]
        precision = self.precision(**precision)

        # (parameters are arguments, never closed over.)
        @jax.jit
        def outputs(params, blocks, actions):
            logits, values = reference.forward(
                params, blocks, model["published"], model["held"], **precision
            )
            return reference.block_reveal(
                logits, blocks, actions, model["held"]["mask_token_id"]
            )[0], values

        b = int(self.cell.config["reference_check"]["rollout_block_envs"])
        with jax.default_matmul_precision("highest"):
            groups = [
                jax.device_get(outputs(
                    params, traj.obs[:, i:i + b], traj.actions[:, i:i + b]
                ))
                for i in range(0, traj.obs.shape[1], b)
            ]
        return tuple(np.concatenate(x, 1) for x in zip(*groups))

    def reference_grads(self, params, block, **precision):
        """``((loss, gradients), loss_scale)`` of the reference on
        ``block``, taken in equal parts of a few envs, advantages
        whitened over the whole block first: the parts' means are the
        block's."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import sdar as reference

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
