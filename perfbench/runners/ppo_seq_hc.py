"""Family ``ppo_seq_hc``: the ``ppo_seq_mla`` family for a core whose
residual path is manifold-constrained hyper-connections around latent
attention and the expert layer (``torso="xing"``, ``models/xing.py``):
token-level PPO, one episode one sequence.

It is ``runners/ppo_seq_mla.py`` — ``ppo_seq``'s set-up, window and
counters of the expert layer (``moe_dispatch_dropless``), its two checks
at the timed sizes (``reference_rollout``: the rollout's absorbed decode
through the cache against the reference's expanded forward, in blocks
of envs; ``reference_block_grads``: ``block_grads`` on one timed
minibatch against the reference's loss and gradient) — but for what
follows, and nothing else:

* the reference is ``reference/xing.py`` (four streams, the Sinkhorn a
  Python loop, the expanded attention with its low-rank query and YaRN
  positions), named inside the two methods that run it, as the parent
  names its own (ROADMAP C8);
* ``_check_model`` reads the held share of THIS configuration (the
  leading dense layers too, ``first_k_dense_replace``) and the YaRN
  group as the dict it is published as;
* two more checks of the window, on the program's own counters of the
  rollout (``COUNTERS``), every iteration: ``mhc_sinkhorn_converged``,
  the largest distance of a row or column sum of any ``H_res`` from 1
  at most ``SINKHORN_RESIDUAL_MAX`` (the projection did its work), and
  ``mhc_res_mixed``, the mean off-diagonal mass of ``H_res`` inside
  ``OFFDIAG_RANGE`` (the streams are mixed, so the comparison through
  them tests the mix: at the identity it would test nothing of it);
* its own ``CONTROLS``, ``REPORTED`` and limits.

The stated precision is Kimi-VL's with the residual path in float32:
bfloat16 inputs to every matrix product of a weight or of attention with
float32 sums; norms, router, softmax, heads, the four streams, the mHC
coefficient product, the Sinkhorn iterations and both mixes in float32.
The program is held to the reference AT THAT PRECISION, the float32
reading reported beside it with no limit.

The steps below it (``tools/precision_controls_hc.py`` sends each
through this module's ``judge_rollout`` and ``compare_loss_and_grads``
at the timed sizes): the streams held in bfloat16, the Sinkhorn
iterations in bfloat16, the cache of latents in 8 bits, the norms in
bfloat16 and everything in bfloat16. **A program whose residual streams
are held in bfloat16 fails** (90th percentile 1.57 x and env median 1.48
x the sound program's); **one whose Sinkhorn runs in bfloat16 cannot be
told apart** (``REPORTED``): it reads 0.0116 / 0.0096 at the 90th
percentile, 0.6 x the program's own distance from the stated reference,
because the absorbed rollout stands a rounding of its own from the
expanded reference whatever the Sinkhorn does, and ``H_res`` rounded to
bfloat16 moves a mix of four streams by less than that.

Limits (my chip runs, PR 39: the program at seeds 2147390041,
2147390061 through ``verify`` and 2147390051 through the tool, the
controls at 2147390051; log-prob / value; PERF.md section 6 has every
number), each the geometric middle of the largest the program read and
the control it is there to fail:

* 90th percentile of the absolute error, ``0.0244 / 0.0212``: program
  0.0194-0.0195 / 0.0155-0.0168; streams 0.0306 / 0.0269, norms 0.0349 /
  0.0307, all 0.0425 / 0.0382, cache 0.0897 / 0.0809. 1.25 x of room on
  either side of the log-probs', 1.27 x of the values'.
* the largest median of any one env, ``0.0114 / 0.0100``: program
  0.0090-0.0094 / 0.0074-0.0082; streams 0.0138 / 0.0121 (1.2 x on
  either side). For a fault in one env of 128, which no percentile of
  all tokens sees.
* 99th percentile, ``0.248 / 0.245``: program 0.167-0.183 / 0.154-0.176
  (the flipped routes of the top-4 of 64, as in Kimi-VL's cell); cache
  0.338 / 0.335. Every bfloat16 row reads 0.22-0.26 here: this limit is
  for a fault, and the streams' control fails the two above.
* loss, cosine and norm of the gradient: the harness's own (3 %, 0.995,
  10 %; ``harness/checks.py``). ``block_grads`` read 1 - cosine 1.2e-3
  to 2.1e-3, loss within 0.04 % of its summands, norm within 0.08 %;
  all-bfloat16 5.9e-3 (fails); streams 3.7e-3, Sinkhorn 1.0e-3 (pass:
  the update's own backward rounds its cotangents to bfloat16).
"""

from __future__ import annotations

import dataclasses
import math

from perfbench.harness import checks
from perfbench.harness.spec import SpecError
from perfbench.rules import scope_lowering
from perfbench.runners import ppo_seq_mla
from perfbench.runners.ppo_seq import (  # noqa: F401  (the family's tools)
    compare_loss_and_grads,
    error_stats,
    errors,
)

# One program, the `ppo` family's fused iteration, built the same way.
scope_lowering._LOWER.setdefault("ppo_seq_hc", scope_lowering._LOWER["ppo"])

# On the absolute error over envs x steps (`ppo_seq.error_stats`), for
# log-probs and for values: the 90th and the 99th percentile of all
# tokens, and the largest median of any one env.
ROLLOUT_LIMITS = {
    "log_prob": {"p90": 2.44e-2, "p99": 0.248, "env_p50_max": 1.14e-2},
    "value": {"p90": 2.12e-2, "p99": 0.245, "env_p50_max": 1.00e-2},
}
# The program's counters of the hyper-connections, read off every
# iteration of the window (`models/hyper_connections.py` names them).
COUNTERS = ("mhc_res_offdiag_mass", "mhc_sinkhorn_residual")
SINKHORN_RESIDUAL_MAX = 1e-3
OFFDIAG_RANGE = (0.05, 0.95)
# `reference.forward`'s precision arguments for each step below the
# stated precision, over the stated ones (`Runner.precision`): the ones
# the limits have to fail.
CONTROLS = {
    "streams_bfloat16": {"lower": ("streams",)},
    "cache_float8": {"lower": ("cache",)},
    "norms_bfloat16": {"lower": ("norms",)},
    "all_bfloat16": {"dtype": "bfloat16", "products": None},
}
# Steps below the stated precision that this comparison cannot tell from
# the program (the docstring says why); the tool reports them all the
# same.
REPORTED = {
    "sinkhorn_bfloat16": {"lower": ("sinkhorn",)},
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


class Runner(ppo_seq_mla.Runner):
    def _check_model(self, cfg) -> None:
        """The preset's model is the configuration file's: every
        published key the program's dataclass has, at the published
        value or, for what is cut, at the held one."""
        model = self.cell.config["model"]
        published, held = model["published"], model["held"]
        stated = dict(published, **{
            k: held[k] for k in (
                "num_hidden_layers", "first_k_dense_replace", "vocab_size",
                "first_expert", "experts_held", "capacity_factor",
            )
        })
        if published["n_routed_experts"] != held["router_width"]:
            raise SpecError("the router is not the published width")
        for field in dataclasses.fields(cfg.seq_model):
            got = getattr(cfg.seq_model, field.name)
            if field.name == "rope_scaling" and got is not None:
                got = dict(got)  # a tuple of items in the dataclass
            if field.name in stated and got != stated[field.name]:
                raise SpecError(
                    f"cell {self.cell.name!r}: the configuration states "
                    f"{field.name}={stated[field.name]!r}, the preset's "
                    f"model has {got!r}"
                )

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        import jax

        window = super().measure(seconds, on_start, on_stop, span)
        rows = [{k: float(m[k]) for k in COUNTERS}
                for m in jax.device_get(self._reported)]
        for row, extra in zip(window["log_rows"], rows, strict=True):
            row.update(extra)
        low, high = OFFDIAG_RANGE
        window["checks"]["mhc_sinkhorn_converged"] = bool(rows) and all(
            r["mhc_sinkhorn_residual"] <= SINKHORN_RESIDUAL_MAX for r in rows
        )
        window["checks"]["mhc_res_mixed"] = bool(rows) and all(
            low < r["mhc_res_offdiag_mass"] < high for r in rows
        )
        window["mhc_res_offdiag_mass"] = sum(
            r["mhc_res_offdiag_mass"] for r in rows) / max(len(rows), 1)
        window["mhc_sinkhorn_residual"] = max(
            (r["mhc_sinkhorn_residual"] for r in rows), default=None)
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

        from perfbench.reference import xing as reference

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

        from perfbench.reference import xing as reference

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
