"""Family ``ppo_seq``: ``make_ppo``'s recurrent path with a sequence
core whose carry the model owns (``torso="qwen3_next"``): token-level
PPO, one episode one sequence.

The window is the ``ppo`` family's, literally — its dispatch-ahead loop,
its ``steady_rate``, its checks: this runner is that one with another
set-up, the program's per-iteration counters kept beside the window,
and another ``verify``. The ``ppo`` runner's ``verify`` flattens
``[T, B]`` and knows no carry; this one reads the program's own two
halves (``IterationFns.collect`` and ``.block_grads``, built from the
closures the fused iteration traces) at the timed sizes against the
plain reference, so it guards what the timed path computes and not a
re-composition of it.

What decides ``correct`` here, beside the ``ppo`` window's checks
(optimizer count, env steps, ``fused_loss_terms``):

* ``reference_rollout``: ``collect`` once at the cell's envs x rollout
  — the timed rollout, step by step through DeltaNet state, convolution
  tail, key/value cache and position — against the reference's full
  forward over the collected tokens, in blocks of a few envs: the
  stored log-probs at the taken actions and the stored values
  (``judge_rollout``).
* ``reference_block_grads``: ``block_grads`` on one timed minibatch
  (the first block of envs, whole sequences; ``check_block``) against
  the reference's loss and gradients (``checks.compare_loss_and_grads``'s
  numbers and limits, accumulated leaf by leaf:
  ``compare_loss_and_grads`` below).
* ``moe_dispatch_dropless``: the expert layer's overflow counter 0 in
  every iteration of the window. An iteration where it is not also
  counts as ``failed``.

Which reference, and why. The configuration states its precision:
bfloat16 inputs to every matrix product of a weight or of attention,
float32 sums, and float32 norms, router, DeltaNet gates, state and
recurrence, softmax and heads. The program is held to the reference AT
THAT PRECISION (``reference.forward(..., products=bfloat16)``, written
from the statement and not from the program). Against the float32
reference the products' rounding alone moves the median log-prob by
0.017-0.021 and the 99th percentile by 0.17-0.18 (13 seeds on the chip,
PERF.md section 6, PR 27): the router's top-10 of 512 is discontinuous,
a token whose tenth and eleventh expert trade places gains or loses a
whole expert term of weight ~0.1, and state and cache carry that on.
Everything a step below the stated precision then reads only 1.45 x
the sound program, which is no room for a limit. Held to the stated
precision, the sound program reads a quarter of that and every step
below reads 2.1 to 6 times the program (the readings are beside the
limits below). The float32 reading is still taken and reported
(``rollout["against_float32"]``), with no limit.

The steps below the stated precision (``CONTROLS``; the reference
computes them, ``tools/precision_controls.py`` sends each through this
module's own ``judge_rollout`` and ``compare_loss_and_grads`` at the
timed sizes): the DeltaNet's state and recurrence in bfloat16, the
router's softmax and top-k in bfloat16, the norms in bfloat16, and
everything, parameters included, in bfloat16. Each has to come out as
not correct by at least one limit.

Limits, each between the largest the program read over 15 seeds on the
chip and what the steps below read (my chip runs, PR 27: the controls at
128 envs, seed 2147400001, through ``tools/precision_controls.py``; log-
prob / value; PERF.md section 6 has every number). Each of the four
controls comes out as not correct by the two percentiles, the
all-bfloat16 one by every limit but loss and norm.

* 90th percentile of the absolute error, ``0.02``: program 0.0135-0.0146
  / 0.0118-0.0128; router 0.0329 / 0.0296, state 0.0511 / 0.0465, norms
  0.0596 / 0.0544, all 0.0898 / 0.0819.
* 99th percentile, ``0.085``: program 0.0435-0.0548 / 0.0438-0.0517;
  router 0.1540 / 0.1350, state 0.1637 / 0.1500, norms 0.1869 / 0.1680,
  all 0.2399 / 0.2134.
* the largest median of any one env, ``0.02``: program 0.0072-0.0112 /
  0.0071-0.0112 (the largest of 128 moves with the seed: an env whose
  early token changed its route carries that on); all 0.0452 / 0.0381,
  norms 0.0328 / 0.0352, state 0.0270 / 0.0277, router 0.0239 / 0.0197
  (this limit does not tell the router control apart; the percentiles
  do). It is there for a fault in one env of 128, 0.8 % of the tokens,
  which no percentile of all tokens sees.
  No limit on the median of all tokens (program 0.0050-0.0052 / 0.0042-
  0.0045, the router control 0.0080 / 0.0071: too close), on the root
  mean square (0.0130-0.0148 / 0.0119-0.0136 against 0.0360 / 0.0323,
  but it leans on the tail) or on the largest (0.28-0.64 against
  0.9-1.6: single tokens).
* loss, cosine and norm of the gradient: the harness's own (3 %, 0.995,
  10 %; ``harness/checks.py``). ``block_grads`` read 1 - cosine 2.6e-4
  to 7.5e-4, loss within 0.10 % of its summands, norm within 0.08 %;
  the all-bfloat16 control 1.3e-2, 0.18 %, 0.23 %.
"""

from __future__ import annotations

import dataclasses
import math

from perfbench.harness import checks
from perfbench.harness.spec import SpecError
from perfbench.rules import scope_lowering
from perfbench.runners import ppo as ppo_family

# The scope join lowers a cell's programs by family; this family's one
# program is the `ppo` family's fused iteration, built the same way.
scope_lowering._LOWER.setdefault("ppo_seq", scope_lowering._LOWER["ppo"])

# On the absolute error of log-probs and of values over envs x steps
# (`error_stats`), each limit for both: the 90th and the 99th
# percentile of all tokens, and the largest median of any one env.
ROLLOUT_LIMITS = {"p90": 2.0e-2, "p99": 8.5e-2, "env_p50_max": 2.0e-2}
COUNTERS = ("moe_local_pairs_per_token", "moe_expert_load_max_over_mean",
            "moe_overflow_pairs", "moe_experts_touched_share")
# `reference.forward`'s precision arguments for each step below the
# stated precision, over the stated ones (`Runner.precision`).
CONTROLS = {
    "state_bfloat16": {"lower": ("state",)},
    "router_bfloat16": {"lower": ("router",)},
    "norms_bfloat16": {"lower": ("norms",)},
    "all_bfloat16": {"dtype": "bfloat16", "products": None},
}


class Runner(ppo_family.Runner):
    # the window's counters, for operations/
    moe_pairs_per_token = None
    moe_experts_touched_share = None

    def __init__(self, cell, seed: int):
        try:
            super().__init__(cell, seed)
        except KeyError as e:
            # A checkout from before the configuration: fail as a
            # missing piece does (exit 6), at once.
            raise SpecError(
                f"cell {cell.name!r}: this checkout's program has no "
                f"preset {e} (cli/train.py::PRESETS)"
            )

    def setup(self) -> dict:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from actor_critic_algs_on_tensorflow_tpu.algos import common
        from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

        cfg, expect = self.cfg, self.cell.traffic["expect"]
        self._check_model(cfg)
        self.fns = make_ppo(cfg)
        if self.fns.steps_per_iteration != expect["env_steps_per_iteration"]:
            raise SpecError(
                f"the traffic file states "
                f"{expect['env_steps_per_iteration']} env steps an "
                f"iteration, the program collects "
                f"{self.fns.steps_per_iteration}"
            )
        key = jax.random.PRNGKey(self.seed)
        placement = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.fns.mesh, spec),
            common.state_specs(jax.eval_shape(self.fns.init, key)),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        self.state = jax.jit(self.fns.init, out_shardings=placement)(key)
        self.num_actions = int(cfg.seq_model.vocab_size)
        out = {
            "placement": self.fns.mesh.devices.size == self.cell.chips
            and checks.placement_ok(self.state, self.fns.mesh.devices.flat),
        }
        # The window dispatches through `self.fns.iteration`: keep what
        # each iteration reports, as the handles they are.
        self._reported = []
        iteration = self.fns.iteration

        def reporting(state):
            state, metrics = iteration(state)
            self._reported.append(metrics)
            return state, metrics

        self.fns = self.fns._replace(iteration=reporting)
        # Warm-up: the one program this cell runs, once.
        self.state, metrics = iteration(self.state)
        jax.block_until_ready(metrics)
        return out

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
        if published["num_experts"] != held["router_width"]:
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

    # ---- the window ----------------------------------------------------

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        import jax

        self._reported.clear()
        window = super().measure(seconds, on_start, on_stop, span)
        rows = [
            {k: float(m[k]) for k in COUNTERS + ("loss",)}
            for m in jax.device_get(self._reported)
        ]
        overflowed = sum(r["moe_overflow_pairs"] != 0.0 for r in rows)
        window["failed"] = sum(
            not math.isfinite(r["loss"]) or r["moe_overflow_pairs"] != 0.0
            for r in rows
        )
        window["checks"]["moe_dispatch_dropless"] = (
            len(rows) == window["iterations"] and overflowed == 0
        )
        # One row an iteration with the program's counters, and the
        # benchmark's clock over them (`rules/log_counter.py`).
        ends = window["row_times_s"]
        window["log_rows"] = rows
        window["log_window_s"] = ends[-1] - ends[0]
        def mean(key):
            return sum(r[key] for r in rows) / max(len(rows), 1)

        self.moe_pairs_per_token = mean("moe_local_pairs_per_token")
        self.moe_experts_touched_share = mean("moe_experts_touched_share")
        window["moe_pairs_per_token"] = self.moe_pairs_per_token
        window["moe_experts_touched_share"] = self.moe_experts_touched_share
        return window

    # ---- the reference checks -------------------------------------------

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

    def collected(self):
        """``(params, traj)``: the parameters the window left, on one
        device, and what the timed rollout collects with them. Adam's
        moments are freed: two float32 gradients of the model do not
        fit beside them."""
        import jax

        shape = self.cell.config["reference_check"]
        if (int(shape["rollout"]), int(shape["envs"])) != (
            self.cfg.rollout_length, self.cfg.num_envs
        ):
            raise SpecError("reference_check is not the timed size")
        if not (self.cfg.vf_clip and self.cfg.normalize_adv):
            raise SpecError("the PPO reference covers vf_clip and "
                            "normalize_adv as the configuration states them")
        one = jax.devices()[0]
        traj, _ = self.fns.collect(self.state)
        traj = jax.device_put(traj, one)
        params = jax.device_put(self.state.params, one)
        self.state = None
        self._reported.clear()
        return params, traj

    def precision(self, **other) -> dict:
        """``reference.forward``'s precision arguments: the stated
        precision (the configuration's ``compute_dtype`` for the
        products), with ``other`` over it."""
        import jax.numpy as jnp

        args = {"products": self.cfg.compute_dtype, **other}
        return {k: jnp.dtype(v) if isinstance(v, str) else v
                for k, v in args.items()}

    def reference_outputs(self, params, traj, **precision):
        """The reference's log-probs at the taken actions and its
        values, ``[T, B]`` each on the host, over the collected tokens
        in blocks of a few envs."""
        import jax
        import numpy as np

        from perfbench.reference import qwen3_next as reference

        model = self.cell.config["model"]
        precision = self.precision(**precision)

        # (parameters are arguments, never closed over: 2.5 GB of
        # constants in a lowered program is tens of GB of host memory.)
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

    def check_block(self, traj) -> dict:
        """One timed minibatch: the first block of envs, whole
        sequences, advantages from the reference's GAE over the
        collected rollout. Old log-probs and old values are the
        collected ones moved by 0, +``old_offset`` or -``old_offset``,
        a third of the tokens each, from the seed: ratios of 1, 0.64
        and 1.57 at clip 0.2, so both clipped branches of the policy
        and of the value loss are compared on the chip, and a token
        stands 0.23-0.27 in the logarithm from a clip edge, five
        times the 99th percentile of the program's log-prob error. (Spread
        evenly around the clip, as the `ppo` family's batch is, a token
        within that error of an edge takes or loses its whole gradient
        by the rounding.)"""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import ppo_loss

        cfg, shape = self.cfg, self.cell.config["reference_check"]
        T, B, mb = cfg.rollout_length, cfg.num_envs, int(shape["block_envs"])
        adv, ret = jax.jit(ppo_loss.gae, static_argnums=(4, 5))(
            traj.rewards, traj.values, traj.dones, jnp.zeros((B,)),
            cfg.gamma, cfg.gae_lambda,
        )
        keys = jax.random.split(jax.random.PRNGKey(self.seed))
        moved = [
            float(shape["old_offset"])
            * (jax.random.randint(k, (T, mb), 0, 3) - 1).astype(jnp.float32)
            for k in keys
        ]
        cut = lambda x: x[:, :mb]
        return {
            "obs": cut(traj.obs), "actions": cut(traj.actions),
            "old_log_probs": cut(traj.log_probs) + moved[0],
            "old_values": cut(traj.values) + moved[1],
            "advantages": cut(adv), "returns": cut(ret),
            "resets": jnp.zeros((T, mb)), "core": None,
        }

    def reference_grads(self, params, block, **precision):
        """``((loss, gradients), loss_scale)`` of the reference on
        ``block``. Its gradient holds more than the chip has beside two
        gradient trees at 8,192 tokens: it is taken in equal parts of a
        few envs, advantages whitened over the whole block first, and
        the parts' means are the block's."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import qwen3_next as reference

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

    def close(self) -> None:
        self.state = None
        self._reported.clear()


def errors(got, want):
    """``(log-prob errors, value errors)``, ``[T, B]`` each."""
    import numpy as np

    return tuple(np.asarray(g, np.float64) - np.asarray(w, np.float64)
                 for g, w in zip(got, want))


def error_stats(err) -> dict:
    """Of the absolute error over steps x envs ``[T, B]``: the median,
    the 90th and 99th percentile, the largest, the root mean square,
    and the largest median of any one env."""
    import numpy as np

    a = np.abs(np.asarray(err, np.float64))
    p50, p90, p99 = np.percentile(a, [50, 90, 99])
    return {"p50": float(p50), "p90": float(p90), "p99": float(p99),
            "max": float(a.max()), "rms": float(np.sqrt(np.mean(a * a))),
            "env_p50_max": float(np.median(a, 0).max())}


def judge_rollout(log_prob_err, value_err) -> dict:
    """The numbers of both errors and ``ok``: every one finite and
    within its limit (``ROLLOUT_LIMITS``). The upper percentiles fail
    a fault in a minority of the tokens (a stale cache, a bad last
    chunk); the per-env median fails one in one env of 128, which the
    percentiles of all tokens cannot see."""
    report = {"log_prob": error_stats(log_prob_err),
              "value": error_stats(value_err)}
    report["ok"] = all(
        math.isfinite(report[k]["max"]) and report[k][name] <= limit
        for k in ("log_prob", "value")
        for name, limit in ROLLOUT_LIMITS.items()
    )
    return report


def compare_loss_and_grads(system, reference, scale) -> dict:
    """``checks.compare_loss_and_grads`` — the same three numbers under
    the same three limits — of two ``(loss, gradients)``, with the
    whole-tree dot product and norms accumulated leaf by leaf in
    float64 on the host. That function lays each tree out as one
    float64 vector, 5 GB a tree of 626 M parameters and as much again
    while it is assembled: with both trees that is the 40 GiB host (my
    chip run, PR 27: killed there)."""
    import jax
    import numpy as np

    (sys_loss, sys_grads), (ref_loss, ref_grads) = system, reference
    dot = n_sys = n_ref = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(sys_grads),
                    jax.tree_util.tree_leaves(ref_grads), strict=True):
        a = np.asarray(jax.device_get(a), np.float64).ravel()
        b = np.asarray(jax.device_get(b), np.float64).ravel()
        dot += float(a @ b)
        n_sys += float(a @ a)
        n_ref += float(b @ b)
    n_sys, n_ref = math.sqrt(n_sys), math.sqrt(n_ref)
    cosine = dot / max(n_sys * n_ref, 1e-30)
    loss_err = abs(float(sys_loss) - float(ref_loss)) / max(scale, 1e-30)
    norm_err = abs(n_sys - n_ref) / max(n_ref, 1e-30)
    ok = (
        all(map(math.isfinite, (loss_err, cosine, norm_err)))
        and loss_err <= checks.LOSS_TOL
        and cosine >= checks.GRAD_COSINE_MIN
        and norm_err <= checks.GRAD_NORM_RTOL
    )
    return {"ok": ok, "loss_sys": float(sys_loss),
            "loss_ref": float(ref_loss), "loss_err": loss_err,
            "grad_cosine": cosine, "grad_norm_sys": n_sys,
            "grad_norm_ref": n_ref, "grad_norm_err": norm_err}
