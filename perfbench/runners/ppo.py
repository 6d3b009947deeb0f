"""Family ``ppo``: the fused on-policy iteration of ``make_ppo``.

One process, one thread: the host dispatches whole iterations (rollout
scan + GAE + every epoch and minibatch, one donated ``jit(shard_map)``
program over the ``data`` mesh) and waits for them.

Throughput is the env steps of one iteration over the MEDIAN time
between two iterations' ends (``harness/rows.py::steady_rate``), not
steps over elapsed: the machine's host freezes for seconds now and
then (one run in ~60 lost 6.5 s of its window, PERF.md section 7), and
one such run among a set's six is a quarter of its loss in that set's
spread. The median is what a long run sustains; what it leaves out —
the first, unoverlapped dispatch and any pause — is the per-layer
``pause_share`` and the whole-window rate in ``run_trace<n>.json``.
"""

from __future__ import annotations

import math
import time

from perfbench.harness import checks, program
from perfbench.harness.rows import pause_share, steady_rate
from perfbench.harness.spec import SpecError


class Runner:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = seed
        self.cfg = program.build_config(cell, seed)
        self.report: dict = {}
        self.num_actions = None

    # ---- set-up --------------------------------------------------------

    def setup(self) -> dict:
        import jax

        from jax.sharding import NamedSharding, PartitionSpec

        from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
        from actor_critic_algs_on_tensorflow_tpu.algos import common
        from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

        cfg, expect = self.cfg, self.cell.traffic["expect"]
        self.fns = make_ppo(cfg)
        if self.fns.steps_per_iteration != expect["env_steps_per_iteration"]:
            raise SpecError(
                f"the traffic file states "
                f"{expect['env_steps_per_iteration']} env steps an "
                f"iteration, the program collects "
                f"{self.fns.steps_per_iteration}"
            )
        # Weights and env state are made on the device from --seed, by
        # the program's own init, as one jitted call.
        # (init places its leaves with device_put, which a jit does not
        # carry to its outputs; the placement is stated again here from
        # the program's own state_specs.)
        key = jax.random.PRNGKey(self.seed)
        placement = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.fns.mesh, spec),
            common.state_specs(jax.eval_shape(self.fns.init, key)),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        self.state = jax.jit(self.fns.init, out_shardings=placement)(key)
        env, env_params = envs_lib.make(
            cfg.env, num_envs=1, frame_stack=cfg.frame_stack
        )
        self.action_space = env.action_space(env_params)
        self.num_actions = int(self.action_space.n)
        out = {
            "placement": self.fns.mesh.devices.size == self.cell.chips
            and checks.placement_ok(self.state, self.fns.mesh.devices.flat),
        }
        # Warm-up: the one program this cell runs, once.
        self.state, metrics = self.fns.iteration(self.state)
        jax.block_until_ready(metrics)
        return out

    def verify(self) -> dict:
        """After the window and the reading of the memory peak: loss
        and gradients on one seeded batch at the published widths
        against the plain float32 reference, on the parameters the
        window left. The fused iteration has no entry that takes a
        batch, so the system's side is composed here from the program's
        own model and ``ops/`` as ``algos/ppo.py::batch_grads`` composes
        them: this check guards the model, its precision and ``ops/``,
        NOT the fused iteration, and is named so. What holds the fused
        iteration itself is in ``measure``: its optimizer count, its env
        steps and ``fused_loss_terms``."""
        import jax
        import jax.numpy as jnp

        from actor_critic_algs_on_tensorflow_tpu.algos import common
        from actor_critic_algs_on_tensorflow_tpu.ops import (
            clipped_value_loss,
            gae_advantages,
            ppo_clip_loss,
        )
        from perfbench.reference import ppo_loss

        cfg = self.cfg
        shape = self.cell.config["reference_check"]
        T, B = int(shape["rollout"]), int(shape["envs"])
        _, dist_and_value = common.make_policy_head(
            self.action_space, torso=cfg.torso,
            hidden_sizes=cfg.hidden_sizes, compute_dtype=cfg.compute_dtype,
        )
        one = jax.devices()[0]
        params = jax.device_put(self.state.params, one)
        obs_shape = self.state.obs.shape[1:]
        batch = jax.jit(
            lambda k: seeded_batch(k, T, B, obs_shape, self.num_actions)
        )(jax.device_put(jax.random.PRNGKey(self.seed + 1), one))

        def flat(x):
            return x.reshape((T * B,) + x.shape[2:])

        def system(params, b):
            adv, ret = gae_advantages(
                b["rewards"], b["old_values"], b["dones"], b["last_value"],
                gamma=cfg.gamma, lam=cfg.gae_lambda,
            )

            def loss_fn(p):
                dist, values = dist_and_value(p, flat(b["obs"]))
                a = common.global_normalize_advantages(
                    flat(adv), axis_name=None
                )
                stats = ppo_clip_loss(
                    dist.log_prob(flat(b["actions"])),
                    flat(b["old_log_probs"]), a, clip_eps=cfg.clip_eps,
                )
                vf = clipped_value_loss(
                    values, flat(b["old_values"]), flat(ret),
                    clip_eps=cfg.clip_eps,
                )
                ent = dist.entropy().mean()
                return (stats.policy_loss + cfg.vf_coef * vf
                        - cfg.ent_coef * ent)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            return adv, loss, grads

        hp = {"clip_eps": cfg.clip_eps, "vf_coef": cfg.vf_coef,
              "ent_coef": cfg.ent_coef}

        def reference(params, b):
            adv, ret = ppo_loss.gae(
                b["rewards"], b["old_values"], b["dones"], b["last_value"],
                cfg.gamma, cfg.gae_lambda,
            )
            fb = {k: flat(b[k]) for k in
                  ("obs", "actions", "old_log_probs", "old_values")}
            fb["advantages"], fb["returns"] = flat(adv), flat(ret)
            (loss, parts), grads = jax.value_and_grad(
                ppo_loss.loss, has_aux=True
            )(params, fb, hp)
            return adv, loss, parts, grads

        if not (cfg.vf_clip and cfg.normalize_adv):
            raise SpecError("the PPO reference covers vf_clip and "
                            "normalize_adv as the configuration states them")
        adv_s, loss_s, grads_s = jax.jit(system)(params, batch)
        with jax.default_matmul_precision("highest"):
            adv_r, loss_r, parts_r, grads_r = jax.jit(reference)(params, batch)
        adv_err = float(
            jnp.max(jnp.abs(adv_s - adv_r)) / jnp.max(jnp.abs(adv_r))
        )
        self.report = checks.compare_loss_and_grads(
            loss_s, loss_r, checks.loss_scale(parts_r, hp), grads_s, grads_r
        )
        self.report["advantage_err"] = adv_err
        return {"reference_model_and_ops": bool(
            self.report["ok"] and adv_err <= checks.ADVANTAGE_RTOL
        )}

    # ---- the window ----------------------------------------------------

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        """Whole iterations until the next would overrun ``seconds``,
        one dispatched ahead of the one being waited for, as the
        program's own run loop dispatches ahead of its log line."""
        import jax

        cfg, expect = self.cfg, self.cell.traffic["expect"]
        iteration, state = self.fns.iteration, self.state
        count0 = checks.optimizer_count(state.opt_state)
        step0 = int(jax.device_get(state.step))
        on_start()
        t0 = time.perf_counter()
        pending, kept, done_t = [], [], []
        dispatched = 0
        while True:
            per_iter = (done_t[-1] - t0) / len(done_t) if done_t else 0.0
            if len(pending) < 2 and (dispatched + 1) * per_iter <= seconds:
                with span("perfbench:dispatch"):
                    state, metrics = iteration(state)
                pending.append(metrics)
                dispatched += 1
                continue
            if not pending:
                break
            with span("perfbench:wait"):
                jax.block_until_ready(pending[0])
            done_t.append(time.perf_counter())
            kept.append(pending.pop(0))
        elapsed = done_t[-1] - t0
        on_stop()
        ends = [t0] + done_t  # the window opens where an iteration ends
        self.state = state
        kept = jax.device_get(kept)
        losses = [float(m["loss"]) for m in kept]
        hp = {"vf_coef": cfg.vf_coef, "ent_coef": cfg.ent_coef}
        chips = int(self.fns.mesh.devices.size)
        per_it = self.fns.steps_per_iteration
        updates = cfg.num_epochs * cfg.num_minibatches
        envs, T = cfg.num_envs, cfg.rollout_length
        return {
            "attempted": dispatched,
            "failed": sum(not math.isfinite(x) for x in losses),
            "elapsed_s": elapsed,
            "iterations": dispatched,
            "whole_window_env_steps_per_s_per_chip":
                dispatched * per_it / elapsed / chips,
            "pause_share_pct": pause_share(ends),
            "end_to_end": {
                "env_steps_per_s_per_chip":
                    steady_rate(ends, per_it) / chips,
            },
            "row_times_s": ends,
            "checks": {
                "optimizer_updates": checks.updates_consistent(
                    count0, checks.optimizer_count(state.opt_state),
                    dispatched, expect["optimizer_updates_per_iteration"],
                ),
                "program_runs_traffic_schedule":
                    updates == expect["optimizer_updates_per_iteration"],
                "env_steps": int(jax.device_get(state.step)) - step0
                    == dispatched,
                # The fused iteration's own reported loss, every
                # iteration of the window.
                "fused_loss_terms": all(
                    checks.loss_terms_consistent(m, hp) for m in kept
                ),
            },
            # What one chip does in one execution of the program.
            "work_per_execution": {"^jit_local_iteration": {
                "forward_samples": envs // chips * (T + 1),
                "forward_calls": T + 1,
                "train_samples": cfg.num_epochs * envs // chips * T,
                "train_calls": updates,
            }},
        }

    def close(self) -> None:
        self.state = None


def seeded_batch(key, T: int, B: int, obs_shape, num_actions: int) -> dict:
    """One rollout's worth of data from the seed; the old log-probs are
    scattered around the uniform policy's so that a share of the ratios
    is clipped."""
    import jax

    k_common, k_values, k_last = jax.random.split(key, 3)
    b = checks.seeded_rollout(k_common, T, B, obs_shape, num_actions)
    b["old_log_probs"] = (
        b.pop("uniform_log_prob") + 0.15 * b.pop("log_prob_noise")
    )
    b["old_values"] = 0.5 * jax.random.normal(k_values, (T, B))
    b["last_value"] = 0.5 * jax.random.normal(k_last, (B,))
    return b
