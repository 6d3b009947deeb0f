"""Family ``impala``: ``run_impala`` — an in-process actor thread and a
learner thread on the same chip, a bounded queue between them.

The run loop is the program's own; the benchmark drives it through
``log_fn`` (a reading of the benchmark's clock at every log row, each
of which waits for that learner step as the program's own log line
does) and ends it through ``stop_event``.

Throughput is the env steps of one row interval over the MEDIAN of the
window's row intervals (``harness/rows.py::steady_rate``): two threads and the host's
shared cores lose one pause of ~76 ms in some runs (PERF.md section 7),
0.45 % of a 17 s window and as much as a bound allows a cell's runs to
spread, and a median over the whole window does not move with it. What
the median leaves out stays in sight: ``pause_share`` of the same rows
is the per-layer metric ``async_pause_share``, and the whole-window
rate goes into ``run_trace<n>.json``.
"""

from __future__ import annotations

import math
import statistics
import threading
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

        from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
            make_impala,
        )

        cfg, expect = self.cfg, self.cell.traffic["expect"]
        self.steps_per_batch = (
            cfg.batch_trajectories * cfg.envs_per_actor * cfg.rollout_length
        )
        if self.steps_per_batch != expect["env_steps_per_learner_batch"]:
            raise SpecError(
                f"the traffic file states "
                f"{expect['env_steps_per_learner_batch']} env steps a "
                f"learner batch, the program consumes {self.steps_per_batch}"
            )
        self.programs = make_impala(cfg)
        self.num_actions = int(self.programs.num_actions)
        # Weights made on the device from --seed by the program's own
        # init, as one jitted call.
        self.init = jax.jit(self.programs.init)
        self.state0 = self.init(jax.random.PRNGKey(self.seed))
        return {}

    def verify(self) -> dict:
        """After the window and the reading of the memory peak: one
        seeded ``[T, B]`` batch through the program's own donated
        ``learner_step`` from a fresh state — its loss terms from the
        step's metrics, its gradient read back from the Adam state the
        step left (``checks.adam_first_step_grads``) — against the plain
        float32 reference."""
        import jax
        import jax.numpy as jnp

        from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
            ActorTrajectory,
        )
        from perfbench.reference import impala_loss

        cfg, progs = self.cfg, self.programs
        # (the run loop donated the state it was given)
        self.state0 = self.init(jax.random.PRNGKey(self.seed))
        T, B = cfg.rollout_length, cfg.batch_trajectories * cfg.envs_per_actor
        chunks = int(self.cell.config["reference_check"]["env_chunks"])
        if B % chunks:
            chunks = 1
        obs_shape = (84, 84, cfg.frame_stack)
        batch = jax.jit(
            lambda k: seeded_batch(k, T, B, obs_shape, self.num_actions)
        )(jax.random.PRNGKey(self.seed + 1))
        hp = {k: getattr(cfg, k) for k in
              ("gamma", "vtrace_lam", "rho_bar", "c_bar", "vf_coef",
               "ent_coef")}

        def reference(params, b):
            def split(x, axis):
                parts = x.reshape(
                    x.shape[:axis] + (chunks, x.shape[axis] // chunks)
                    + x.shape[axis + 1:]
                )
                return jnp.moveaxis(parts, axis, 0)

            xs = {k: split(v, 0 if k == "last_obs" else 1)
                  for k, v in b.items()}

            def one(chunk):
                (loss, parts), grads = jax.value_and_grad(
                    impala_loss.loss, has_aux=True
                )(params, chunk, hp)
                parts = {k: parts[k] for k in
                         ("policy_loss", "value_loss", "entropy")}
                return loss, parts, grads

            # Every term is a mean over samples and the chunks are of
            # one size, so the mean over chunks is the batch's value.
            return jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0), jax.lax.map(one, xs)
            )

        with jax.default_matmul_precision("highest"):
            loss_r, parts_r, grads_r = jax.jit(reference)(
                self.state0.params, batch
            )
        jax.block_until_ready(grads_r)
        state1, metrics = progs.learner_step_donated(
            self.state0, ActorTrajectory(**batch)
        )
        m = {k: float(v) for k, v in jax.device_get(metrics).items()}
        grads_s = checks.adam_first_step_grads(
            state1.opt_state, m["grad_norm"], cfg.max_grad_norm
        )
        self.report = checks.compare_loss_and_grads(
            m["loss"], loss_r, checks.loss_scale(parts_r, hp), grads_s, grads_r
        )
        self.report["count_after_one_step"] = checks.optimizer_count(
            state1.opt_state
        )
        return {"reference_learner_step": bool(
            self.report["ok"] and self.report["count_after_one_step"] == 1
        )}

    # ---- the window ----------------------------------------------------

    def measure(self, seconds: float, on_start, on_stop, span) -> dict:
        import jax

        from actor_critic_algs_on_tensorflow_tpu.algos.impala import run_impala

        cfg, traffic = self.cfg, self.cell.traffic
        warm_steps = int(traffic["warmup_learner_steps"])
        rows, state = [], {"t0": None, "stopped": False}
        stop = threading.Event()

        def log_fn(env_steps, m):
            now = time.perf_counter()
            if state["stopped"]:
                return
            if state["t0"] is None:
                if env_steps >= warm_steps * self.steps_per_batch:
                    on_start()
                    state["t0"] = time.perf_counter()
                    rows.append((state["t0"], env_steps, dict(m)))
                return
            if now - state["t0"] > seconds and len(rows) >= 2:
                state["stopped"] = True
                on_stop()
                stop.set()
                return
            rows.append((now, env_steps, dict(m)))

        final, _ = run_impala(
            cfg, log_interval=int(traffic["log_interval"]), log_fn=log_fn,
            stop_event=stop, initial_state=self.state0,
            programs=self.programs,
        )
        if not state["stopped"]:
            raise RuntimeError("run_impala ended before the window did")
        (t_first, steps_first, m_first), (t_last, steps_last, m_last) = (
            rows[0], rows[-1]
        )
        elapsed = t_last - t_first
        times = [t for t, _, _ in rows]
        intervals = [b - a for a, b in zip(times, times[1:])]
        batches = (steps_last - steps_first) // self.steps_per_batch
        inside = [m for _, _, m in rows[1:]]
        failed = sum(
            not math.isfinite(m["loss"]) or m.get("health_finite", 1.0) != 1.0
            for m in inside
        )
        for key in ("health_guard_trips", "actor_restarts"):
            failed += int(m_last.get(key, 0) - m_first.get(key, 0))
        count = checks.optimizer_count(final.opt_state)
        step = int(jax.device_get(final.step))
        per_batch = traffic["expect"]["optimizer_updates_per_learner_batch"]
        interval = int(traffic["log_interval"])
        logged = steps_last // self.steps_per_batch
        B = cfg.batch_trajectories * cfg.envs_per_actor
        T = cfg.rollout_length
        chips = int(self.programs.mesh.devices.size)
        return {
            "attempted": batches,
            "failed": failed,
            "elapsed_s": elapsed,
            "readings": len(rows),
            # For whoever reads a slow run: one stalled interval shows
            # as a max far from the median (run_trace<n>.json), and in
            # the whole-window rate under the reported one.
            "row_interval_ms": {
                "median": 1e3 * statistics.median(intervals),
                "min": 1e3 * min(intervals),
                "max": 1e3 * max(intervals),
            },
            "whole_window_env_steps_per_s_per_chip":
                (steps_last - steps_first) / elapsed / chips,
            "pause_share_pct": pause_share(times),
            "end_to_end": {
                "async_env_steps_per_s_per_chip": steady_rate(
                    times, interval * self.steps_per_batch
                ) / chips,
            },
            "checks": {
                # One optimizer update per learner batch over the whole
                # run (the state is donated inside the loop, so it is
                # read when the loop returns): the count equals the
                # learner's steps, and the log rows saw them all but
                # the tail after the last row.
                "optimizer_updates": checks.updates_consistent(
                    0, count, step, per_batch
                ) and logged <= step <= logged + 2 * interval,
                "env_steps": all(
                    b[1] - a[1] == interval * self.steps_per_batch
                    for a, b in zip(rows, rows[1:])
                ),
                "readings": len(rows) >= 3,
            },
            # What the chip does in one execution of each program: the
            # learner's update with its bootstrap value, the actor's
            # rollout.
            "work_per_execution": {
                "^jit_local_learner_step": {
                    "forward_samples": B // chips, "forward_calls": 1,
                    "train_samples": B // chips * T, "train_calls": 1,
                },
                "^jit_actor_rollout": {
                    "forward_samples": cfg.envs_per_actor * T,
                    "forward_calls": T,
                },
            },
            "log_rows": inside,
            "log_window_s": elapsed,
            "row_times_s": times,
        }

    def close(self) -> None:
        self.state0 = self.programs = None


def seeded_batch(key, T: int, B: int, obs_shape, num_actions: int) -> dict:
    """One learner batch from the seed, in ``ActorTrajectory``'s
    fields; the behaviour log-probs are scattered around the uniform
    policy's so that the importance ratios straddle the clip at 1."""
    import jax
    import jax.numpy as jnp

    k_common, k_last = jax.random.split(key)
    b = checks.seeded_rollout(k_common, T, B, obs_shape, num_actions)
    b["behaviour_log_probs"] = (
        b.pop("uniform_log_prob") + 0.3 * b.pop("log_prob_noise")
    )
    b["last_obs"] = jax.random.bits(k_last, (B,) + tuple(obs_shape), jnp.uint8)
    return b
