"""A2C: synchronous advantage actor-critic.

Capability parity: the reference's A2C baseline — N synchronous actors,
GAE(lambda) advantages, combined policy + value + entropy loss, and
synchronous gradient averaging across actors (BASELINE.json:5,7;
SURVEY.md §2.1 "A2C trainer", §3.1 call stack). Its scaling metric is
efficiency from 8 to 256 actors (BASELINE.json:2).

TPU-first design: actors are vectorized envs sharded over the ``data``
mesh axis; one iteration (rollout scan + GAE + update with
``lax.pmean`` gradient averaging — the MirroredStrategy/NCCL analog)
is a single jitted ``shard_map`` program.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import optax

from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
from actor_critic_algs_on_tensorflow_tpu.algos import common
from actor_critic_algs_on_tensorflow_tpu.models import DiscreteActorCritic
from actor_critic_algs_on_tensorflow_tpu.ops import (
    Categorical,
    gae_advantages,
    policy_gradient_loss,
    value_loss,
)
from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
    DATA_AXIS,
    device_count,
    make_mesh,
    put_by_specs,
)
from actor_critic_algs_on_tensorflow_tpu.utils import prng


@dataclasses.dataclass(frozen=True)
class A2CConfig:
    env: str = "CartPole-v1"
    num_envs: int = 16              # global, across all devices
    rollout_length: int = 16
    total_env_steps: int = 500_000
    frame_stack: int = 0
    torso: str = "mlp"
    hidden_sizes: Tuple[int, ...] = (64, 64)
    lr: float = 7e-4
    lr_decay: bool = True
    gamma: float = 0.99
    gae_lambda: float = 0.95
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    max_grad_norm: float = 0.5
    normalize_adv: bool = False
    # Recurrent (LSTM) policy (models.RecurrentActorCritic); A2C's
    # whole-batch update replays the full [T, B] sequence, so no
    # minibatch constraints apply — but time_limit_bootstrap must be
    # off (V(final_obs) would need the per-step carry).
    recurrent: bool = False
    lstm_size: int = 128
    # Bootstrap truncated (time-limit) episodes from V(final_obs)
    # instead of treating them as terminal (see ops.gae). Costs an
    # extra [T, B, obs] buffer + value forward; disable for image envs.
    time_limit_bootstrap: bool = True
    compute_dtype: str = "float32"  # "bfloat16" runs torsos on the MXU in bf16
    # In-graph all-finite guard over loss/grads/params folded into the
    # iteration (one fused reduction, surfaced as ``health_finite``) —
    # the same guard the IMPALA learner carries; ``common.run_loop``'s
    # sentinel reads it and rolls back to a last-good snapshot.
    numerics_guards: bool = True
    seed: int = 0
    num_devices: int = 0            # 0 = all visible devices


def make_a2c(cfg: A2CConfig) -> common.IterationFns:
    """Build jitted ``init`` and fused ``iteration`` for A2C."""
    mesh = make_mesh(cfg.num_devices or None)
    n_dev = device_count(mesh)
    if cfg.num_envs % n_dev:
        raise ValueError(
            f"num_envs={cfg.num_envs} not divisible by {n_dev} devices"
        )
    local_envs = cfg.num_envs // n_dev
    # One env instance at per-device width (used inside shard_map), one
    # at global width (used for init/reset on the host).
    common.check_host_env_topology(cfg.env, n_dev)
    env, env_params = envs_lib.make(
        cfg.env, num_envs=local_envs, frame_stack=cfg.frame_stack
    )
    genv, _ = envs_lib.make(
        cfg.env, num_envs=cfg.num_envs, frame_stack=cfg.frame_stack
    )
    action_space = env.action_space(env_params)
    if cfg.recurrent:
        if cfg.time_limit_bootstrap:
            raise ValueError(
                "recurrent A2C requires time_limit_bootstrap=False "
                "(V(final_obs) would need the per-step carry)"
            )
        model, seq_dist_value = common.make_recurrent_policy_head(
            action_space,
            torso=cfg.torso,
            hidden_sizes=cfg.hidden_sizes,
            lstm_size=cfg.lstm_size,
            compute_dtype=cfg.compute_dtype,
        )
    else:
        model = DiscreteActorCritic(
            num_actions=action_space.n,
            torso=cfg.torso,
            hidden_sizes=cfg.hidden_sizes,
            dtype=jnp.dtype(cfg.compute_dtype),
        )

    num_iters = max(1, cfg.total_env_steps // (cfg.num_envs * cfg.rollout_length))
    if cfg.lr_decay:
        schedule = optax.linear_schedule(cfg.lr, 0.0, num_iters)
    else:
        schedule = cfg.lr
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adam(schedule, eps=1e-5),
    )

    def policy_fn(params, obs, key):
        logits, value = model.apply(params, obs)
        dist = Categorical(logits)
        action = dist.sample(key)
        return action, dist.log_prob(action), value

    def init(key: jax.Array) -> common.OnPolicyState:
        k_env, k_model = jax.random.split(key)
        env_state, obs = genv.reset(k_env, env_params)
        if cfg.recurrent:
            params = model.init(
                k_model, obs[:1][None], jnp.zeros((1, 1)),
                model.initialize_carry(1),
            )
            carry = {
                "core": model.initialize_carry(cfg.num_envs),
                "prev_done": jnp.zeros((cfg.num_envs,), jnp.float32),
            }
        else:
            params = model.init(k_model, obs[:1])
            carry = None
        state = common.OnPolicyState(
            params=params,
            opt_state=tx.init(params),
            env_state=env_state,
            obs=obs,
            key=key,
            step=jnp.zeros((), jnp.int32),
            carry=carry,
        )
        return put_by_specs(state, common.state_specs(state), mesh)

    def local_iteration(state: common.OnPolicyState):
        dev = jax.lax.axis_index(DATA_AXIS)
        it_key = prng.fold(state.key, state.step, dev)

        env_state, obs, traj, ep_info = common.collect_rollout(
            env, env_params, policy_fn,
            state.params, state.env_state, state.obs, it_key,
            cfg.rollout_length,
            keep_final_obs=cfg.time_limit_bootstrap,
        )
        _, last_value = model.apply(state.params, obs)
        if cfg.time_limit_bootstrap:
            _, truncation_values = model.apply(
                state.params, ep_info["final_obs"]
            )
        else:
            truncation_values = None
        advantages, returns = gae_advantages(
            traj.rewards, traj.values, traj.dones, last_value,
            gamma=cfg.gamma, lam=cfg.gae_lambda,
            terminations=ep_info["terminated"],
            truncation_values=truncation_values,
        )
        if cfg.normalize_adv:
            advantages = common.global_normalize_advantages(advantages)

        def loss_fn(params):
            logits, values = model.apply(params, traj.obs)
            dist = Categorical(logits)
            pg = policy_gradient_loss(dist.log_prob(traj.actions), advantages)
            vf = value_loss(values, returns)
            ent = dist.entropy().mean()
            total = pg + cfg.vf_coef * vf - cfg.ent_coef * ent
            return total, (pg, vf, ent)

        (loss, (pg, vf, ent)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        # Synchronous multi-actor gradient averaging over ICI — the
        # tf.distribute.MirroredStrategy/NCCL analog (BASELINE.json:5).
        grads = jax.lax.pmean(grads, DATA_AXIS)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)

        metrics = jax.lax.pmean(
            {
                "loss": loss, "policy_loss": pg, "value_loss": vf,
                "entropy": ent,
                **common.guard_metrics(
                    cfg.numerics_guards, (loss, grads, params)
                ),
            },
            DATA_AXIS,
        )
        metrics.update(common.episode_metrics(ep_info))

        new_state = common.OnPolicyState(
            params=params,
            opt_state=opt_state,
            env_state=env_state,
            obs=obs,
            key=state.key,
            step=state.step + 1,
        )
        return new_state, metrics

    def local_iteration_recurrent(state: common.OnPolicyState):
        """Recurrent A2C iteration: the whole-batch update replays the
        full [T, B] sequence from the rollout-entry carry."""
        dev = jax.lax.axis_index(DATA_AXIS)
        it_key = prng.fold(state.key, state.step, dev)

        carry0 = state.carry
        env_state, obs, carry1, traj, ep_info, _ = (
            common.collect_rollout_recurrent(
                env, env_params, seq_dist_value, state.params,
                state.env_state, state.obs, carry0, it_key,
                cfg.rollout_length,
            )
        )
        _, last_value_tb, _, _ = seq_dist_value(
            state.params, obs[None], carry1["prev_done"][None],
            carry1["core"],
        )
        advantages, returns = gae_advantages(
            traj.rewards, traj.values, traj.dones, last_value_tb[0],
            gamma=cfg.gamma, lam=cfg.gae_lambda,
            terminations=ep_info["terminated"],
            truncation_values=None,
        )
        if cfg.normalize_adv:
            advantages = common.global_normalize_advantages(advantages)
        resets_tb = common.replay_resets(carry0["prev_done"], traj.dones)

        def loss_fn(params):
            dist, values, _, _ = seq_dist_value(
                params, traj.obs, resets_tb, carry0["core"]
            )
            pg = policy_gradient_loss(
                dist.log_prob(traj.actions), advantages
            )
            vf = value_loss(values, returns)
            ent = dist.entropy().mean()
            total = pg + cfg.vf_coef * vf - cfg.ent_coef * ent
            return total, (pg, vf, ent)

        (loss, (pg, vf, ent)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        grads = jax.lax.pmean(grads, DATA_AXIS)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)

        metrics = jax.lax.pmean(
            {
                "loss": loss, "policy_loss": pg, "value_loss": vf,
                "entropy": ent,
                **common.guard_metrics(
                    cfg.numerics_guards, (loss, grads, params)
                ),
            },
            DATA_AXIS,
        )
        metrics.update(common.episode_metrics(ep_info))

        return common.OnPolicyState(
            params=params,
            opt_state=opt_state,
            env_state=env_state,
            obs=obs,
            key=state.key,
            step=state.step + 1,
            carry=carry1,
        ), metrics

    example = jax.eval_shape(init, jax.random.PRNGKey(0))
    iteration = common.build_data_parallel_iteration(
        local_iteration_recurrent if cfg.recurrent else local_iteration,
        example, mesh,
    )
    return common.IterationFns(
        init=init,
        iteration=iteration,
        mesh=mesh,
        steps_per_iteration=cfg.num_envs * cfg.rollout_length,
    )
