"""PPO: clipped-surrogate proximal policy optimization.

Capability parity: the reference's PPO baseline — vectorized envs,
Nature-CNN encoder on Atari, minibatched multi-epoch updates, and the
headline env-steps/sec/chip workload (BASELINE.json:5,8,2; SURVEY.md
§2.1 "PPO trainer", §3.1 call stack). Discrete (Categorical) and
continuous (diagonal Gaussian) action spaces are both supported, per
the reference's Atari + MuJoCo coverage (BASELINE.json:8-9).

TPU-first design: one iteration — rollout ``lax.scan``, GAE, then the
FULL epoch x minibatch update loop — is a single jitted ``shard_map``
program over the ``data`` mesh axis. Minibatches are drawn from the
device-local shard (standard data-parallel PPO) and gradients are
``lax.pmean``-averaged over ICI every minibatch, so the schedule is
equivalent to large-batch PPO with num_envs spread over devices.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
from actor_critic_algs_on_tensorflow_tpu.algos import common
from actor_critic_algs_on_tensorflow_tpu.data.rollout import (
    Trajectory,
    env_block_starts,
    env_blocks,
    flatten_time_batch,
    frame_storage_context,
    gather_stacked_obs,
    minibatch_iter_indices,
    take_minibatch,
)
from actor_critic_algs_on_tensorflow_tpu.models import SEQUENCE_CORES
from actor_critic_algs_on_tensorflow_tpu.ops import (
    clipped_value_loss,
    gae_advantages,
    ppo_clip_loss,
    rms_init,
    rms_normalize,
    rms_update,
    value_loss,
)
from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
    DATA_AXIS,
    device_count,
    make_mesh,
    put_by_specs,
    shard_batch_specs,
    shard_map,
)
from actor_critic_algs_on_tensorflow_tpu.utils import prng, profiling


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    env: str = "CartPole-v1"
    num_envs: int = 8               # global, across all devices
    rollout_length: int = 128
    total_env_steps: int = 500_000
    frame_stack: int = 0
    torso: str = "mlp"              # "mlp" | "nature_cnn" | "qwen3_next"
    hidden_sizes: Tuple[int, ...] = (64, 64)
    lr: float = 2.5e-4
    lr_decay: bool = True
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    vf_clip: bool = True
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    max_grad_norm: float = 0.5
    num_epochs: int = 4
    num_minibatches: int = 4
    # Minibatch composition for num_minibatches > 1:
    #   "full" — classic PPO: random permutation of the flattened
    #            [T*B] batch each epoch. The gather+relayout it implies
    #            is pure HBM data movement (~10 ms of every 41 ms
    #            minibatch at 1024 envs in the r2 device trace).
    #   "env"  — contiguous env-sliced minibatches: each minibatch is
    #            ALL rollout steps of B/num_minibatches CONTIGUOUS
    #            envs; only the block visit order is drawn per epoch
    #            (data.rollout.env_block_starts). No gather, and no
    #            slice either: a slice of the env axis cost 28 % of the
    #            ppo-breakout iteration on the TPU's tiled layouts, so
    #            the rollout is arranged block-major once an iteration
    #            (data.rollout.env_blocks) and a minibatch is an index.
    shuffle: str = "full"
    # Whole-batch epochs only (num_minibatches=1): accumulate the epoch
    # gradient over this many CONTIGUOUS rollout slices instead of one
    # giant forward/backward. No shuffle, no gather, and advantage
    # normalization runs over the full batch first, so the summed
    # gradient is mathematically the whole-batch gradient — but peak
    # activation memory drops by the accumulation factor (lets 2048-env
    # whole-batch schedules fit where the single pass OOMs).
    grad_accum: int = 1
    normalize_adv: bool = True
    # Recurrent (LSTM) policy over the torso features — the partially-
    # observable model family (models.RecurrentActorCritic). Sequence
    # structure must survive minibatching, so recurrent runs require
    # whole-batch epochs (num_minibatches=1) or shuffle="env" (each
    # minibatch is all T steps of contiguous envs); grad_accum,
    # compact_frames, and time_limit_bootstrap are unsupported (the
    # latter would need per-step carries for V(final_obs)).
    recurrent: bool = False
    lstm_size: int = 128
    # A torso of models.SEQUENCE_CORES (recurrent only): that
    # sequence-policy core in place of torso + LSTM. ``seq_model`` is
    # its config (a Qwen3NextConfig, a KimiVLConfig) — every width, the
    # share of experts and vocabulary held here, the dispatch buffer's
    # factor (``--set seq_model.capacity_factor=...``). One episode is
    # one rollout: the env's ``episode_length`` must equal
    # ``rollout_length``, so that every sequence the update replays
    # starts from an empty carry.
    seq_model: Any = None
    # The env's params in place of its defaults (a dataclass of the
    # env's own, e.g. envs.TokenRecallParams; ``--set
    # env_params.delay=...``). None: the defaults.
    env_params: Any = None
    # Running mean/std observation normalization (vector obs only) —
    # the VecNormalize-style statistics live in state.extra, frozen
    # within an iteration so update-time log-probs match collection.
    normalize_obs: bool = False
    time_limit_bootstrap: bool = True
    # Store only the newest frame per rollout step and rebuild stacks
    # during the update (exact; frame_stack-x smaller rollout buffer).
    # Requires frame_stack >= 2 and time_limit_bootstrap=False.
    compact_frames: bool = False
    compute_dtype: str = "float32"  # "bfloat16" runs torsos on the MXU in bf16
    # In-graph all-finite guard over the per-minibatch losses and the
    # final params, folded into the iteration (one fused reduction;
    # surfaced as ``health_finite`` for common.run_loop's sentinel).
    numerics_guards: bool = True
    seed: int = 0
    num_devices: int = 0            # 0 = all visible devices


def make_ppo(cfg: PPOConfig) -> common.IterationFns:
    """Build jitted ``init`` and fused ``iteration`` for PPO."""
    mesh = make_mesh(cfg.num_devices or None)
    n_dev = device_count(mesh)
    if cfg.num_envs % n_dev:
        raise ValueError(
            f"num_envs={cfg.num_envs} not divisible by {n_dev} devices"
        )
    local_envs = cfg.num_envs // n_dev
    local_batch = local_envs * cfg.rollout_length
    if local_batch % cfg.num_minibatches:
        raise ValueError(
            f"local batch {local_batch} not divisible by "
            f"{cfg.num_minibatches} minibatches"
        )
    if cfg.shuffle not in ("full", "env"):
        raise ValueError(f"shuffle must be 'full' or 'env', got {cfg.shuffle!r}")
    env_sliced = cfg.shuffle == "env" and cfg.num_minibatches > 1
    if env_sliced and local_envs % cfg.num_minibatches:
        raise ValueError(
            f"shuffle='env' slices the env axis: local envs {local_envs} "
            f"not divisible by {cfg.num_minibatches} minibatches"
        )
    if cfg.grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {cfg.grad_accum}")
    if cfg.grad_accum > 1:
        if cfg.num_minibatches != 1:
            raise ValueError(
                "grad_accum accumulates whole-batch epochs; it requires "
                f"num_minibatches=1 (got {cfg.num_minibatches})"
            )
        if local_batch % cfg.grad_accum:
            raise ValueError(
                f"local batch {local_batch} not divisible by "
                f"grad_accum={cfg.grad_accum}"
            )
    if cfg.recurrent:
        if cfg.num_minibatches > 1 and cfg.shuffle != "env":
            raise ValueError(
                "recurrent PPO needs sequence-shaped minibatches: use "
                "num_minibatches=1 or shuffle='env' (the flat random "
                "shuffle would scatter each env's trajectory)"
            )
        if cfg.grad_accum > 1:
            raise ValueError(
                "recurrent PPO does not support grad_accum (slices cut "
                "across trajectories)"
            )
        if cfg.compact_frames:
            raise ValueError(
                "recurrent PPO does not support compact_frames"
            )
        if cfg.time_limit_bootstrap:
            raise ValueError(
                "recurrent PPO requires time_limit_bootstrap=False "
                "(V(final_obs) would need the per-step carry)"
            )
    if cfg.torso in SEQUENCE_CORES and not cfg.recurrent:
        raise ValueError(
            f"torso={cfg.torso!r} is a sequence-policy core "
            f"(models.SEQUENCE_CORES): it needs recurrent=True (its "
            f"layers carry state across steps)"
        )
    common.check_host_env_topology(cfg.env, n_dev)
    env, env_params = envs_lib.make(
        cfg.env, num_envs=local_envs, frame_stack=cfg.frame_stack,
        params=cfg.env_params,
    )
    genv, _ = envs_lib.make(
        cfg.env, num_envs=cfg.num_envs, frame_stack=cfg.frame_stack,
        params=cfg.env_params,
    )
    action_space = env.action_space(env_params)
    if cfg.recurrent:
        model, seq_dist_value = common.make_recurrent_policy_head(
            action_space,
            torso=cfg.torso,
            hidden_sizes=cfg.hidden_sizes,
            lstm_size=cfg.lstm_size,
            compute_dtype=cfg.compute_dtype,
            seq_model=cfg.seq_model,
            # a cache row a token: one a step, unless the env says that
            # an episode commits another number (a step that is a pass
            # over a block: envs/block_turns.py)
            cache_len=getattr(
                env_params, "tokens_per_episode", cfg.rollout_length
            ),
        )
        dist_and_value = None
        # A core whose sequence form starts from the empty carry needs
        # no rollout-entry carry in the update.
        replay_carry = not getattr(
            model, "replays_from_empty_carry", False
        )
        episode_length = getattr(env_params, "episode_length", None)
        if not replay_carry and episode_length != cfg.rollout_length:
            raise ValueError(
                f"torso={cfg.torso!r} replays every sequence from an "
                "empty carry (its model's replays_from_empty_carry), so "
                "one episode must be one rollout: the env's "
                f"episode_length ({episode_length}) must equal "
                f"rollout_length ({cfg.rollout_length}); resets inside a "
                "sequence (packed episodes) are not supported"
            )
    else:
        model, dist_and_value = common.make_policy_head(
            action_space,
            torso=cfg.torso,
            hidden_sizes=cfg.hidden_sizes,
            compute_dtype=cfg.compute_dtype,
        )
    prep_obs = common.make_obs_prep(cfg.torso, cfg.compute_dtype)

    num_iters = max(1, cfg.total_env_steps // (cfg.num_envs * cfg.rollout_length))
    if cfg.lr_decay:
        schedule = optax.linear_schedule(
            cfg.lr, 0.0, num_iters * cfg.num_epochs * cfg.num_minibatches
        )
    else:
        schedule = cfg.lr
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adam(schedule, eps=1e-5),
    )

    def policy_fn(params, obs, key):
        dist, value = dist_and_value(params, obs)
        action = dist.sample(key)
        return action, dist.log_prob(action), value

    def init(key: jax.Array) -> common.OnPolicyState:
        k_env, k_model = jax.random.split(key)
        env_state, obs = genv.reset(k_env, env_params)
        if cfg.normalize_obs:
            if obs.ndim != 2:
                raise ValueError(
                    "normalize_obs supports vector observations only"
                )
            extra = rms_init(obs.shape[1:])
        else:
            extra = None
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
            extra=extra,
            carry=carry,
        )
        return put_by_specs(state, common.state_specs(state), mesh)

    if cfg.compact_frames:
        if cfg.frame_stack < 2:
            raise ValueError("compact_frames requires frame_stack >= 2")
        if cfg.time_limit_bootstrap:
            raise ValueError(
                "compact_frames requires time_limit_bootstrap=False "
                "(final_obs would still store full stacks)"
            )
        if cfg.normalize_obs:
            raise ValueError(
                "compact_frames stores single frames, which cannot fold "
                "into full-stack normalize_obs statistics"
            )

    def local_iteration(state: common.OnPolicyState):
        dev = jax.lax.axis_index(DATA_AXIS)
        it_key = prng.fold(state.key, state.step, dev)
        k_roll, k_perm = jax.random.split(it_key)

        # Obs normalization uses the PRE-update statistics everywhere in
        # this iteration (collection AND update, so the PPO ratio's
        # old/new log-probs see identical inputs); this rollout folds
        # into the stats at the end, taking effect next iteration.
        if cfg.normalize_obs:
            rms = state.extra
            norm = lambda o: rms_normalize(o, rms)
        else:
            norm = lambda o: o

        def rollout_policy(params, obs, key):
            return policy_fn(params, norm(obs), key)

        if cfg.compact_frames:
            frame_c = state.obs.shape[-1] // cfg.frame_stack
            store_obs_fn = lambda o: o[..., -frame_c:]
        else:
            store_obs_fn = None
        obs0 = state.obs
        env_state, obs, traj, ep_info = common.collect_rollout(
            env, env_params, rollout_policy,
            state.params, state.env_state, state.obs, k_roll,
            cfg.rollout_length,
            keep_final_obs=cfg.time_limit_bootstrap,
            store_obs_fn=store_obs_fn,
        )
        with jax.named_scope(profiling.ADVANTAGE):
            _, last_value = dist_and_value(state.params, norm(obs))
            if cfg.time_limit_bootstrap:
                _, truncation_values = dist_and_value(
                    state.params, norm(ep_info["final_obs"])
                )
            else:
                truncation_values = None
            advantages, returns = gae_advantages(
                traj.rewards, traj.values, traj.dones, last_value,
                gamma=cfg.gamma, lam=cfg.gae_lambda,
                terminations=ep_info["terminated"],
                truncation_values=truncation_values,
            )

        time_major = {
            "actions": traj.actions,
            "old_log_probs": traj.log_probs,
            "old_values": traj.values,
            "advantages": advantages,
            "returns": returns,
        }
        batch = flatten_time_batch(time_major)
        if cfg.compact_frames:
            extended, resets = frame_storage_context(
                obs0, traj.obs, traj.dones, cfg.frame_stack
            )
            resets_flat = resets.reshape(-1)

            def minibatch_obs(idx):
                return gather_stacked_obs(
                    extended, resets_flat, idx, local_envs, cfg.frame_stack
                )
        else:
            obs_flat = traj.obs.reshape((-1,) + traj.obs.shape[2:])

            def minibatch_obs(idx):
                return jnp.take(obs_flat, idx, axis=0)

        def batch_grads(params, mb, adv):
            """PPO loss value+grad on ``mb`` with advantages ``adv``
            (normalization is the CALLER's job: per-minibatch for the
            minibatch path, whole-batch for accumulation)."""

            with jax.named_scope(profiling.MINIBATCH_PREP):
                obs = prep_obs(mb["obs"])

            def loss_fn(p):
                dist, values = dist_and_value(p, norm(obs))
                stats = ppo_clip_loss(
                    dist.log_prob(mb["actions"]),
                    mb["old_log_probs"],
                    adv,
                    clip_eps=cfg.clip_eps,
                )
                if cfg.vf_clip:
                    vf = clipped_value_loss(
                        values, mb["old_values"], mb["returns"],
                        clip_eps=cfg.clip_eps,
                    )
                else:
                    vf = value_loss(values, mb["returns"])
                ent = dist.entropy().mean()
                total = stats.policy_loss + cfg.vf_coef * vf - cfg.ent_coef * ent
                return total, (stats, vf, ent)

            with jax.named_scope(profiling.LOSS_GRAD):
                (loss, (stats, vf, ent)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
            m = {
                "loss": loss,
                "policy_loss": stats.policy_loss,
                "value_loss": vf,
                "entropy": ent,
                "clip_fraction": stats.clip_fraction,
                "approx_kl": stats.approx_kl,
            }
            return grads, m

        def apply_grads(params, opt_state, grads):
            with jax.named_scope(profiling.OPTIMIZER):
                grads = jax.lax.pmean(grads, DATA_AXIS)
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

        def minibatch_update(carry, mb):
            params, opt_state = carry
            adv = mb["advantages"]
            if cfg.normalize_adv:
                with jax.named_scope(profiling.ADVANTAGE):
                    adv = common.global_normalize_advantages(adv)
            grads, m = batch_grads(params, mb, adv)
            params, opt_state = apply_grads(params, opt_state, grads)
            return (params, opt_state), m

        def minibatch_step(carry, idx):
            with jax.named_scope(profiling.MINIBATCH_PREP):
                mb = take_minibatch(batch, idx)
                mb["obs"] = minibatch_obs(idx)
            return minibatch_update(carry, mb)

        # shuffle="env": a minibatch is every rollout step of one
        # contiguous block of envs. Cut out of the TIME-MAJOR [T, B]
        # arrays per minibatch, that block is not free on the TPU's
        # tiled layouts: the envs sit in the lanes, [T, mb] -> [T*mb]
        # is no bitcast there, and each minibatch's observations were
        # written out in the compute dtype and re-laid for Conv_0, 28 %
        # of the ppo-breakout iteration (PERF.md section 6, PR 26). So
        # the arrays are arranged block-major ONCE an iteration; a
        # minibatch is an index on the leading axis, and observations
        # stay uint8 until the torso's own conversion in batch_grads.
        mb_envs = local_envs // cfg.num_minibatches
        if env_sliced:
            with jax.named_scope(profiling.UPDATE), jax.named_scope(
                profiling.MINIBATCH_PREP
            ):
                env_major = jax.tree_util.tree_map(
                    lambda x: env_blocks(x, cfg.num_minibatches),
                    time_major if cfg.compact_frames
                    else {**time_major, "obs": traj.obs},
                )

        def env_minibatch_step(carry, start):
            with jax.named_scope(profiling.MINIBATCH_PREP):
                mb = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_index_in_dim(
                        x, start // mb_envs, keepdims=False
                    ),
                    env_major,
                )
                if cfg.compact_frames:
                    idx = (
                        jnp.arange(cfg.rollout_length)[:, None] * local_envs
                        + start
                        + jnp.arange(mb_envs)[None, :]
                    ).reshape(-1)
                    mb["obs"] = minibatch_obs(idx)
            return minibatch_update(carry, mb)

        def accum_epoch_update(carry):
            """Whole-batch epoch as ``grad_accum`` CONTIGUOUS slices:
            advantages normalized over the FULL batch first, per-slice
            gradients accumulated, ONE optimizer step — the mean of
            equal-size slice gradients IS the whole-batch gradient, but
            peak activation memory shrinks by the accumulation factor.
            No permutation, so no shuffle gather (contiguous reshape)."""
            params, opt_state = carry
            adv = batch["advantages"]
            if cfg.normalize_adv:
                with jax.named_scope(profiling.ADVANTAGE):
                    adv = common.global_normalize_advantages(adv)
            n_acc = cfg.grad_accum
            resh = lambda x: x.reshape((n_acc, -1) + x.shape[1:])
            with jax.named_scope(profiling.MINIBATCH_PREP):
                sliced = {k: resh(v) for k, v in batch.items()}
                sliced["advantages"] = resh(adv)
                if cfg.compact_frames:
                    obs_xs = jnp.arange(local_batch).reshape(n_acc, -1)
                    get_obs = minibatch_obs
                else:
                    obs_xs = resh(obs_flat)
                    get_obs = lambda o: o

            def slice_step(gacc, xs):
                mb, obs_x = xs
                mb = dict(mb)
                with jax.named_scope(profiling.MINIBATCH_PREP):
                    mb["obs"] = get_obs(obs_x)
                grads, m = batch_grads(params, mb, mb["advantages"])
                gacc = jax.tree_util.tree_map(jnp.add, gacc, grads)
                return gacc, m

            gacc, ms = jax.lax.scan(
                slice_step,
                jax.tree_util.tree_map(jnp.zeros_like, params),
                (sliced, obs_xs),
            )
            grads = jax.tree_util.tree_map(lambda g: g / n_acc, gacc)
            params, opt_state = apply_grads(params, opt_state, grads)
            m = jax.tree_util.tree_map(jnp.mean, ms)
            return (params, opt_state), m

        def epoch_step(carry, k):
            if cfg.num_minibatches == 1:
                # Whole-batch epoch: the gradient is permutation-
                # invariant, so skip the shuffle AND the full-buffer
                # random gather (a pure HBM-bandwidth tax at this
                # scale; the obs buffer alone is ~3.7 GB at 1024
                # envs x 128 steps).
                if cfg.grad_accum > 1:
                    carry, m = accum_epoch_update(carry)
                else:
                    mb = dict(batch)
                    with jax.named_scope(profiling.MINIBATCH_PREP):
                        if cfg.compact_frames:
                            mb["obs"] = minibatch_obs(
                                jnp.arange(local_batch)
                            )
                        else:
                            mb["obs"] = obs_flat
                    carry, m = minibatch_update(carry, mb)
                return carry, jax.tree_util.tree_map(lambda x: x[None], m)
            if env_sliced:
                starts = env_block_starts(k, cfg.num_minibatches, mb_envs)
                return jax.lax.scan(env_minibatch_step, carry, starts)
            idx = minibatch_iter_indices(k, local_batch, cfg.num_minibatches)
            return jax.lax.scan(minibatch_step, carry, idx)

        with jax.named_scope(profiling.UPDATE):
            epoch_keys = jax.random.split(k_perm, cfg.num_epochs)
            (params, opt_state), m = jax.lax.scan(
                epoch_step, (state.params, state.opt_state), epoch_keys
            )
        # Mean over [num_epochs, num_minibatches]; pmean so replicated.
        metrics = jax.lax.pmean(
            jax.tree_util.tree_map(jnp.mean, m), DATA_AXIS
        )
        # Guard BEFORE the mean dilutes anything: any non-finite
        # minibatch loss, or a non-finite leaf in the final params.
        metrics.update(
            common.guard_metrics(cfg.numerics_guards, (m["loss"], params))
        )
        metrics.update(common.episode_metrics(ep_info))

        new_extra = (
            rms_update(state.extra, traj.obs, axis_name=DATA_AXIS)
            if cfg.normalize_obs
            else state.extra
        )
        new_state = common.OnPolicyState(
            params=params,
            opt_state=opt_state,
            env_state=env_state,
            obs=obs,
            key=state.key,
            step=state.step + 1,
            extra=new_extra,
        )
        return new_state, metrics

    # ---- the recurrent path: its pieces, traced by the fused iteration
    # and by the two entry points alike --------------------------------

    def iteration_keys(state):
        dev = jax.lax.axis_index(DATA_AXIS)
        return jax.random.split(prng.fold(state.key, state.step, dev))

    def obs_norm(state):
        if cfg.normalize_obs:
            rms = state.extra
            return lambda o: rms_normalize(o, rms)
        return lambda o: o

    def rollout_recurrent(state, k_roll, norm):
        return common.collect_rollout_recurrent(
            env, env_params, seq_dist_value, state.params,
            state.env_state, state.obs, state.carry, k_roll,
            cfg.rollout_length, norm=norm,
        )

    def block_loss_grads(params, block, norm):
        """The PPO loss and its gradient on one whole-trajectory block,
        before the optimizer: obs/env fields [T, b], resets [T, b],
        the core's rollout-entry carry [b, ...] (None where the core
        replays from the empty carry). Advantages are whitened over
        the block. Returns ``(grads, metrics, the core's counters)``."""
        adv = block["advantages"].reshape(-1)
        if cfg.normalize_adv:
            with jax.named_scope(profiling.ADVANTAGE):
                adv = common.global_normalize_advantages(adv)

        with jax.named_scope(profiling.MINIBATCH_PREP):
            obs = prep_obs(block["obs"])

        def loss_fn(p):
            dist, values_tb, _, core_stats = seq_dist_value(
                p, norm(obs), block["resets"], block["core"]
            )
            with jax.named_scope(profiling.LM_HEAD):
                log_probs, ent = dist.log_prob_and_entropy(block["actions"])
                log_probs, ent = log_probs.reshape(-1), ent.mean()
            clip = ppo_clip_loss(
                log_probs,
                block["old_log_probs"].reshape(-1),
                adv,
                clip_eps=cfg.clip_eps,
            )
            values = values_tb.reshape(-1)
            if cfg.vf_clip:
                vf = clipped_value_loss(
                    values, block["old_values"].reshape(-1),
                    block["returns"].reshape(-1), clip_eps=cfg.clip_eps,
                )
            else:
                vf = value_loss(values, block["returns"].reshape(-1))
            total = (
                clip.policy_loss + cfg.vf_coef * vf - cfg.ent_coef * ent
            )
            return total, (clip, vf, ent, core_stats)

        with jax.named_scope(profiling.LOSS_GRAD):
            (loss, (clip, vf, ent, core_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
        m = {
            "loss": loss,
            "policy_loss": clip.policy_loss,
            "value_loss": vf,
            "entropy": ent,
            "clip_fraction": clip.clip_fraction,
            "approx_kl": clip.approx_kl,
        }
        return grads, m, core_stats

    def local_iteration_recurrent(state: common.OnPolicyState):
        """Recurrent PPO iteration: same rollout -> GAE -> epochs shape,
        but the policy forward is the time-major sequence pass and every
        minibatch is a whole-trajectory env block replayed from the
        rollout-entry carry (truncated BPTT over the rollout window;
        the stored carry goes stale across epochs as params move — the
        standard recurrent-PPO approximation)."""
        k_roll, k_perm = iteration_keys(state)
        norm = obs_norm(state)

        carry0 = state.carry
        env_state, obs, carry1, traj, ep_info, rollout_stats = (
            rollout_recurrent(state, k_roll, norm)
        )
        with jax.named_scope(profiling.ADVANTAGE):
            _, last_value_tb, _, _ = seq_dist_value(
                state.params, norm(obs)[None], carry1["prev_done"][None],
                carry1["core"],
            )
            advantages, returns = gae_advantages(
                traj.rewards, traj.values, traj.dones, last_value_tb[0],
                gamma=cfg.gamma, lam=cfg.gae_lambda,
                terminations=ep_info["terminated"],
                truncation_values=None,
            )

        resets_tb = common.replay_resets(carry0["prev_done"], traj.dones)
        entry_core = carry0["core"] if replay_carry else None
        env_tb = {
            "actions": traj.actions,
            "old_log_probs": traj.log_probs,
            "old_values": traj.values,
            "advantages": advantages,
            "returns": returns,
        }

        def seq_update(carry_po, block):
            """One optimizer step on a whole-trajectory block."""
            params, opt_state = carry_po
            grads, m, stats = block_loss_grads(params, block, norm)
            with jax.named_scope(profiling.OPTIMIZER):
                grads = jax.lax.pmean(grads, DATA_AXIS)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return (params, opt_state), (m, stats)

        mb_envs = local_envs // cfg.num_minibatches

        def env_block_update(carry_po, start):
            def cut(x, axis):
                return jax.lax.dynamic_slice_in_dim(x, start, mb_envs, axis)

            with jax.named_scope(profiling.MINIBATCH_PREP):
                block = {k: cut(v, 1) for k, v in env_tb.items()}
                block["obs"] = cut(traj.obs, 1)
                block["resets"] = cut(resets_tb, 1)
                block["core"] = jax.tree_util.tree_map(
                    lambda x: cut(x, 0), entry_core
                )
            return seq_update(carry_po, block)

        def epoch_step(carry_po, k):
            if cfg.num_minibatches == 1:
                block = dict(
                    env_tb, obs=traj.obs, resets=resets_tb, core=entry_core,
                )
                carry_po, out = seq_update(carry_po, block)
                return carry_po, jax.tree_util.tree_map(
                    lambda x: x[None], out
                )
            starts = env_block_starts(k, cfg.num_minibatches, mb_envs)
            return jax.lax.scan(env_block_update, carry_po, starts)

        with jax.named_scope(profiling.UPDATE):
            epoch_keys = jax.random.split(k_perm, cfg.num_epochs)
            (params, opt_state), (m, update_stats) = jax.lax.scan(
                epoch_step, (state.params, state.opt_state), epoch_keys
            )
        metrics = jax.lax.pmean(
            jax.tree_util.tree_map(jnp.mean, m), DATA_AXIS
        )
        if update_stats:  # the core's own counters, reduced by the core
            metrics.update(
                model.iteration_stats(rollout_stats, update_stats, DATA_AXIS)
            )
        metrics.update(
            common.guard_metrics(cfg.numerics_guards, (m["loss"], params))
        )
        metrics.update(common.episode_metrics(ep_info))

        new_extra = (
            rms_update(state.extra, traj.obs, axis_name=DATA_AXIS)
            if cfg.normalize_obs
            else state.extra
        )
        return common.OnPolicyState(
            params=params,
            opt_state=opt_state,
            env_state=env_state,
            obs=obs,
            key=state.key,
            step=state.step + 1,
            extra=new_extra,
            carry=carry1,
        ), metrics

    example = jax.eval_shape(init, jax.random.PRNGKey(0))
    specs = common.state_specs(example)

    def local_collect(state: common.OnPolicyState):
        """The rollout the next iteration would collect, alone."""
        k_roll, _ = iteration_keys(state)
        traj = rollout_recurrent(state, k_roll, obs_norm(state))[3]
        return traj, state.carry

    def local_block_grads(params, block):
        grads, m, _ = block_loss_grads(params, block, lambda o: o)
        parts = {k: m[k] for k in ("policy_loss", "value_loss", "entropy")}
        return jax.lax.pmean((m["loss"], parts, grads), DATA_AXIS)

    def block_grads(params, block):
        """``(loss, parts, grads)`` of one env block: fields ``[T, b,
        ...]`` as ``seq_update`` takes them (``obs``, ``actions``,
        ``old_log_probs``, ``old_values``, ``advantages``, ``returns``,
        ``resets``) and ``core``, the entry carry ``[b, ...]`` or None."""
        block_specs = {
            k: jax.tree_util.tree_map(
                lambda _: P(DATA_AXIS) if k == "core" else P(None, DATA_AXIS),
                v,
            )
            for k, v in block.items()
        }
        return shard_map(
            local_block_grads, mesh=mesh, in_specs=(P(), block_specs),
            out_specs=P(),
        )(params, block)

    entry_points = {}
    if cfg.recurrent and not cfg.normalize_obs:
        by_env = P(None, DATA_AXIS)
        entry_points = dict(
            collect=jax.jit(shard_map(
                local_collect, mesh=mesh, in_specs=(specs,),
                out_specs=(Trajectory(*[by_env] * 6),
                           shard_batch_specs(example.carry)),
            )),
            block_grads=jax.jit(block_grads),
        )
    iteration = common.build_data_parallel_iteration(
        local_iteration_recurrent if cfg.recurrent else local_iteration,
        example, mesh,
    )
    return common.IterationFns(
        init=init,
        iteration=iteration,
        mesh=mesh,
        steps_per_iteration=cfg.num_envs * cfg.rollout_length,
        **entry_points,
    )
