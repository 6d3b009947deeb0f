"""Async host-env loop for off-policy trainers (DDPG/TD3/SAC).

Capability parity: the reference steps real Gym/MuJoCo envs from its
Python loop while the accelerator runs the updates (BASELINE.json:9-10,
SURVEY.md §3.2). The fused ``shard_map`` iteration in
``algos.offpolicy`` instead pulls env stepping INSIDE the jitted
program via ``io_callback``, which serialises MuJoCo physics and
gradient updates inside one device program. ``--host-loop async``
selects this loop instead; ``auto`` and ``fused`` take the fused path.

This loop is the TPU-first decomposition of the same trainer:

  host CPU:   env stepping + acting (a CPU-jitted copy of ``act_fn``
              on a <=1-iteration-stale param snapshot — off-policy
              algorithms are indifferent to that lag by construction)
  accelerator: replay ingest + the update block (the trainer's OWN
              ``one_update`` scanned ``updates_per_iter`` times, the
              exact math the fused path runs)

synchronized once per iteration: stage the host transitions, dispatch
ingest+updates (async), step the next iteration's envs while the
accelerator crunches, then refresh the acting snapshot. Update
dispatch overlaps env physics (throughput against the fused path on
the chip: not measured).

Uses ``TrainerParts`` (``algos.offpolicy``) — the trainer's composable
acting/update/init pieces — so DDPG, TD3, and SAC all run through this
loop unchanged. Checkpoints use the same ``OffPolicyState`` structure
as the fused path (mutual resume works; the host simulator state
itself is not checkpointable and re-seeds on resume, as in the fused
host-env mode).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from actor_critic_algs_on_tensorflow_tpu.algos import offpolicy
from actor_critic_algs_on_tensorflow_tpu.envs.host import HostEnvState
from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
    DATA_AXIS,
    shard_map,
)


def host_async_supported(cfg) -> bool:
    """This loop serves host-resident envs on a single-device config."""
    return str(cfg.env).startswith(("gym:", "native:")) and (
        cfg.num_devices in (0, 1)
    )


class _GuardedPair(NamedTuple):
    """What the async loop's health sentinel snapshots and restores:
    the learner-side state a bad update can poison. The replay ring is
    NOT rolled back — its contents are data, not derived state, and
    stay valid across a rollback."""

    params: Any
    opt_state: Any


def _build_update(parts, accel) -> Any:
    """jit(shard_map) of ``updates_per_iter`` x ``one_update`` over a
    1-device mesh on the accelerator (``one_update`` contains
    ``lax.pmean`` over the data axis, so it needs the mesh ctx)."""
    cfg = parts.cfg

    def body(params, opt_state, replay, keys):
        (params, opt_state), m = jax.lax.scan(
            functools.partial(parts.one_update, replay),
            (params, opt_state),
            keys,
        )
        # TD3-style delayed metrics: actor_loss is only produced on
        # delay steps, so average it over the updates that RAN (same
        # masking the fused path applies) instead of diluting with
        # skip-step zeros.
        did = m.pop("actor_updates", None)
        out = jax.tree_util.tree_map(jnp.mean, m)
        if did is not None:
            out["actor_loss"] = jnp.sum(m["actor_loss"]) / jnp.maximum(
                jnp.sum(did), 1.0
            )
            out["actor_updates"] = jnp.mean(did)
        # Same in-graph guard as the fused path's finalize_iteration:
        # the async loop's sentinel reads health_finite off these
        # metrics once the dispatched update retires.
        from actor_critic_algs_on_tensorflow_tpu.algos.common import (
            guard_metrics,
        )

        out.update(
            guard_metrics(
                getattr(cfg, "numerics_guards", False), (m, params)
            )
        )
        return params, opt_state, out

    mesh = Mesh(np.asarray([accel]), (DATA_AXIS,))
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


def _build_ingest(parts) -> Any:
    def ingest(replay, staged):
        """``staged``: a Transition pytree of [T, B, ...] leaves,
        flattened to ONE ring scatter (insertion order within the batch
        does not matter for uniform replay, and a single scatter beats
        a scan of T scatters by the scan's length)."""
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), staged
        )
        return parts.setup.buf.add_batch(replay, flat)

    return jax.jit(ingest, donate_argnums=(0,))


def run_host_async(
    fns: offpolicy.OffPolicyFns,
    *,
    total_env_steps: int,
    seed: int = 0,
    log_interval_iters: int = 20,
    log_fn=None,
    summary_writer=None,
    checkpointer=None,
    checkpoint_interval_iters: int = 0,
    initial_state: offpolicy.OffPolicyState | None = None,
    snapshot_interval: int = 0,
    sentinel=None,
) -> Tuple[offpolicy.OffPolicyState, list]:
    """Train with host-side env stepping and accelerator-side updates.

    Mirrors ``common.run_loop``'s interface/logging; returns
    ``(final OffPolicyState, history)``.

    ``sentinel`` (utils.health.TrainingHealthSentinel) guards the
    learner-side ``(params, opt_state)`` pair against the
    ``health_finite`` bit the update program emits (the trainer's
    ``numerics_guards``): a NaN update rolls both back to a last-good
    snapshot instead of poisoning every later iteration. Use the
    sentinel's ``delayed`` mode here — an immediate check would stall
    the host loop on the in-flight accelerator update every iteration.
    """
    from actor_critic_algs_on_tensorflow_tpu.algos.common import (
        RateClock,
        emit_log,
    )

    parts = fns.parts
    cfg, s = parts.cfg, parts.setup
    if not host_async_supported(cfg):
        raise ValueError(
            f"host_async serves gym:/native: envs on one device; got "
            f"env={cfg.env!r} num_devices={cfg.num_devices}"
        )
    env = s.genv  # global-width host env pool; stepped DIRECTLY below
    accel = jax.devices()[0]
    cpu = jax.devices("cpu")[0]

    update = _build_update(parts, accel)
    ingest = _build_ingest(parts)

    key = jax.random.PRNGKey(seed)
    k_params, k_loop = jax.random.split(key)
    # EVERYTHING the host loop touches per step must live on the CPU
    # device: with an accelerator as the default backend, a single
    # stray fold_in/asarray costs a device dispatch and a host fetch
    # per env step.
    k_loop = jax.device_put(k_loop, cpu)

    steps_per_iteration = s.steps_per_iteration
    num_iters = max(1, total_env_steps // steps_per_iteration)
    iters_done0 = int(initial_state.step) if initial_state is not None else 0
    num_iters -= iters_done0
    if iters_done0 == 0:
        num_iters = max(1, num_iters)
    if num_iters <= 0:
        return initial_state, []

    # The host simulator is not checkpointable; (re)seed it either way.
    obs = env._host_reset(seed + iters_done0)

    if initial_state is None:
        with jax.default_device(accel):
            params, opt_state = jax.jit(parts.init_params)(
                k_params, jnp.asarray(obs[:1])
            )
        example = offpolicy.Transition(
            obs=jnp.asarray(obs[0]),
            action=jnp.zeros((s.action_dim,)),
            reward=jnp.zeros(()),
            next_obs=jnp.asarray(obs[0]),
            terminated=jnp.zeros(()),
        )
        replay = jax.device_put(s.buf.init(example), accel)
        inserted = 0
    else:
        params = jax.device_put(initial_state.params, accel)
        opt_state = jax.device_put(initial_state.opt_state, accel)
        replay = jax.device_put(
            jax.tree_util.tree_map(lambda x: x[0], initial_state.replay),
            accel,
        )
        inserted = int(replay.size)

    if initial_state is not None:
        # Resume the exploration carry (OU state / PRNG-noise carry)
        # from the checkpoint so async resume matches the fused loop's
        # semantics; only the host env simulator itself re-seeds.
        noise = jax.device_put(initial_state.noise, cpu)
    else:
        noise = jax.device_put(parts.noise_init(cfg.num_envs), cpu)
    # The acting snapshot transfers ONLY the pieces acting reads
    # (actor + warmup scalars), refreshed every ``snapshot_interval``
    # iterations: the device->host hop stalls the host loop, and
    # off-policy acting tolerates a bounded-staleness policy by
    # construction. interval=0 adapts: keep transfer wait under ~1/3
    # of the env-stepping time, capped at 16 iterations.
    acting_params = jax.device_put(parts.acting_slice(params), cpu)
    act = jax.jit(parts.act_with)

    history = []
    clock = RateClock(steps_per_iteration, log_interval_iters)
    staged = None
    staged_slot = -1
    # Double-buffered host staging arenas: one preallocated contiguous
    # buffer per Transition field per slot, filled with indexed writes
    # in the env loop (no per-iteration list + np.stack allocation).
    # A slot is rewritten only after its previous device transfer
    # completed (stage_pending gate), so the async H2D copy can ride
    # under env stepping without ever reading a half-overwritten slot.
    stage_arenas: list = [None, None]
    stage_pending: list = [None, None]
    snap_interval_eff = max(0, snapshot_interval) or 1

    def dispatch_staged():
        # device_put + ingest of the staged arena slot; records the
        # transfer handle that gates the slot's reuse.
        nonlocal replay, inserted
        staged_dev = jax.device_put(staged, accel)
        stage_pending[staged_slot] = staged_dev
        replay = ingest(replay, staged_dev)
        inserted += steps_per_iteration

    def flush_staged():
        # Ingest any not-yet-dispatched transitions so a packed state's
        # replay ring agrees with its step counter.
        nonlocal staged
        if staged is not None:
            dispatch_staged()
            staged = None
    m_dev: Dict[str, jax.Array] = {}
    ep_returns: list = []

    if sentinel is not None:
        sentinel.seed(_GuardedPair(params, opt_state), iters_done0 - 1)

    for it_off in range(num_iters):
        it = iters_done0 + it_off
        it_key = jax.random.fold_in(k_loop, it)

        # 1. Dispatch accelerator work for the PREVIOUS iteration's
        #    transitions (runs while this iteration steps envs).
        if staged is not None:
            dispatch_staged()
        size = min(inserted, s.buf.capacity)
        if it >= s.warmup_iters and size >= cfg.batch_size:
            upd_keys = jax.device_put(
                jax.random.split(
                    jax.random.fold_in(it_key, 1), cfg.updates_per_iter
                ),
                accel,
            )
            params, opt_state, m_dev = update(
                params, opt_state, replay, upd_keys
            )
            if sentinel is not None:
                # Delayed mode checks the PREVIOUS update's (long
                # retired) guard bit — no stall on the dispatch above.
                pair = sentinel.after_step(
                    it, _GuardedPair(params, opt_state), m_dev
                )
                params, opt_state = pair.params, pair.opt_state

        # 2. Step envs on the host with the bounded-stale snapshot,
        #    writing transitions straight into this iteration's arena
        #    slot (alternating slots; reuse gated on the slot's last
        #    transfer having completed).
        env_t0 = time.perf_counter()
        step_scalar = jax.device_put(np.int32(it), cpu)
        k_steps = jax.random.fold_in(it_key, 2)  # cpu (it_key is cpu)
        slot = it_off % 2
        if stage_pending[slot] is not None:
            jax.block_until_ready(stage_pending[slot])
            stage_pending[slot] = None
        arena = stage_arenas[slot]
        for t in range(cfg.steps_per_iter):
            k_t = jax.random.fold_in(k_steps, t)
            obs_cpu = jax.device_put(obs, cpu)
            a, noise = act(acting_params, obs_cpu, noise, k_t, step_scalar)
            a_np = np.asarray(a)
            (next_obs, reward, done, term, trunc, final_obs,
             ep_ret, ep_len) = env._host_step(a_np)
            if arena is None:
                mk = lambda x: np.empty(
                    (cfg.steps_per_iter,) + np.shape(x),
                    dtype=np.asarray(x).dtype,
                )
                arena = offpolicy.Transition(
                    obs=mk(obs), action=mk(a_np), reward=mk(reward),
                    next_obs=mk(final_obs), terminated=mk(term),
                )
                stage_arenas[slot] = arena
            arena.obs[t] = obs
            arena.action[t] = a_np
            arena.reward[t] = reward
            arena.next_obs[t] = final_obs
            arena.terminated[t] = term
            if parts.noise_reset is not None and done.any():
                noise = parts.noise_reset(
                    noise, jax.device_put(done, cpu)
                )
            for i in np.nonzero(done > 0.5)[0]:
                ep_returns.append(float(ep_ret[i]))
            obs = next_obs
        staged = arena
        staged_slot = slot

        # 3. Refresh the acting snapshot (the transfer is enqueued
        #    behind the update, so its completion implies the update
        #    finished — the loop's only accelerator sync point).
        env_dt = time.perf_counter() - env_t0
        if snap_interval_eff <= 1 or (it_off % snap_interval_eff) == 0:
            sync_t0 = time.perf_counter()
            acting_params = jax.device_put(parts.acting_slice(params), cpu)
            jax.block_until_ready(acting_params)
            # Total SYNC time, deliberately including update completion
            # (the transfer queues behind the dispatched update): each
            # snapshot refresh stalls the host loop by this full
            # amount, so cadence backs off
            # whenever the sync point is expensive for ANY reason —
            # slow transfer or slow updates alike.
            sync_dt = time.perf_counter() - sync_t0
            if snapshot_interval == 0 and env_dt > 0:
                snap_interval_eff = int(
                    np.clip(np.ceil(sync_dt / (env_dt / 3.0)), 1, 16)
                )

        if it_off == 0:
            clock.first_iteration_done()

        if (it_off + 1) % log_interval_iters == 0 or it_off == num_iters - 1:
            m = {k: float(v) for k, v in m_dev.items()}
            window_eps = ep_returns[-100:]
            m["episodes"] = float(len(ep_returns))
            m["avg_return"] = (
                float(np.mean(window_eps)) if window_eps else 0.0
            )
            m["replay_size"] = float(size)
            env_steps = (it + 1) * steps_per_iteration
            m["steps_per_sec"] = clock.rate(it_off)
            emit_log(env_steps, m, history, summary_writer, log_fn)

        if (
            checkpointer is not None
            and checkpoint_interval_iters
            and (it_off + 1) % checkpoint_interval_iters == 0
        ):
            if sentinel is not None:
                # A checkpoint must never capture a state whose own
                # update went unchecked (delayed guard mode).
                pair = sentinel.flush(_GuardedPair(params, opt_state))
                params, opt_state = pair.params, pair.opt_state
            flush_staged()
            state = _pack_state(
                params, opt_state, obs, noise, replay, key, it + 1
            )
            checkpointer.save((it + 1) * steps_per_iteration, state)

    if sentinel is not None:
        pair = sentinel.flush(_GuardedPair(params, opt_state))
        params, opt_state = pair.params, pair.opt_state
    flush_staged()
    state = _pack_state(
        params, opt_state, obs, noise, replay, key, iters_done0 + num_iters
    )
    return state, history


def _pack_state(
    params, opt_state, obs, noise, replay, key, step
) -> offpolicy.OffPolicyState:
    """Fused-path-compatible ``OffPolicyState`` (checkpoint format)."""
    return offpolicy.OffPolicyState(
        params=params,
        opt_state=opt_state,
        env_state=HostEnvState(t=jnp.asarray(step, jnp.int32)),
        obs=jnp.asarray(obs),
        noise=noise,
        replay=jax.tree_util.tree_map(lambda x: x[None], replay),
        key=key,
        step=jnp.asarray(step, jnp.int32),
    )
