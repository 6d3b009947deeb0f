"""Algorithm-neutral distributed off-policy runner (the Ape-X shape).

``run_offpolicy_distributed`` wires the prioritized replay tier
(``distributed/replay.py``) end-to-end for any trainer that exposes
``TrainerParts.update_batch`` (DDPG/TD3/SAC):

  - N replay-server PROCESSES, each one shard of the prioritized ring
    (actor->shard assignment from ``ShardPlan``'s contiguous slices);
  - M env-stepper actor PROCESSES: jitted act+env.step on the host
    CPU, transitions pushed to their shard over the coded trajectory
    wire path, acting params fetched from the learner's param plane
    (KIND_GET_PARAMS + publish notifies — the PR-5 machinery as-is);
  - the learner (this process): round-robin prioritized draws across
    shards, one ``update_batch`` per draw with importance weights,
    absolute-TD priorities flowed back over ``KIND_PRIO_UPDATE``, and
    acting-slice publishes after each update burst.

Update pacing: the learner targets the SAME updates-per-transition
ratio as the single-process fused iteration
(``updates_per_iter / (num_envs * steps_per_iter)``), so a distributed
run at a fixed env-step budget performs a comparable number of
gradient steps — the learning-parity contract the acceptance test
pins. Acting and learning are otherwise unsynchronized (Ape-X).

Fault semantics: replay-server and actor processes are monitored and
respawned in place (same port — the fleet's endpoint lists are
immutable); a replay-server restart costs refill time while draws
fail over to the surviving shards, never the learner.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from actor_critic_algs_on_tensorflow_tpu.algos import offpolicy
from actor_critic_algs_on_tensorflow_tpu.utils.metric_names import (
    REPLAY,
    REPLAY_SAMPLE,
)

_ALGOS = ("ddpg", "td3", "sac")


def _maker(algo: str):
    if algo == "ddpg":
        from actor_critic_algs_on_tensorflow_tpu.algos.ddpg import make_ddpg

        return make_ddpg
    if algo == "td3":
        from actor_critic_algs_on_tensorflow_tpu.algos.td3 import make_td3

        return make_td3
    if algo == "sac":
        from actor_critic_algs_on_tensorflow_tpu.algos.sac import make_sac

        return make_sac
    raise ValueError(f"unknown off-policy algo {algo!r} (want {_ALGOS})")


def algo_of_config(cfg) -> str:
    """DDPGConfig -> 'ddpg' etc. — the spawn-safe trainer identity
    (configs pickle across process boundaries; closures do not)."""
    name = type(cfg).__name__.lower()
    for algo in _ALGOS:
        if name.startswith(algo):
            return algo
    raise ValueError(
        f"config {type(cfg).__name__} is not an off-policy trainer "
        f"config ({_ALGOS})"
    )


def _validate_cfg(cfg, n_replay_shards: int, n_actors: int) -> None:
    if str(cfg.env).startswith(("gym:", "native:")):
        raise ValueError(
            f"run_offpolicy_distributed steps pure-JAX envs in the "
            f"actor processes; host-resident env {cfg.env!r} is not "
            f"supported (use the single-process --host-loop paths)"
        )
    if n_replay_shards < 1 or n_actors < 1:
        raise ValueError(
            f"need >= 1 replay shard and >= 1 actor, got "
            f"{n_replay_shards}/{n_actors}"
        )
    # No divisibility requirement: actor->shard assignment uses
    # ShardPlan.balanced()'s remainder-spreading slices, so any fleet
    # size maps onto any shard count — the elasticity precondition
    # (an autoscaler-ramped fleet cannot promise divisibility).


def _offpolicy_actor_main(
    algo: str,
    cfg,
    actor_id: int,
    learner_host: str,
    learner_port: int,
    replay_endpoints: List[Tuple[str, int]],
    seed: int,
    generation: int = 0,
    max_env_steps: int = 0,
    throttle_steps_per_s: float = 0.0,
    param_endpoints: List[Tuple[str, int]] | None = None,
) -> None:
    """Entry point of one spawned env-stepper actor PROCESS.

    The off-policy analog of the IMPALA actor main: a jitted
    act+env.step scan on the host CPU, ``cfg.steps_per_iter`` steps
    per push, transitions flattened to ``[T*B, ...]`` rows and shipped
    to this actor's replay shard (coded when ``cfg.replay_codec``),
    acting params re-fetched on publish notifies. ``replay_endpoints``
    is PRIORITY-ordered with the actor's OWN shard at the head — if
    that shard dies, pushes fail over to a sibling (any shard's data
    is good data) and re-home head-first once it returns.

    ``max_env_steps`` (> 0) caps this actor's share of the global
    env-step budget: at the cap it PARKS (keeps the param-plane link
    so KIND_CLOSE still reaches it; exiting would trip the runner's
    respawn) instead of free-running past the budget — the fixed-budget
    comparability contract of the acceptance test."""
    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
        pin_process_to_cpu,
    )

    pin_process_to_cpu(f"replay-actor {actor_id}")
    from actor_critic_algs_on_tensorflow_tpu.distributed import (
        codec as codec_lib,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.resilience import (
        ResilientActorClient,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        CAP_REPLAY,
        CAP_TRAJ_CODED,
        ROLE_ACTOR,
        LearnerShutdown,
    )

    acfg = dataclasses.replace(cfg, num_devices=1)
    parts = _maker(algo)(acfg).parts
    s = parts.setup
    env, env_params = s.genv, s.env_params

    @jax.jit
    def collect(acting_params, env_state, obs, noise, key, step):
        def _step(c, k):
            env_state, obs, noise = c
            k_act, k_env = jax.random.split(k)
            a, noise = parts.act_with(acting_params, obs, noise, k_act, step)
            env_state, next_obs, reward, done, info = env.step(
                k_env, env_state, a, env_params
            )
            if parts.noise_reset is not None:
                noise = parts.noise_reset(noise, done)
            tr = offpolicy.Transition(
                obs=obs,
                action=a,
                reward=reward,
                # AutoReset returns the post-reset obs at boundaries;
                # the true successor is final_obs (same contract as
                # act_then_store).
                next_obs=info["final_obs"],
                terminated=info["terminated"],
            )
            ep = (info["episode_return"], info["done_episode"])
            return (env_state, next_obs, noise), (tr, ep)

        keys = jax.random.split(key, cfg.steps_per_iter)
        (env_state, obs, noise), (traj, ep) = jax.lax.scan(
            _step, (env_state, obs, noise), keys
        )
        return env_state, obs, noise, traj, ep

    # Acting-slice treedef, derived without touching the network: the
    # learner publishes exactly acting_slice(params)'s leaves.
    obs_spec = jax.eval_shape(
        lambda k: env.reset(k, env_params)[1], jax.random.PRNGKey(0)
    )
    obs_example = jnp.zeros((1,) + obs_spec.shape[1:], obs_spec.dtype)
    params_spec = jax.eval_shape(
        lambda k: parts.init_params(k, obs_example)[0],
        jax.random.PRNGKey(0),
    )
    acting_def = jax.tree_util.tree_structure(
        parts.acting_slice(params_spec)
    )

    caps = CAP_REPLAY | (CAP_TRAJ_CODED if cfg.replay_codec else 0)
    hello = (actor_id, generation, ROLE_ACTOR, caps)
    # ``param_endpoints`` is the PRIORITY-ordered param-plane address
    # list (primary first, warm standbys after): losing the primary
    # costs one endpoint rotation inside the ordinary retry walk, and
    # the actor lands on the standby's (early) listener instead of
    # backing off against a dead address until its budget runs out.
    pclient = ResilientActorClient(
        learner_host, learner_port, hello=hello,
        endpoints=param_endpoints,
    )
    rclient = ResilientActorClient(
        replay_endpoints[0][0],
        replay_endpoints[0][1],
        hello=hello,
        endpoints=replay_endpoints,
    )
    encoder = (
        codec_lib.TrajEncoder(obs_delta=False) if cfg.replay_codec else None
    )
    try:
        version, leaves = pclient.fetch_params()
        while version == 0:  # learner has not published yet
            time.sleep(0.05)
            version, leaves = pclient.fetch_params()
        acting = jax.tree_util.tree_unflatten(acting_def, leaves)

        def refetch():
            nonlocal version, acting
            fetched, fresh = pclient.fetch_params()
            if fetched > 0:
                version = fetched
                acting = jax.tree_util.tree_unflatten(acting_def, fresh)

        key = jax.random.PRNGKey(seed)
        key, k = jax.random.split(key)
        env_state, obs = env.reset(k, env_params)
        noise = parts.noise_init(cfg.num_envs)
        steps_per_push = cfg.num_envs * cfg.steps_per_iter
        it = 0
        t_start = time.monotonic()
        while True:
            if throttle_steps_per_s > 0:
                # Actor pacing (chaos drills / rate experiments): a
                # pure-JAX toy env outruns any wall-clock schedule, so
                # cap the push rate instead of letting the fleet
                # exhaust its budget in one burst.
                ahead = (
                    it * steps_per_push / throttle_steps_per_s
                    - (time.monotonic() - t_start)
                )
                if ahead > 0:
                    time.sleep(min(ahead, 0.5))
            if max_env_steps and it * steps_per_push >= max_env_steps:
                # Budget share done: park (LearnerShutdown from the
                # notify drain is the exit signal). wait_params_notify,
                # not poll_notified: the park loop makes no other call
                # that would reconnect a dropped link, and a parked
                # actor that can't hear KIND_CLOSE only exits via the
                # teardown SIGTERM.
                pclient.wait_params_notify(0.2)
                continue
            key, k = jax.random.split(key)
            env_state, obs, noise, traj, ep = collect(
                acting, env_state, obs, noise, k, jnp.int32(it)
            )
            # [T, B, ...] -> [T*B, ...] transition rows (insertion
            # order inside one push is irrelevant to replay).
            rows = [
                np.asarray(x).reshape((-1,) + np.shape(x)[2:])
                for x in jax.tree_util.tree_leaves(traj)
            ]
            ep_ret, ep_done = (np.asarray(x) for x in ep)
            finished = ep_ret[ep_done > 0.5].astype(np.float32)
            # Fetch-before-push: a notify that landed during the
            # rollout is in the buffer now (same discipline as the
            # IMPALA actor main).
            notified = pclient.poll_notified()
            if notified > 0 and notified != version:
                refetch()
            rclient.push_trajectory(rows, [finished], encoder=encoder)
            it += 1
            if it % 10 == 0:
                # Drift back onto the actor's OWN shard if a past
                # fault parked this link on a fallback sibling.
                rclient.rehome()
    except LearnerShutdown:
        print(
            f"[replay-actor {actor_id}] learner closed the stream; "
            f"exiting ({pclient.stats()} / {rclient.stats()})",
            flush=True,
        )
    except (ConnectionError, OSError) as e:
        print(
            f"[replay-actor {actor_id}] transport failed after "
            f"retries: {type(e).__name__}: {e}",
            flush=True,
        )
    finally:
        for c in (pclient, rclient):
            try:
                c.close()
            except Exception:
                pass


def paced_update_target(
    total_env_steps: int, warmup_env_steps: int, update_ratio: float
) -> int:
    """Updates the paced learner owes by the end of the run. Zero when
    the budget can never clear warmup — the update gate requires
    ``inserted >= warmup_env_steps``, so a sub-warmup run that owed
    updates could only ever exit through the stall guard."""
    if total_env_steps < warmup_env_steps:
        return 0
    return int(total_env_steps * update_ratio)


def _build_wire_update(parts, accel, donate: bool = False):
    """jit(shard_map) of one ``update_batch`` step over a 1-device
    mesh on the accelerator (the update math pmean's over the data
    axis, so it needs the mesh ctx — same shape as the host-async
    loop's update program).

    ``donate=True`` is the pipelined loop's second compilation: the
    carry (params, opt_state) and the consumed (batch, weights)
    buffers are donated so XLA updates in place instead of holding
    two generations live. Safe by construction — the health sentinel
    snapshots/restores COPIES, the key is never donated, and the
    metrics/td outputs are fresh buffers. Donation changes buffer
    lifetimes only, never numerics, so the depth-1 bit-identity
    contract holds across both compilations. (On the CPU backend a
    transferred batch may alias arena host memory; XLA then refuses
    that donation with a warning rather than corrupting the slot.)"""
    from jax.sharding import Mesh, PartitionSpec as P

    from actor_critic_algs_on_tensorflow_tpu.algos.common import (
        guard_metrics,
    )
    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
        DATA_AXIS,
        shard_map,
    )

    cfg = parts.cfg

    def body(params, opt_state, batch, weights, key):
        (params, opt_state), m, td = parts.update_batch(
            batch, weights, (params, opt_state), key
        )
        m = dict(m)
        m.update(
            guard_metrics(
                getattr(cfg, "numerics_guards", False), (m, params)
            )
        )
        return params, opt_state, m, td

    mesh = Mesh(np.asarray([accel]), (DATA_AXIS,))
    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1, 2, 3) if donate else (),
    )


class ReplayRunHandles(NamedTuple):
    """Live process/endpoint view handed to ``on_start`` (chaos tests
    SIGKILL through it; dicts are mutated in place as the runner
    respawns, so the caller always sees the CURRENT processes)."""

    replay_procs: Dict[int, Any]
    replay_ports: Dict[int, int]
    actor_procs: Dict[int, Any]
    server: Any
    group: Any


class OffPolicyDistributedResult(NamedTuple):
    params: Any
    opt_state: Any
    updates: int
    env_steps: int


class _Carry(NamedTuple):
    """The learner-loop train state the sentinel snapshots/rolls back
    (named fields so ``TrainingHealthSentinel._trip`` can reach
    ``.params``)."""

    params: Any
    opt_state: Any


def _ckpt_state(
    params, opt_state, updates_done, meter_cum, meter_last,
    env_steps, epoch,
):
    """The off-policy learner's checkpoint pytree: weights + optimizer
    PLUS the run-progress scalars a resume must not re-derive — the
    paced-update meter and the per-shard ingest watermarks (so the
    global transition meter continues instead of double- or under-
    counting against snapshot-restored shards)."""
    return {
        "params": params,
        "opt_state": opt_state,
        "updates_done": np.asarray(int(updates_done), np.int64),
        "meter_cum": np.asarray(meter_cum, np.float64),
        "meter_last": np.asarray(meter_last, np.float64),
        "env_steps": np.asarray(int(env_steps), np.int64),
        "epoch": np.asarray(int(epoch), np.int64),
    }


def run_offpolicy_distributed(
    fns: offpolicy.OffPolicyFns,
    *,
    total_env_steps: int,
    seed: int = 0,
    n_replay_shards: int = 2,
    n_actors: int = 2,
    host: str = "127.0.0.1",
    port: int = 0,
    log_interval: int = 20,
    log_fn=None,
    summary_writer=None,
    stop_event=None,
    on_start=None,
    max_replay_restarts: int = 20,
    max_actor_restarts: int = 5,
    sample_retry_s: float = 2.0,
    actor_throttle_steps_per_s: float = 0.0,
    stall_timeout_s: float = 60.0,
    checkpointer=None,
    checkpoint_interval: int = 200,
    resume: bool = False,
    initial_state: Dict[str, Any] | None = None,
    epoch: int = 0,
    replay_ports_fixed: List[int] | None = None,
    external_replay_endpoints: List[Tuple[str, int]] | None = None,
    spawn_actors: bool = True,
    actor_param_endpoints: List[Tuple[str, int]] | None = None,
    server=None,
    update_program=None,
    reshard_policy=None,
) -> Tuple[OffPolicyDistributedResult, list]:
    """Train off-policy through the distributed replay tier.

    Returns ``(result, history)`` — ``result.params`` is the FULL
    host-side params pytree (actor + critics + targets), directly
    evaluable by the greedy-eval harnesses.

    Durability: with ``checkpointer`` set the learner checkpoints
    params, optimizer state, the paced-update meter and the per-shard
    ingest watermarks (step id = the global transition meter), and the
    replay servers spill ring snapshots under
    ``cfg.replay_snapshot_dir`` (default ``<checkpoint dir>/replay``).
    ``resume=True`` restores the latest checkpoint so the run
    continues with the meter and pacing intact — paired with
    ring-restoring replay respawns, a killed run resumes instead of
    re-warming from zero. ``initial_state`` (a ``_ckpt_state`` dict,
    e.g. a standby's tailed restore) takes precedence over
    ``resume``. The resumed/taken-over reign is fenced:
    ``epoch`` (or the checkpointed epoch + 1, whichever is larger) is
    stamped into publishes and the sample/priority plane so a deposed
    learner's late priority updates are dropped shard-side.

    Topology overrides (the warm-standby takeover path):
    ``external_replay_endpoints`` attaches to an EXISTING replay tier
    instead of spawning one (no respawn supervision — the dead
    primary's spawned shards are respawned by nobody, but ring
    snapshots make even that survivable); ``spawn_actors=False``
    expects the existing env-stepper fleet to fail over via its
    ``param_endpoints`` priority list; ``server`` adopts a pre-bound
    (early) param-plane listener with the fleet already parked on it;
    ``update_program`` reuses a standby's warm-compiled update so the
    takeover pays no XLA compile.

    Live resharding (``cfg.autoscale_reshard``): shard-count proposals
    from a ``ThresholdPolicy`` over the learner's own metrics stream
    (or from ``reshard_policy``, a test-injectable
    ``(metrics, current_shards) -> Optional[int]``) are APPLIED in
    place — the sample plane quiesces, every ring drains a final
    snapshot, the rings are re-dealt bit-exactly across the new shard
    count (``elastic.reshard_rings``), and the replay tier + actor
    fleet respawn under a bumped fencing epoch with the plan committed
    through the ``PlanStore`` stage/commit discipline.
    """
    import multiprocessing as mp
    import os as os_lib

    from actor_critic_algs_on_tensorflow_tpu.algos.common import emit_log
    from actor_critic_algs_on_tensorflow_tpu.distributed.elastic import (
        Autoscaler,
        MembershipView,
        PlanStore,
        ReshardPlan,
        ThresholdPolicy,
        reshard_rings,
        write_ring_snapshot,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.replay import (
        PrioritizedReplayShard,
        ReplayClientGroup,
        ReplaySnapshotter,
        replay_server_main,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.sharding import (
        ShardPlan,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        LearnerServer,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.metrics import (
        LatencyStats,
        device_get_metrics,
    )

    parts = fns.parts
    if parts is None or parts.update_batch is None:
        raise ValueError(
            "run_offpolicy_distributed needs TrainerParts.update_batch "
            "(a trainer factored for wire-sourced batches)"
        )
    cfg = parts.cfg
    algo = algo_of_config(cfg)
    if external_replay_endpoints is not None:
        n_replay_shards = len(external_replay_endpoints)
    _validate_cfg(cfg, n_replay_shards, n_actors)
    # Balanced (remainder-spreading) slices: fleet size need not
    # divide the shard count — the elastic-fleet precondition.
    plan = ShardPlan.balanced(n_replay_shards)
    ctx = mp.get_context("spawn")
    log = lambda msg: print(f"[offpolicy-dist] {msg}", flush=True)

    # Replay-ring snapshot root: explicit knob first, else spilled
    # next to the learner checkpoints so --resume finds both halves of
    # the run's durable state under one directory.
    snap_root = getattr(cfg, "replay_snapshot_dir", "") or ""
    if not snap_root and checkpointer is not None:
        snap_root = os_lib.path.join(checkpointer.directory, "replay")

    # -- replay-server tier -------------------------------------------
    replay_procs: Dict[int, Any] = {}
    replay_ports: Dict[int, int] = {}
    replay_restarts = [0] * n_replay_shards

    # Per-shard snapshot dirs are GENERATION-suffixed after the first
    # live reshard (gen 0 keeps the legacy name so plain resumes find
    # their old cuts): a re-dealt ring must restore from its OWN fresh
    # cut, never a stale pre-reshard chain with the wrong row deal.
    reshard_gen = 0

    def _shard_snap_dir(k: int):
        if not snap_root:
            return None
        name = (
            f"shard-{k}" if reshard_gen == 0
            else f"shard-{k}-g{reshard_gen}"
        )
        return os_lib.path.join(snap_root, name)

    def spawn_replay(k: int, bind_port: int = 0):
        parent = None
        child = None
        if bind_port == 0:
            parent, child = ctx.Pipe()
        p = ctx.Process(
            target=replay_server_main,
            args=(k, child),
            kwargs=dict(
                host="127.0.0.1",
                port=bind_port,
                capacity=cfg.replay_capacity,
                alpha=cfg.per_alpha,
                eps=cfg.per_eps,
                seed=seed + 7919 * (k + 1),
                snapshot_dir=_shard_snap_dir(k),
                snapshot_interval_s=getattr(
                    cfg, "replay_snapshot_interval_s", 30.0
                ),
                snapshot_full_every=getattr(
                    cfg, "replay_snapshot_full_every", 8
                ),
                # Per-tenant ingest metering at the replay tier (see
                # distributed.tenancy) — the same knobs the on-policy
                # learner's ingress gate reads.
                tenancy_budget_mb_s=getattr(
                    cfg, "tenancy_budget_mb_s", 0.0
                ),
                tenancy_budgets=getattr(cfg, "tenancy_budgets", ""),
                tenancy_burst_s=getattr(cfg, "tenancy_burst_s", 2.0),
                server_io_mode=getattr(
                    cfg, "server_io_mode", "reactor"
                ),
            ),
            daemon=True,
            name=f"replay-server-{k}",
        )
        p.start()
        if child is not None:
            child.close()
        if parent is not None:
            if not parent.poll(120.0):
                p.terminate()
                raise RuntimeError(
                    f"replay server {k} never reported its port"
                )
            replay_ports[k] = int(parent.recv())
            parent.close()
        return p

    if external_replay_endpoints is not None:
        # Takeover shape: the tier already exists (spawned — and, while
        # it lived, supervised — by the deposed primary). This learner
        # attaches but does not respawn; ring snapshots cover the case
        # where a shard dies unsupervised.
        shard_endpoints = [
            (h, int(p)) for h, p in external_replay_endpoints
        ]
        for k, (_, p_) in enumerate(shard_endpoints):
            replay_ports[k] = p_
    else:
        for k in range(n_replay_shards):
            if replay_ports_fixed is not None:
                replay_ports[k] = int(replay_ports_fixed[k])
                replay_procs[k] = spawn_replay(k, replay_ports[k])
            else:
                replay_procs[k] = spawn_replay(k)
        shard_endpoints = [
            ("127.0.0.1", replay_ports[k])
            for k in range(n_replay_shards)
        ]

    # -- learner param plane ------------------------------------------
    def _discard(traj, ep, peer):
        # Actors push transitions to the replay tier, never here; a
        # frame landing on the param plane is a mis-wired fleet.
        return False

    if server is None:
        server = LearnerServer(
            _discard, host=host, port=port, epoch=epoch,
            tenant=getattr(cfg, "tenant_id", 0), log=log,
            server_io_mode=getattr(cfg, "server_io_mode", "reactor"),
        )
    else:
        # Adopt a pre-bound listener (the standby's early data plane —
        # the actor fleet is already parked on it).
        server.set_trajectory_sink(_discard)
    accel = jax.devices()[0]
    key = jax.random.PRNGKey(seed)
    k_params, k_updates = jax.random.split(key)

    s = parts.setup
    obs_spec = jax.eval_shape(
        lambda k: s.genv.reset(k, s.env_params)[1], jax.random.PRNGKey(0)
    )
    obs_example = jnp.zeros((1,) + obs_spec.shape[1:], obs_spec.dtype)
    with jax.default_device(accel):
        params, opt_state = jax.jit(parts.init_params)(
            k_params, obs_example
        )

    # -- checkpoint restore (resume / standby takeover) ----------------
    ckpt = initial_state
    if (
        ckpt is None
        and resume
        and checkpointer is not None
        and checkpointer.latest_step() is not None
    ):
        ckpt = checkpointer.restore(_ckpt_state(
            params, opt_state, 0,
            np.zeros(n_replay_shards), np.zeros(n_replay_shards),
            0, 0,
        ))
    updates_done = 0
    restored_meters = None
    if ckpt is not None:
        params = ckpt["params"]
        opt_state = ckpt["opt_state"]
        updates_done = int(np.asarray(ckpt["updates_done"]))
        restored_meters = (
            np.asarray(ckpt["meter_cum"], np.float64),
            np.asarray(ckpt["meter_last"], np.float64),
        )
        # A restored run is a NEW reign: its publishes and priority
        # updates must outrank anything the dead predecessor's
        # processes still have in flight.
        epoch = max(int(epoch), int(np.asarray(ckpt["epoch"])) + 1)
        log(
            f"resumed: env_steps={int(np.asarray(ckpt['env_steps']))} "
            f"updates={updates_done} fencing epoch={epoch}"
        )
    server.set_epoch(epoch)

    # Eval-gated delivery (cfg.delivery): acting-slice publishes park
    # as versioned candidates; an evaluator peer polls + scores them
    # and only a signed PROMOTE reaches the fleet (the controller's
    # default promote path IS ``server.publish`` — no serving tier
    # here). The bootstrap publish below auto-promotes, so actors
    # never block on version 0.
    delivery_ctl = None
    if getattr(cfg, "delivery", False):
        from actor_critic_algs_on_tensorflow_tpu.distributed.delivery import (  # noqa: E501
            DeliveryController,
        )
        from actor_critic_algs_on_tensorflow_tpu.distributed.tenancy import (  # noqa: E501
            PolicyRegistry,
        )

        delivery_ctl = DeliveryController(
            PolicyRegistry().store(getattr(cfg, "tenant_id", 0)),
            server,
            secret=getattr(cfg, "delivery_secret", "") or None,
            verdict_timeout_s=float(
                getattr(cfg, "delivery_timeout_s", 60.0)
            ),
            verdict_quorum=int(getattr(cfg, "delivery_quorum", 1)),
            tenant=int(getattr(cfg, "tenant_id", 0)),
            log=log,
        )
        server.set_delivery_handler(delivery_ctl.handle)

    def publish():
        leaves = [
            np.asarray(x)
            for x in jax.tree_util.tree_leaves(
                jax.device_get(parts.acting_slice(params))
            )
        ]
        if delivery_ctl is not None:
            delivery_ctl.submit(leaves, step=updates_done)
            return
        server.publish(leaves, notify=True)

    publish()  # version 1: actors block on version 0 until this

    # Wire-batch expectations: the flattened Transition layout every
    # sample reply must match (a stale-config fleet's frames are
    # rejected, not crashed on).
    example_tr = offpolicy.Transition(
        obs=jnp.zeros(obs_spec.shape[1:], obs_spec.dtype),
        action=jnp.zeros((s.action_dim,)),
        reward=jnp.zeros(()),
        next_obs=jnp.zeros(obs_spec.shape[1:], obs_spec.dtype),
        terminated=jnp.zeros(()),
    )
    tr_leaves, tr_def = jax.tree_util.tree_flatten(example_tr)
    leaf_specs = [
        (tuple(x.shape), np.dtype(x.dtype)) for x in tr_leaves
    ]

    def batch_ok(leaves: List[np.ndarray]) -> bool:
        if len(leaves) != len(leaf_specs):
            return False
        for a, (shape, dtype) in zip(leaves, leaf_specs):
            if (
                a.ndim != len(shape) + 1
                or a.shape[0] != cfg.batch_size
                or tuple(a.shape[1:]) != shape
                or a.dtype != dtype
            ):
                return False
        return True

    # -- actor fleet ---------------------------------------------------
    learner_host = "127.0.0.1" if host in ("0.0.0.0", "") else host
    actor_procs: Dict[int, Any] = {}
    actor_restarts = [0] * n_actors

    def actor_endpoints(i: int) -> List[Tuple[str, int]]:
        own = plan.shard_of_actor(n_actors, i)
        return [
            shard_endpoints[(own + j) % n_replay_shards]
            for j in range(n_replay_shards)
        ]

    # Per-actor budget shares: actors park at their share instead of
    # free-running past the global budget between learner-side meter
    # refreshes (the meter only advances on sample replies). A
    # RESUMED run's fresh fleet owes only the REMAINING budget — the
    # restored meter already covers the rest, and a full share here
    # would re-collect an entire budget of transitions (min 1: 0
    # means "no cap" to the actor main, and a met-budget resume only
    # needs the fleet parked for the update catch-up tail).
    remaining_steps = total_env_steps
    if ckpt is not None:
        remaining_steps = max(
            0, total_env_steps - int(np.asarray(ckpt["env_steps"]))
        )
    per_actor_steps = max(1, -(-remaining_steps // n_actors))  # ceil

    def spawn_actor(i: int, generation: int):
        p = ctx.Process(
            target=_offpolicy_actor_main,
            args=(
                algo, cfg, i, learner_host, server.port,
                actor_endpoints(i), seed + 100 + i, generation,
                per_actor_steps, actor_throttle_steps_per_s,
                actor_param_endpoints,
            ),
            daemon=True,
            name=f"replay-actor-{i}",
        )
        p.start()
        return p

    if spawn_actors:
        for i in range(n_actors):
            actor_procs[i] = spawn_actor(i, 0)

    group = ReplayClientGroup(
        shard_endpoints, client_id=10_000, retry_s=sample_retry_s,
        epoch=epoch,
    )
    if restored_meters is not None:
        group.restore_meter_state(*restored_meters)
    if on_start is not None:
        on_start(ReplayRunHandles(
            replay_procs, replay_ports, actor_procs, server, group,
        ))

    # -- learner-side replay pipeline (PR 17) --------------------------
    # ``replay_pipeline=False`` keeps the serial draw->update->write-
    # back loop; the pipelined loop prefetches a bounded window of
    # draws across all shards, overlaps batch N+1's device transfer
    # under batch N's update, and coalesces priority write-backs. A
    # warm ``update_program`` (standby takeover) is used as handed
    # over — only a fresh compilation takes the donated second form.
    use_pipeline = bool(getattr(cfg, "replay_pipeline", False))
    prefetch_depth = max(
        1, int(getattr(cfg, "replay_prefetch_depth", 2))
    )
    prio_coalesce = bool(getattr(cfg, "replay_prio_coalesce", True))

    update = (
        update_program if update_program is not None
        else _build_wire_update(parts, accel, donate=use_pipeline)
    )
    # PR-3 sentinel on the wire-update loop: the update program
    # already emits the in-graph ``health_finite`` bit when
    # ``numerics_guards`` is on; roll (params, opt_state) back to a
    # last-good snapshot on a trip instead of training — and
    # checkpointing — NaNs. ``publish`` is a no-op here because the
    # loop publishes after every update burst anyway, so the restored
    # weights reach the fleet within one burst.
    sentinel = None
    if getattr(cfg, "numerics_guards", False):
        from actor_critic_algs_on_tensorflow_tpu.utils import (
            health as health_lib,
        )

        sentinel = health_lib.TrainingHealthSentinel(
            copy_state=jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t)
            ),
            publish=lambda p: None,
            delayed=True,
            log=log,
        )
        sentinel.seed(_Carry(params, opt_state))
    sample_lat = LatencyStats()
    # Learning-parity pacing: the single-process fused iteration does
    # updates_per_iter updates per (num_envs * steps_per_iter)
    # transitions; match that updates-per-transition rate against the
    # GLOBAL ingest meter so a fixed env-step budget buys a comparable
    # number of gradient steps however many actors feed it.
    update_ratio = cfg.updates_per_iter / float(
        max(1, cfg.num_envs * cfg.steps_per_iter)
    )

    def _pace(outstanding: int) -> bool:
        # Issue-time pacing gate, evaluated by the prefetch workers
        # BEFORE drawing: with ``outstanding`` draws already in flight
        # or staged, one more draw is only allowed if a paced update
        # will consume it — so a warming-up or paced-out learner never
        # makes a shard serve a batch that would be discarded.
        ins = group.inserted_total()
        if ins < cfg.warmup_env_steps:
            return False
        target = int(min(ins, total_env_steps) * update_ratio)
        return updates_done + outstanding < target

    pipeline = None
    if use_pipeline:
        from actor_critic_algs_on_tensorflow_tpu.data.replay_pipeline import (  # noqa: E501
            ReplayPipeline,
        )

        pipeline = ReplayPipeline(
            group,
            batch_size=cfg.batch_size,
            beta=cfg.per_beta,
            pace=_pace,
            depth=prefetch_depth,
            coalesce=prio_coalesce,
            device=accel,
            validate=batch_ok,
            part_specs=[
                ((cfg.batch_size,) + shape, dtype)
                for shape, dtype in leaf_specs
            ],
        )
    # Checkpoint pacing: step id = the GLOBAL transition meter, so the
    # learner checkpoints and the replay-ring snapshots (stamped with
    # the same meter via the per-shard ``inserted`` watermark) name
    # compatible cuts of one run. Saves are gated on the meter having
    # ADVANCED — Checkpointer steps are unique, and an idle learner
    # must not burn a save slot re-writing the same cut.
    ckpt_saves = 0
    last_ckpt_updates = updates_done
    last_ckpt_step = -1
    if ckpt is not None:
        # Resume from the latest on-disk KEY, not the state's true
        # meter: catch-up-tail keys bump past the meter, and a new
        # save below the existing latest would leave the stale step
        # as "latest" for the next resume.
        last_ckpt_step = int(np.asarray(ckpt["env_steps"]))
        if checkpointer is not None:
            latest = checkpointer.latest_step()
            if latest is not None:
                last_ckpt_step = max(last_ckpt_step, int(latest))

    def save_checkpoint(inserted: int) -> None:
        nonlocal ckpt_saves, last_ckpt_updates, last_ckpt_step
        nonlocal params, opt_state
        if checkpointer is None or (
            inserted <= last_ckpt_step
            and updates_done <= last_ckpt_updates
        ):
            return
        # Step keys must be unique and increasing, but the transition
        # meter SATURATES at the budget while the paced learner still
        # catches up on updates — bump past the last key there so the
        # catch-up tail (and its final updates_done) stays
        # checkpointed instead of a resume redoing it. The STATE's
        # env_steps field keeps the true meter; only the key bumps.
        step = max(int(inserted), last_ckpt_step + 1)
        if sentinel is not None:
            # A checkpoint must never capture a state whose own update
            # went unchecked (delayed guard mode) — resolve the
            # pending verdict first.
            carry = sentinel.flush(_Carry(params, opt_state))
            params, opt_state = carry.params, carry.opt_state
        cum, last_seen = group.meter_state()
        checkpointer.save(step, _ckpt_state(
            params, opt_state, updates_done, cum, last_seen,
            inserted, epoch,
        ))
        ckpt_saves += 1
        last_ckpt_updates = updates_done
        last_ckpt_step = step

    server_restarts = 0
    actor_respawns = 0
    batch_rejects = 0
    history: list = []
    # Device-side metrics of the newest update; materialized ONLY at
    # log boundaries (one transfer for the whole dict) — the old
    # per-update ``{k: float(v)}`` forced a host sync every iteration.
    m_dev_last = None
    ep_returns_sum, ep_count = 0.0, 0
    t_last_log = time.perf_counter()
    inserted_last_log = 0
    it = 0

    def check_procs():
        nonlocal server_restarts, actor_respawns
        for k in range(n_replay_shards):
            # .get: the takeover shape attaches to an EXISTING tier /
            # fleet (external_replay_endpoints, spawn_actors=False) —
            # processes this learner did not spawn are not its to
            # supervise.
            p = replay_procs.get(k)
            if p is None or p.is_alive():
                continue
            replay_restarts[k] += 1
            server_restarts += 1
            if replay_restarts[k] > max_replay_restarts:
                raise RuntimeError(
                    f"replay server {k} died {replay_restarts[k]} "
                    f"times; giving up"
                )
            log(
                f"replay server {k} died (exit {p.exitcode}); "
                f"respawning on port {replay_ports[k]}"
            )
            # Same port (the fleet's endpoint lists are immutable);
            # the respawn needs no port report, so it never blocks
            # the learner loop.
            replay_procs[k] = spawn_replay(k, bind_port=replay_ports[k])
            if pipeline is not None:
                # A prefetch worker may be blocked mid-draw against
                # the dead process, riding out its retry deadline.
                # Abort it NOW (lock-free): the worker drops the draw
                # (no reply ever reached the meter reconciliation, so
                # nothing is double-counted) and reissues against the
                # respawn.
                group.interrupt(k)
            # Drop this learner's half-open link to the dead process
            # NOW: left alone, the first post-restore draw would fault
            # on it, burn part of the short per-draw retry deadline,
            # and be counted as a failover against a shard that is
            # back and serving.
            group.rehome(k)
        for i in range(n_actors):
            if i in retired_actors:
                # An autoscaler scale-down is a deliberate leave, not a
                # death — the supervisor must not fight the policy by
                # respawning what it just retired.
                continue
            p = actor_procs.get(i)
            if p is None or p.is_alive():
                continue
            actor_restarts[i] += 1
            actor_respawns += 1
            if actor_restarts[i] > max_actor_restarts:
                raise RuntimeError(
                    f"actor {i} died {actor_restarts[i]} times; giving up"
                )
            log(f"actor {i} died (exit {p.exitcode}); respawning")
            actor_procs[i] = spawn_actor(i, actor_restarts[i])

    # -- elastic fleet: live membership + optional autoscaler ----------
    # MembershipView diffs the param plane's hello registry each log
    # tick, so joins/leaves/rejoins and the fleet count ride the
    # metrics stream. The autoscaler (off by default — determinism for
    # fixed-budget runs) resizes the SUPERVISED fleet between
    # [min_actors, n_actors]: a scale-down terminates the highest-id
    # actors (their shard slices are the remainder tail, so the move
    # count is minimal) and marks them retired so check_procs() does
    # not respawn them; a scale-up un-retires and respawns in place.
    retired_actors: set = set()
    membership = MembershipView(server)
    autoscaler = None
    if spawn_actors and getattr(cfg, "autoscaler_enabled", False):
        autoscaler = Autoscaler(
            ThresholdPolicy(),
            min_actors=max(
                1, int(getattr(cfg, "autoscaler_min_actors", 1))
            ),
            max_actors=max(1, min(
                n_actors,
                int(getattr(cfg, "autoscaler_max_actors", n_actors)),
            )),
            cooldown_s=float(
                getattr(cfg, "autoscaler_cooldown_s", 30.0)
            ),
        )

    def apply_autoscale(metrics: Dict[str, float]) -> None:
        nonlocal actor_respawns
        if autoscaler is None:
            return
        live = n_actors - len(retired_actors)
        target = autoscaler.evaluate(live, metrics)
        if target is None or target == live:
            return
        if target < live:
            for i in sorted(actor_procs, reverse=True):
                if live <= target:
                    break
                if i in retired_actors:
                    continue
                retired_actors.add(i)
                p = actor_procs.get(i)
                if p is not None and p.is_alive():
                    p.terminate()
                live -= 1
            log(f"autoscaler: scaled down to {live} actors")
        else:
            for i in sorted(retired_actors):
                if live >= target:
                    break
                retired_actors.discard(i)
                actor_procs[i] = spawn_actor(i, actor_restarts[i])
                actor_respawns += 1
                live += 1
            log(f"autoscaler: scaled up to {live} actors")

    # -- live resharding (cfg.autoscale_reshard) -----------------------
    # ThresholdPolicy shard-count proposals APPLIED in place: quiesce
    # the sample plane, drain every ring to a final snapshot, re-deal
    # bit-exactly across the new count (elastic.reshard_rings), then
    # respawn the replay tier + actor fleet under a bumped fencing
    # epoch with the plan committed through the PlanStore stage/commit
    # discipline. Requires ring snapshots (the rings travel via final
    # cuts) and a self-spawned fleet (a takeover learner does not own
    # the tier it attached to).
    reshard_count = 0
    resharder = reshard_policy
    if getattr(cfg, "autoscale_reshard", False):
        if not snap_root:
            raise ValueError(
                "autoscale_reshard needs replay-ring snapshots: set "
                "cfg.replay_snapshot_dir or pass a checkpointer"
            )
        if external_replay_endpoints is not None or not spawn_actors:
            raise ValueError(
                "autoscale_reshard needs a self-spawned replay tier "
                "and actor fleet (not the takeover topology)"
            )
        if resharder is None:
            _reshard_pol = ThresholdPolicy()
            _reshard_cool = float(
                getattr(cfg, "autoscaler_cooldown_s", 30.0)
            )
            _reshard_last = [float("-inf")]
            _reshard_max = max(1, n_actors, n_replay_shards)

            def resharder(metrics, current):
                now = time.monotonic()
                if now - _reshard_last[0] < _reshard_cool:
                    return None
                d = _reshard_pol.decide(metrics)
                if d == 0:
                    return None
                target = max(1, min(
                    _reshard_max,
                    current * 2 if d > 0 else current // 2,
                ))
                if target == current:
                    return None
                _reshard_last[0] = now
                return target
    elif resharder is not None and not snap_root:
        raise ValueError(
            "reshard_policy needs replay-ring snapshots: set "
            "cfg.replay_snapshot_dir or pass a checkpointer"
        )

    plan_store = (
        PlanStore(os_lib.path.join(snap_root, "plans"))
        if resharder is not None and snap_root else None
    )

    def do_reshard(new_count: int) -> None:
        nonlocal n_replay_shards, plan, shard_endpoints, group
        nonlocal pipeline, epoch, reshard_gen, reshard_count
        nonlocal replay_restarts, actor_respawns
        old_count = n_replay_shards
        log(
            f"reshard: {old_count} -> {new_count} shards (quiescing "
            f"the sample plane)"
        )
        # 1) Quiesce: flush held priority tokens while the shards are
        #    alive, then the group's ROLE_LEARNER goodbye makes every
        #    shard spill a final ring snapshot and drain.
        if pipeline is not None:
            pipeline.close(flush=True)
        group.close()
        deadline = time.monotonic() + 30.0
        for k, p in list(replay_procs.items()):
            p.join(timeout=max(0.1, deadline - time.monotonic()))
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
        replay_procs.clear()
        # 2) Restore every old ring locally from its final cut and
        #    re-deal under the NEW reign (the reshard IS the epoch
        #    bump — deposed late priority frames are fenced).
        old_shards = []
        for k in range(old_count):
            sh = PrioritizedReplayShard(
                cfg.replay_capacity, alpha=cfg.per_alpha,
                eps=cfg.per_eps, seed=seed + 7919 * (k + 1),
            )
            snap = ReplaySnapshotter(_shard_snap_dir(k), log=log)
            if snap.available():
                sh.begin_restore()
                snap.restore(sh)
                sh.end_restore()
            old_shards.append(sh)
        epoch += 1
        reshard_gen += 1
        states = reshard_rings(
            old_shards, new_count, epoch=epoch,
            base_seed=seed + 104_729 * reshard_gen,
        )
        for k, state in enumerate(states):
            write_ring_snapshot(_shard_snap_dir(k), state)
        # 3) Respawn the tier on the fresh generation dirs; the new
        #    servers restore their re-dealt rings through the normal
        #    snapshot boot path.
        n_replay_shards = new_count
        replay_restarts = [0] * new_count
        plan = ShardPlan.balanced(new_count)
        replay_ports.clear()
        for k in range(new_count):
            replay_procs[k] = spawn_replay(k)
        shard_endpoints = [
            ("127.0.0.1", replay_ports[k]) for k in range(new_count)
        ]
        # 4) Durable commit: stage -> commit so a SIGKILL at any point
        #    resumes either the old topology or the new one, never a
        #    hybrid.
        if plan_store is not None:
            rp = ReshardPlan(
                epoch=epoch,
                shard_count=new_count,
                endpoints=tuple(shard_endpoints),
                assignment={
                    i: plan.shard_of_actor(n_actors, i)
                    for i in range(n_actors)
                },
            )
            plan_store.stage(rp)
            plan_store.commit(rp)
        # 5) Fence the param plane under the new reign, rebuild the
        #    sample plane (fresh meters reconstruct the global
        #    transition total from the restored cuts), and re-point
        #    the actor fleet at the new endpoints.
        server.set_epoch(epoch)
        group = ReplayClientGroup(
            shard_endpoints, client_id=10_000, retry_s=sample_retry_s,
            epoch=epoch,
        )
        if use_pipeline:
            pipeline = ReplayPipeline(
                group,
                batch_size=cfg.batch_size,
                beta=cfg.per_beta,
                pace=_pace,
                depth=prefetch_depth,
                coalesce=prio_coalesce,
                device=accel,
                validate=batch_ok,
                part_specs=[
                    ((cfg.batch_size,) + shape, dtype)
                    for shape, dtype in leaf_specs
                ],
            )
        for i, p in list(actor_procs.items()):
            if p.is_alive():
                p.terminate()
            p.join(timeout=5.0)
        for i in range(n_actors):
            if i in retired_actors:
                continue
            actor_procs[i] = spawn_actor(
                i, actor_restarts[i] + reshard_gen
            )
            actor_respawns += 1
        publish()
        reshard_count += 1
        log(
            f"reshard complete: {new_count} shards under fencing "
            f"epoch {epoch}"
        )

    # The run is done when the ingest budget is met AND the learner
    # has caught up to its paced update target. A shard SIGKILL can
    # leave the budget meter permanently short: transitions the dead
    # shard ingested after the learner's last draw died with its ring
    # unseen, so the cumulative meter stalls a bounded window below
    # the budget while every actor has already parked at its share.
    # The stall guard breaks the loop once NEITHER the meter nor the
    # update count has moved for ``stall_timeout_s`` — armed only
    # after the first ingest so actor compile time can't trip it.
    target_total = paced_update_target(
        total_env_steps, cfg.warmup_env_steps, update_ratio
    )
    last_progress_t = None
    progress_mark = (-1, -1)
    # The restore-aware stall hold's last view: holding is bounded by
    # VISIBLE load progress — a shard that died mid-restore freezes
    # its cached fraction, and holding on a frozen view forever would
    # turn the dead-run abort into a hang.
    stall_hold_view = None
    # Whether teardown may DRAIN the replay tier (the group's
    # ROLE_LEARNER goodbye makes every shard flush a final snapshot
    # and exit). True only for the orderly exits — budget complete, or
    # a coordinated stop (--preempt-save wants the final cuts). An
    # ABNORMAL exit (stall-guard abort, crash) must leave the tier up:
    # in the warm-standby topology those shards are the very thing the
    # takeover attaches to, and nobody respawns them.
    drain_tier = False
    try:
        while True:
            if stop_event is not None and stop_event.is_set():
                log("stop event set; shutting down")
                drain_tier = True
                break
            inserted = group.inserted_total()
            if inserted >= total_env_steps and (
                updates_done >= target_total
            ):
                drain_tier = True
                break
            did_work = False
            if pipeline is not None:
                # Pipelined burst: the prefetch workers own the draw
                # gate (``_pace`` at issue time), so the runner only
                # mirrors the serial gate to pick the idle path fast —
                # gate-closed implies no draw is in flight (pacing
                # capped them) and nothing is staged.
                target_updates = int(
                    min(inserted, total_env_steps) * update_ratio
                )
                gate_open = (
                    inserted >= cfg.warmup_env_steps
                    and updates_done < target_updates
                )
                if gate_open:
                    for _ in range(max(1, cfg.updates_per_iter)):
                        t0 = time.perf_counter()
                        pb = pipeline.get(timeout=0.25)
                        sample_lat.add_s(time.perf_counter() - t0)
                        if pb is None:
                            break
                        b = jax.tree_util.tree_unflatten(
                            tr_def, pb.leaves
                        )
                        ukey = parts.update_key_fn(
                            jax.random.fold_in(k_updates, updates_done)
                        )
                        params, opt_state, m_dev, td = update(
                            params, opt_state, b, pb.weights, ukey
                        )
                        updates_done += 1
                        # Counted first, THEN the credit frees: the
                        # dispatch above is async, so the next draw
                        # still overlaps this update's compute; the
                        # slot itself stays pinned until a worker
                        # blocks on m_dev (never donated).
                        pipeline.mark_consumed(pb, m_dev)
                        if sentinel is not None:
                            carry = sentinel.after_step(
                                updates_done - 1,
                                _Carry(params, opt_state), m_dev,
                            )
                            params, opt_state = (
                                carry.params, carry.opt_state
                            )
                        pipeline.write_back(pb.sampled, td)
                        m_dev_last = m_dev
                        did_work = True
                    inserted = group.inserted_total()
                if did_work:
                    # One coalesced prio frame per shard per burst:
                    # staleness is bounded by the burst length plus
                    # the one-step TD token delay.
                    pipeline.flush_priorities()
                    publish()
                else:
                    group.poll_meters()
                    time.sleep(0.02)
            else:
                for _ in range(max(1, cfg.updates_per_iter)):
                    # Gate BEFORE drawing: a warming-up or paced-out
                    # learner must not make a shard serve (and ship) a
                    # batch it will discard — the idle path refreshes
                    # its meters with the zero-row status probe
                    # instead.
                    target_updates = int(
                        min(inserted, total_env_steps) * update_ratio
                    )
                    if (
                        inserted < cfg.warmup_env_steps
                        or updates_done >= target_updates
                    ):
                        break
                    t0 = time.perf_counter()
                    batch = group.sample(cfg.batch_size, cfg.per_beta)
                    sample_lat.add_s(time.perf_counter() - t0)
                    inserted = group.inserted_total()
                    if batch is None:
                        break
                    if not batch_ok(batch.leaves):
                        batch_rejects += 1
                        continue
                    b = jax.tree_util.tree_unflatten(
                        tr_def,
                        [
                            jax.device_put(x, accel)
                            for x in batch.leaves
                        ],
                    )
                    w = jax.device_put(batch.weights, accel)
                    ukey = parts.update_key_fn(
                        jax.random.fold_in(k_updates, updates_done)
                    )
                    params, opt_state, m_dev, td = update(
                        params, opt_state, b, w, ukey
                    )
                    if sentinel is not None:
                        # Delayed mode checks the PREVIOUS update's
                        # (long retired) guard bit — no stall on the
                        # dispatch above; a trip rolls (params,
                        # opt_state) back and the next publish
                        # re-points the fleet.
                        carry = sentinel.after_step(
                            updates_done, _Carry(params, opt_state),
                            m_dev,
                        )
                        params, opt_state = (
                            carry.params, carry.opt_state
                        )
                    group.update_priorities(
                        batch.shard_idx,
                        batch.ids,
                        batch.indices,
                        np.asarray(td),
                    )
                    # Metrics stay DEVICE-side until a log tick needs
                    # them: per-update float() materialization was a
                    # hidden host sync on every iteration.
                    m_dev_last = m_dev
                    updates_done += 1
                    did_work = True
                if did_work:
                    publish()
                else:
                    group.poll_meters()
                    time.sleep(0.02)
            inserted = group.inserted_total()
            if (
                checkpoint_interval > 0
                and updates_done - last_ckpt_updates >= checkpoint_interval
            ):
                save_checkpoint(inserted)
            if inserted > 0:
                now = time.perf_counter()
                mark = (inserted, updates_done)
                if mark != progress_mark or last_progress_t is None:
                    progress_mark, last_progress_t = mark, now
                elif now - last_progress_t > stall_timeout_s:
                    # Diagnosis before verdict: a respawned shard mid
                    # ring-restore serves nothing (draws answer meta-
                    # only with the load fraction), which looks exactly
                    # like the killed-shard stall from the meter's side.
                    # The durability meta disambiguates — a restoring
                    # shard is "loading", not dead, so hold the stall
                    # clock instead of ending the run under it.
                    restoring = [
                        (k, f)
                        for k, f in enumerate(group.shard_restore_frac)
                        if f < 1.0
                    ]
                    if restoring and restoring != stall_hold_view:
                        # Load progress is visible since the last
                        # hold: genuinely restoring, not dead.
                        stall_hold_view = restoring
                        log(
                            "stall guard held: "
                            + ", ".join(
                                f"shard {k} restoring (ring "
                                f"{f * 100.0:.0f}% loaded)"
                                for k, f in restoring
                            )
                        )
                        last_progress_t = now
                    else:
                        if restoring:
                            log(
                                "restoring shard(s) made no load "
                                "progress for a full stall window — "
                                "treating them as dead"
                            )
                        ages = [
                            a for a in group.shard_snapshot_age if a >= 0
                        ]
                        bound = (
                            f"bounded by the newest snapshot age, "
                            f"<= {max(ages):.0f}s of ingest"
                            if ages else "unbounded without snapshots"
                        )
                        log(
                            f"no ingest or update progress for "
                            f"{stall_timeout_s:.0f}s at env_steps="
                            f"{inserted}/{total_env_steps}, updates="
                            f"{updates_done}/{target_total}; stopping "
                            f"(transitions lost with a killed shard "
                            f"leave the meter short by a window "
                            f"{bound})"
                        )
                        break
            check_procs()
            it += 1
            if it % max(1, log_interval) == 0:
                rs, rc = group.drain_episode_stats()
                ep_returns_sum += rs
                ep_count += rc
                now = time.perf_counter()
                rate = (inserted - inserted_last_log) / max(
                    now - t_last_log, 1e-9
                )
                t_last_log, inserted_last_log = now, inserted
                m = (
                    device_get_metrics(m_dev_last)
                    if m_dev_last is not None else {}
                )
                m.update(group.stats())
                m.update(sample_lat.summary(REPLAY_SAMPLE))
                m.update(server.metrics())
                if pipeline is not None:
                    m.update(pipeline.metrics())
                m[REPLAY + "updates"] = updates_done
                m[REPLAY + "server_restarts"] = server_restarts
                m[REPLAY + "actor_respawns"] = actor_respawns
                m[REPLAY + "batch_rejects"] = batch_rejects + (
                    pipeline.rejects if pipeline is not None else 0
                )
                m[REPLAY + "shards"] = n_replay_shards
                m[REPLAY + "ckpt_saves"] = ckpt_saves
                m[REPLAY + "fence_epoch"] = epoch
                m[REPLAY + "shards_restoring"] = sum(
                    1 for f in group.shard_restore_frac if f < 1.0
                )
                m[REPLAY + "ingest_tps"] = rate
                membership.refresh()
                m.update(membership.metrics())
                if autoscaler is not None:
                    apply_autoscale(m)
                    m.update(autoscaler.metrics())
                if resharder is not None:
                    target_shards = resharder(m, n_replay_shards)
                    if target_shards:
                        do_reshard(int(target_shards))
                    m[REPLAY + "reshards"] = reshard_count
                if delivery_ctl is not None:
                    # The log tick doubles as the delivery watchdog:
                    # judge-less candidates past the verdict timeout
                    # are quarantined here (evaluator died mid-verdict
                    # — the fleet keeps serving last-good).
                    delivery_ctl.check_timeouts()
                    m.update(delivery_ctl.metrics())
                m["episodes"] = ep_count
                m["avg_return"] = (
                    ep_returns_sum / ep_count if ep_count else 0.0
                )
                ep_returns_sum, ep_count = 0.0, 0
                m["steps_per_sec"] = rate
                emit_log(inserted, m, history, summary_writer, log_fn)
    finally:
        if pipeline is not None:
            # Stop the prefetchers before anything else touches the
            # sample plane: an orderly exit (drain_tier) flushes the
            # held TD tokens into final coalesced frames while the
            # shards are alive; an abnormal exit ABORTS in-flight
            # draws without goodbye frames — the takeover drain — so
            # the tier stays up for the next reign to attach to.
            try:
                pipeline.close(flush=drain_tier)
            except Exception as e:
                log(
                    f"pipeline close failed ({type(e).__name__}: {e})"
                )
        # Final checkpoint first (the --preempt-save contract: a
        # stop_event exit must be resumable end-to-end), while every
        # shard is still up to answer the meter poll.
        if checkpointer is not None:
            try:
                save_checkpoint(group.inserted_total())
            except Exception as e:
                log(
                    f"final checkpoint failed "
                    f"({type(e).__name__}: {e})"
                )
        # Orderly teardown: the param plane's KIND_CLOSE tells actors
        # to exit; the GROUP's KIND_CLOSE goodbyes (this peer hello'd
        # ROLE_LEARNER) tell each replay server to flush a final ring
        # snapshot and drain — so a coordinated shutdown is resumable,
        # not just the chaos path. SIGTERM is the backstop for a
        # server that never saw the goodbye; it drains the same way.
        try:
            server.close()
        except Exception:
            pass
        if not drain_tier:
            # Abnormal exit: drop the sample links WITHOUT goodbyes (a
            # reset link sends no KIND_CLOSE) so the shards stay up
            # for a standby takeover or a resume against the live
            # tier. Self-spawned shards still drain below via their
            # teardown SIGTERM.
            group.rehome()
        group.close()
        deadline = time.monotonic() + 10.0
        for p in actor_procs.values():
            p.join(timeout=max(0.1, deadline - time.monotonic()))
        for p in actor_procs.values():
            if p.is_alive():
                p.terminate()
        drain_deadline = time.monotonic() + 15.0
        for p in replay_procs.values():
            p.join(timeout=max(0.1, drain_deadline - time.monotonic()))
        for p in replay_procs.values():
            if p.is_alive():
                p.terminate()
        for p in list(actor_procs.values()) + list(
            replay_procs.values()
        ):
            p.join(timeout=5.0)

    result = OffPolicyDistributedResult(
        params=jax.device_get(params),
        opt_state=jax.device_get(opt_state),
        updates=updates_done,
        env_steps=group.inserted_total(),
    )
    log(
        f"done: env_steps={result.env_steps} updates={result.updates} "
        f"(draws={group.draws}, failovers={group.sample_failovers})"
    )
    return result, history


def run_offpolicy_standby(
    fns: offpolicy.OffPolicyFns,
    *,
    checkpointer,
    primary_host: str,
    primary_port: int,
    replay_endpoints: List[Tuple[str, int]],
    total_env_steps: int,
    n_actors: int = 2,
    seed: int = 0,
    host: str = "127.0.0.1",
    port: int = 0,
    redirect=None,
    heartbeat_interval_s: float = 0.5,
    takeover_deadline_s: float = 3.0,
    never_seen_grace_s: float | None = None,
    warm_compile: bool = True,
    log_interval: int = 20,
    log_fn=None,
    summary_writer=None,
    checkpoint_interval: int = 200,
    stop_event=None,
    on_ready=None,
    on_serving=None,
    standby_id: int = 0,
    peers: List[Tuple[str, int]] | None = None,
    stall_timeout_s: float = 60.0,
    sample_retry_s: float = 2.0,
) -> Tuple[OffPolicyDistributedResult, list] | None:
    """Warm-standby learner for the off-policy (Ape-X) topology.

    The IMPALA control plane (PRs 4/10), grafted onto the replay tier:
    while the primary at ``primary_host:primary_port`` is healthy this
    process (a) warm-compiles the wire update program (``warm_compile``
    executes one throwaway zero-batch update so XLA compilation is
    PAID, not just scheduled), (b) tails the primary's checkpoint
    directory (``controlplane.CheckpointTailer`` — each landed step is
    restored into memory, off the takeover's critical path), (c) tails
    its acting-slice publish stream (``ParamTailer``) and re-publishes
    into its OWN pre-bound listener, so env-stepper actors whose
    ``param_endpoints`` priority list names this standby keep acting
    on live weights the moment they lose the primary, and (d) watches
    liveness over KIND_PING/PONG (``PrimaryMonitor``).

    On primary death the standby (after winning the ``peers`` election
    when there is a quorum — ``StandbyElection``, rank-ordered, same
    semantics as the IMPALA quorum) re-enters
    ``run_offpolicy_distributed`` with the tailed checkpoint as
    ``initial_state``, ATTACHING to the existing replay tier
    (``replay_endpoints``) and actor fleet instead of spawning its
    own, adopting its early listener with the fleet already parked on
    it, and bumping the fencing epoch — the deposed learner's late
    ``KIND_PRIO_UPDATE``s and publishes are dropped tier-wide. Replay
    shards lost with the primary (it supervised them) restore their
    rings from snapshots when respawned externally; the takeover
    learner's transition meter continues from the checkpointed
    per-shard watermarks either way.

    Takeover staleness is bounded by the CHECKPOINT interval, not the
    publish interval: off-policy publishes carry only the acting
    slice (actor + obs stats), so unlike the IMPALA standby there is
    no full-params graft — critics and targets exist nowhere fresher
    than the checkpoint, and grafting a fresher actor onto older
    critics would hand TD3/SAC a target mismatch no fence catches.
    The tailed publishes still serve the FLEET (acting needs only the
    slice); only the training state resumes from the checkpoint.

    Returns ``None`` without taking over when the primary finishes
    cleanly (or the tailed checkpoint already covers the env-step
    budget — the lost-KIND_CLOSE race), else the takeover run's
    ``(result, history)``."""
    from actor_critic_algs_on_tensorflow_tpu.distributed.controlplane import (  # noqa: E501
        CheckpointTailer,
        ParamTailer,
        PrimaryMonitor,
        StandbyElection,
    )
    from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
        _fenced_redirect,
        _peer_epoch_knowledge,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        LearnerServer,
        epoch_of,
    )

    parts = fns.parts
    if parts is None or parts.update_batch is None:
        raise ValueError(
            "run_offpolicy_standby needs TrainerParts.update_batch "
            "(a trainer factored for wire-sourced batches)"
        )
    cfg = parts.cfg
    n_replay_shards = len(replay_endpoints)
    _validate_cfg(cfg, n_replay_shards, n_actors)
    if peers is not None and len(peers) > 1:
        election = StandbyElection(
            standby_id, peers,
            probe_timeout_s=1.0, probe_attempts=3,
        )
    else:
        election = None
    _slog = lambda msg: print(
        f"[offpolicy-standby-{standby_id}] {msg}", flush=True
    )

    accel = jax.devices()[0]
    s = parts.setup
    obs_spec = jax.eval_shape(
        lambda k: s.genv.reset(k, s.env_params)[1], jax.random.PRNGKey(0)
    )
    obs_example = jnp.zeros((1,) + obs_spec.shape[1:], obs_spec.dtype)
    params_shape, opt_shape = jax.eval_shape(
        parts.init_params, jax.random.PRNGKey(0), obs_example
    )
    template = _ckpt_state(
        params_shape, opt_shape, 0,
        np.zeros(n_replay_shards), np.zeros(n_replay_shards), 0, 0,
    )

    update_program = None
    if warm_compile:
        # Pay the XLA compile of the SAME jitted update the takeover
        # run will pick, driven with a zero batch of the real wire
        # shapes — every second shaved here comes off the gap.
        update_program = _build_wire_update(parts, accel)
        with jax.default_device(accel):
            w_params, w_opt = jax.jit(parts.init_params)(
                jax.random.PRNGKey(0), obs_example
            )
        zero_b = offpolicy.Transition(
            obs=jnp.zeros(
                (cfg.batch_size,) + obs_spec.shape[1:], obs_spec.dtype
            ),
            action=jnp.zeros((cfg.batch_size, s.action_dim)),
            reward=jnp.zeros((cfg.batch_size,)),
            next_obs=jnp.zeros(
                (cfg.batch_size,) + obs_spec.shape[1:], obs_spec.dtype
            ),
            terminated=jnp.zeros((cfg.batch_size,)),
        )
        out = update_program(
            w_params, w_opt, zero_b,
            jnp.ones((cfg.batch_size,)), parts.update_key_fn(
                jax.random.PRNGKey(1)
            ),
        )
        jax.block_until_ready(out)
        del w_params, w_opt, zero_b, out
        _slog("wire update program compiled (warm)")

    # Early data plane: bind NOW so actors that lose the primary land
    # here via their param_endpoints priority walk and pay their
    # reconnect backoff BEFORE the failover; their fetches serve the
    # tailed acting weights re-published below. (Transition pushes
    # never ride this plane — the absorb sink is a mis-wire backstop.)
    server = LearnerServer(
        lambda traj, ep: True, host=host, port=port,
        server_io_mode=getattr(cfg, "server_io_mode", "reactor"),
        log=lambda msg: print(
            f"[offpolicy-standby-{standby_id}-server] {msg}", flush=True
        ),
    )
    if on_serving is not None:
        try:
            on_serving(host, server.port)
        except BaseException:
            server.close()
            raise

    def _republish(version, leaves):
        # Stamped with the REIGN the tailed publish came from, so
        # parked actors fetch weights whose version already carries
        # the right fencing epoch.
        server.set_epoch(epoch_of(version))
        server.publish(leaves)

    cur_host, cur_port = primary_host, primary_port
    min_epoch = 0
    seen_epoch = 0
    tailer = None
    ptailer = None
    outcome = None
    monitor = None

    def _make_ptailer(phost, pport, floor):
        return ParamTailer(
            phost, pport,
            standby_id=standby_id,
            min_epoch=floor,
            poll_interval_s=max(heartbeat_interval_s, 0.25),
            on_params=_republish,
        )

    try:
        ptailer = _make_ptailer(cur_host, cur_port, min_epoch)
        tailer = CheckpointTailer(
            checkpointer, template, standby_id=standby_id, log=_slog
        )
        while True:
            monitor = PrimaryMonitor(
                cur_host, cur_port,
                interval_s=heartbeat_interval_s,
                deadline_s=takeover_deadline_s,
                never_seen_grace_s=never_seen_grace_s,
                standby_id=standby_id,
                epoch=min_epoch,
                log=_slog,
            )
            try:
                if on_ready is not None:
                    on_ready(monitor)
                outcome = monitor.wait_outcome(stop_event=stop_event)
            finally:
                monitor.close()
            seen_epoch = max(
                seen_epoch,
                min_epoch,
                monitor.epoch_seen,
                epoch_of(ptailer.newest()[0]),
                _peer_epoch_knowledge([server]),
            )
            if outcome != "down":
                break  # finished / stopped: stand down, no takeover
            if election is not None:
                winner = election.elect(stop_event)
                if stop_event is not None and stop_event.is_set():
                    outcome = None
                    break
                if winner != standby_id:
                    # Lost: re-arm as a follower of the winner; its
                    # reign is seen_epoch + 1, so anything older on
                    # the re-pointed param tail is a deposed
                    # learner's late frame — fenced.
                    cur_host, cur_port = peers[winner]
                    min_epoch = seen_epoch + 1
                    ptailer.close()
                    ptailer = _make_ptailer(
                        cur_host, cur_port, min_epoch
                    )
                    _slog(
                        f"following elected rank {winner} at "
                        f"{cur_host}:{cur_port} (fencing epoch >= "
                        f"{min_epoch})"
                    )
                    continue
            break  # down, and this standby won (or runs solo)
    except BaseException:
        server.close()
        raise
    finally:
        # One last synchronous poll: the primary's dying save may have
        # landed between our last poll and its death.
        if tailer is not None:
            tailer.close(final_poll=True)
        if ptailer is not None:
            ptailer.close()
    if outcome != "down":
        server.close()
        _slog(
            f"no takeover ({outcome or 'stopped before any outcome'})"
        )
        return None

    try:
        step_id, state = tailer.newest()
        if state is not None:
            # A primary that finished its budget and exited looks like
            # a crashed one whenever the orderly KIND_CLOSE loses a
            # wire race; the checkpointed PROGRESS is race-free. Both
            # halves of "done" must hold — the transition meter AND
            # the paced update target: an Ape-X meter saturates at the
            # budget long before the paced learner's catch-up tail,
            # and standing down on the meter alone would abandon a
            # primary killed mid-catch-up.
            done_steps = int(np.asarray(state["env_steps"]))
            done_updates = int(np.asarray(state["updates_done"]))
            target = paced_update_target(
                total_env_steps, cfg.warmup_env_steps,
                cfg.updates_per_iter / float(
                    max(1, cfg.num_envs * cfg.steps_per_iter)
                ),
            )
            if done_steps >= total_env_steps and (
                done_updates >= target
            ):
                server.close()
                _slog(
                    f"tailed checkpoint covers the whole run "
                    f"(env_steps {done_steps} >= {total_env_steps}, "
                    f"updates {done_updates} >= {target}); training "
                    f"finished — standing down"
                )
                return None
        new_epoch = seen_epoch + 1
        _slog(
            f"TAKEOVER ({monitor.reason}) at fencing epoch {new_epoch}: "
            + (
                f"resuming from tailed checkpoint step {step_id} "
                f"(already restored in memory)"
                if state is not None
                else "no checkpoint ever landed; starting from init"
            )
            + f", attaching to {n_replay_shards} replay shard(s)"
        )
        fenced = _fenced_redirect(redirect, new_epoch, standby_id)
        if fenced is not None:
            fenced(host, server.port)
        return run_offpolicy_distributed(
            fns,
            total_env_steps=total_env_steps,
            seed=seed,
            n_replay_shards=n_replay_shards,
            n_actors=n_actors,
            host=host,
            port=server.port,
            log_interval=log_interval,
            log_fn=log_fn,
            summary_writer=summary_writer,
            stop_event=stop_event,
            sample_retry_s=sample_retry_s,
            stall_timeout_s=stall_timeout_s,
            checkpointer=checkpointer,
            checkpoint_interval=checkpoint_interval,
            initial_state=state,
            epoch=new_epoch,
            external_replay_endpoints=replay_endpoints,
            spawn_actors=False,
            server=server,
            update_program=update_program,
        )
    except BaseException:
        # The takeover prologue raised before the runner's teardown
        # could own the adopted listener: release it (close is
        # idempotent) so a supervisor retry never hits EADDRINUSE.
        server.close()
        raise
