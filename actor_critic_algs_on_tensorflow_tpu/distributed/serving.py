"""SEED-style central-inference serving tier.

The classic IMPALA topology (Espeholt et al. 2018) puts a full policy
copy on every actor; SEED RL (Espeholt et al. 2019) showed that moving
inference onto the central accelerator and BATCHING ``act()`` across
hundreds of connections is both faster and the natural shape of a
serving system — request/response, dynamic batching, per-connection
provenance, a load balancer in front. This module is that tier,
grafted onto the existing training runtime:

  - Actors become **env shims** (``env_shim_actor_main``): a thin env
    loop with NO policy, no params, no jitted rollout program. Each
    step it ships ``[obs, reward, done, episode_return, done_episode]``
    as a ``KIND_OBS_REQ`` (optionally coded with the PR-6 byte-plane
    core) and blocks for the ``KIND_ACT_RESP`` carrying its actions.
  - The **InferenceServer** lives in the learner process. Connection
    threads ``submit()`` requests; a batching tick thread coalesces
    everything pending — across ALL connections — into ONE jitted
    ``act()`` dispatch per tick (dynamic batch: fires when
    ``batch_max`` requests are pending or ``max_wait_s`` after the
    first arrival, whichever comes first), splits the sampled actions
    back per request, and replies on each connection.
  - **Zero-staleness weights**: the learner's publish path calls
    ``set_params`` with the same device params it broadcasts, so the
    very next tick acts with the new weights — the in-process analog
    of ``KIND_PARAMS_NOTIFY`` (what remote peers get), minus the wire.
  - **Server-side trajectory assembly**: the serving tier already
    knows every action and behaviour log-prob it sampled, so actors
    never see (or ship) them. A per-actor ``_TrajBuilder`` pairs each
    request's reward/done (which belong to the PREVIOUS action — env
    semantics) with that action, and every ``rollout_length`` complete
    steps emits a segment through the SAME trajectory path classic
    actors use (validator -> queue -> arena): the learner side is
    unchanged, and an env-shim fleet and a fetch-params fleet can
    coexist on one server.

Idempotency (the resilience story): every request carries a per-step
sequence number. A retry after a reconnect re-sends the SAME seq; the
lane guard replays the cached actions without touching the trajectory
builder, so the env steps exactly once per sequence number no matter
how many times the wire faults. A discontinuity (seq jumps — actor
respawn, server restart losing lane state) resets the builder: the
partial segment is dropped rather than stitched across the gap.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from actor_critic_algs_on_tensorflow_tpu.distributed import codec
from actor_critic_algs_on_tensorflow_tpu.utils import metric_names

# Request leaf layout (after the obs leaves): reward, done,
# episode_return, done_episode — all [B_env] float32, produced by the
# shim's env wrapper for the step its PREVIOUS action caused.
N_STEP_LEAVES = 4


def request_specs_for(
    traj_obs_shape, envs_per_actor: int
) -> Tuple[Any, List[Tuple[Tuple[int, ...], np.dtype]]]:
    """The wire contract of one observation request, derived from the
    learner's trajectory-obs eval_shape tree (leaves ``[T, B, ...]``):
    ``(obs_treedef, [(shape, dtype) per request leaf])`` — obs leaves
    at ``[B_env, ...]`` followed by the ``N_STEP_LEAVES`` float32 step
    leaves. The SINGLE definition of the request layout: the trainer
    validates incoming shims against it and the serve bench builds its
    clients from it, so the two cannot drift."""
    import jax

    obs_treedef = jax.tree_util.tree_structure(traj_obs_shape)
    b = envs_per_actor
    specs: List[Tuple[Tuple[int, ...], np.dtype]] = [
        ((b, *tuple(x.shape[2:])), np.dtype(x.dtype))
        for x in jax.tree_util.tree_leaves(traj_obs_shape)
    ]
    specs += [((b,), np.dtype(np.float32))] * N_STEP_LEAVES
    return obs_treedef, specs


@dataclasses.dataclass
class _Pending:
    """One not-yet-acted observation request."""

    lane: "_Lane"
    seq: int
    leaves: List[np.ndarray]
    reply: Callable[[List[np.ndarray]], bool]
    t0: float


class _TrajBuilder:
    """Per-actor rollout-segment assembly on the serving side.

    Mirrors ``common.collect_rollout`` semantics exactly: step ``t`` is
    (obs_t, action_t, reward_t, done_t) where reward/done are the
    CONSEQUENCE of action_t — which the shim only learns at its next
    env step, so they arrive with request ``t+1``. ``advance`` is
    called once per served request with that request's payload and the
    actions/log-probs just sampled for it; when ``length`` complete
    steps exist, the segment is emitted with the current request's obs
    as the bootstrap ``last_obs`` (the boundary request also becomes
    step 0 of the next segment, exactly like a rollout loop's carry).
    """

    def __init__(self, length: int, n_obs: int, obs_treedef, actor_id: int):
        self._length = length
        self._n_obs = n_obs
        self._obs_treedef = obs_treedef
        self._actor_id = actor_id
        self._steps: List[tuple] = []
        self._held: Optional[tuple] = None  # (obs_leaves, actions, logp)

    def reset(self) -> None:
        self._steps = []
        self._held = None

    def advance(
        self,
        leaves: Sequence[np.ndarray],
        actions: np.ndarray,
        log_probs: np.ndarray,
    ) -> Optional[Tuple[List[np.ndarray], List[np.ndarray]]]:
        obs = list(leaves[: self._n_obs])
        reward, done, ep_ret, ep_done = leaves[self._n_obs :]
        out = None
        if self._held is not None:
            h_obs, h_act, h_logp = self._held
            self._steps.append(
                (h_obs, h_act, h_logp, reward, done, ep_ret, ep_done)
            )
            if len(self._steps) == self._length:
                out = self._emit(obs)
                self._steps = []
        self._held = (obs, actions, log_probs)
        return out

    def _emit(
        self, last_obs: Sequence[np.ndarray]
    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """Stack the completed steps into the SAME wire-leaf layout a
        classic actor pushes (``ActorTrajectory`` + episode-info tree
        leaves), so everything downstream — validator, queue, arena
        ingest plan — is reused unchanged."""
        import jax

        from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
            ActorTrajectory,
        )

        steps = self._steps
        unflat = lambda leaves: jax.tree_util.tree_unflatten(
            self._obs_treedef, list(leaves)
        )
        traj = ActorTrajectory(
            obs=unflat(
                np.stack([s[0][i] for s in steps])
                for i in range(self._n_obs)
            ),
            actions=np.stack([s[1] for s in steps]),
            rewards=np.stack([s[3] for s in steps]),
            dones=np.stack([s[4] for s in steps]),
            behaviour_log_probs=np.stack([s[2] for s in steps]),
            last_obs=unflat(np.asarray(x) for x in last_obs),
        )
        ep = {
            "actor_id": np.full((), self._actor_id, np.int32),
            "episode_return": np.stack([s[5] for s in steps]),
            "done_episode": np.stack([s[6] for s in steps]),
        }
        return (
            jax.tree_util.tree_leaves(traj),
            jax.tree_util.tree_leaves(ep),
        )


@dataclasses.dataclass
class _Lane:
    """Per-``(tenant, actor)`` serving state: the idempotency guard +
    builder. ``actor_id`` is the lane's actor component (unique within
    its tenant); ``tenant`` selects which job's policy acts for it."""

    actor_id: int
    generation: int
    builder: _TrajBuilder
    tenant: int = 0
    last_seq: int = -1
    last_reply: Optional[List[np.ndarray]] = None
    inflight: Optional[_Pending] = None


class InferenceServer:
    """Batched central ``act()`` over the env-shim fleet.

    ``submit(peer, seq, arrays, coded, reply)`` is installed as the
    ``LearnerServer``'s inference handler and runs on connection
    threads: it decodes/validates the request, applies the sequence
    guard, and queues it for the tick thread. The tick thread batches
    everything pending into one ``act(params, obs, key) ->
    (actions, log_probs)`` dispatch (request count padded to the next
    power of two so XLA compiles O(log fleet) shapes, not one per
    transient batch size), replies per connection, advances the
    per-actor trajectory builders, and hands completed segments to
    ``sink(traj_leaves, ep_leaves, actor_id)`` — the existing
    trajectory ingest path.

    ``set_params`` swaps the weights the next tick acts with (a
    GIL-atomic reference store; params trees are immutable device
    arrays): called from the learner's publish path, so weight
    staleness for the whole fleet is one tick, not a fetch round-trip.
    """

    def __init__(
        self,
        act,
        params,
        *,
        obs_treedef,
        request_specs: Sequence[Tuple[Tuple[int, ...], np.dtype]],
        rollout_length: int,
        batch_max: int,
        max_wait_s: float = 0.002,
        sink: Callable[[List[np.ndarray], List[np.ndarray], int], Any],
        seed: int = 0,
        exec_lock: Optional[threading.Lock] = None,
        max_decode_bytes: int = 1 << 30,
        log: Callable[[str], None] | None = None,
    ):
        import jax

        from actor_critic_algs_on_tensorflow_tpu.utils.metrics import (
            LatencyStats,
        )

        if batch_max < 1:
            raise ValueError(f"batch_max must be >= 1, got {batch_max}")
        self._act = act
        self._params = params
        self._obs_treedef = obs_treedef
        self._n_obs = obs_treedef.num_leaves
        self._request_specs = [
            (tuple(s), np.dtype(d)) for s, d in request_specs
        ]
        if len(self._request_specs) != self._n_obs + N_STEP_LEAVES:
            raise ValueError(
                f"{len(self._request_specs)} request specs for "
                f"{self._n_obs} obs leaves + {N_STEP_LEAVES} step leaves"
            )
        # Env rows per request: every request in one fleet carries the
        # same cfg.envs_per_actor rows (enforced by the spec check).
        self._rows = self._request_specs[0][0][0]
        self._rollout_length = rollout_length
        self._batch_max = batch_max
        self._max_wait = max_wait_s
        self._sink = sink
        # A sink accepting a 4th parameter opts into tenant
        # attribution (sink(traj, ep, actor_id, tenant)) — 3-arg
        # sinks keep the pre-tenancy contract.
        try:
            import inspect

            self._sink_tenant = (
                len(inspect.signature(sink).parameters) >= 4
            )
        except (TypeError, ValueError):
            self._sink_tenant = False
        self._exec_lock = exec_lock
        self._max_decode_bytes = max_decode_bytes
        self._log = log if log is not None else (
            lambda msg: print(f"[inference-server] {msg}", flush=True)
        )
        self._key = jax.random.PRNGKey(seed)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: List[_Pending] = []
        # Deferred wakes (reactor transport): when True, submit() does
        # NOT notify the tick per request — the transport's event loop
        # calls wake() once per readiness pass instead, so an OBS_REQ
        # burst costs one condition-variable wake, not N. The tick's
        # bounded wait (0.2 s / 50 ms) backstops a lost wake.
        self._defer_wakes = False
        # Lanes are keyed (tenant, actor_key): one fleet multiplexes N
        # jobs, each actor's idempotency guard and builder scoped to
        # its tenant. Tenant 0 is the default single-job tenant.
        self._lanes: Dict[Tuple[int, int], _Lane] = {}
        self._stop = False
        # Per-tenant policies: tenant 0 acts with self._params (the
        # original hot-path attribute — single-tenant fleets touch no
        # dict); other tenants' params live here and FALL BACK to the
        # live params until their job registers its own.
        self._tenant_params: Dict[int, Any] = {}
        # Candidate lanes (continuous delivery), PER TENANT: a canary
        # routes a deterministic fraction of a tenant's lanes to its
        # candidate params; a shadow scores the candidate against that
        # tenant's live traffic without serving its actions. Reference
        # stores under self._lock, same discipline as self._params.
        self._canary: Dict[int, Tuple[Any, int, float]] = {}
        self._shadow: Dict[int, Tuple[Any, int]] = {}
        # Counters (all under self._lock).
        self._requests = 0
        self._policy_groups = 0
        self._tenant_requests: Dict[int, int] = {}
        self._dup_replays = 0
        self._seq_resets = 0
        self._rejected = 0
        self._batches = 0
        self._batched_requests = 0
        self._segments = 0
        self._reply_failures = 0
        self._param_swaps = 0
        self._lane_retires = 0
        self._canary_requests = 0
        self._canary_batches = 0
        self._candidate_clears = 0
        self._shadow_batches = 0
        self._shadow_div_sum = 0.0
        self._act_lat = LatencyStats()
        self._tick = threading.Thread(
            target=self._tick_loop, name="inference-server-tick", daemon=True
        )
        self._tick.start()

    # -- weights --------------------------------------------------------

    def set_params(self, params, tenant: int = 0) -> None:
        """Swap a tenant's acting weights (reference store; the next
        tick's dispatch reads the new tree). The learner's publish path
        calls this alongside the wire publish, which is what makes the
        serving tier's staleness ~one tick: by the time remote peers
        even receive their ``KIND_PARAMS_NOTIFY``, central inference
        is already acting with the new weights. Tenant 0 (the default)
        is the live single-job path."""
        if tenant:
            with self._lock:
                self._tenant_params[int(tenant)] = params
                self._param_swaps += 1
            return
        self._params = params
        with self._lock:
            self._param_swaps += 1

    def _params_for(self, tenant: int):
        """The tree a tenant's lanes act with: its registered policy,
        falling back to the live (tenant-0) params until one exists."""
        if not tenant:
            return self._params
        return self._tenant_params.get(tenant, self._params)

    # -- candidate lanes (continuous delivery) --------------------------

    @staticmethod
    def _lane_slot(lane_key) -> float:
        """Deterministic [0, 1) slot for a lane (Knuth multiplicative
        hash on the lane's ACTOR component — a ``(tenant, actor)``
        tuple hashes its actor, so a given actor id lands on the same
        slot in every tenant): stable across processes and restarts,
        so a lane's canary membership never flaps while the fraction
        holds — each actor sees ONE policy per candidate, not a
        per-tick coin flip."""
        key = lane_key[1] if isinstance(lane_key, tuple) else lane_key
        return ((int(key) * 2654435761) & 0xFFFFFFFF) / 2.0**32

    def set_canary(
        self, params, version: int, fraction: float, tenant: int = 0
    ) -> None:
        """Stage candidate params on a canary slice of ``tenant``'s
        lanes: lanes whose slot falls below ``fraction`` are served BY
        the candidate from the next tick on (their builders keep
        assembling segments — canary experience trains like any
        other). Everyone else stays on the tenant's live params until
        a PROMOTE lands. Canaries are per tenant: one job's candidate
        never routes another job's lanes."""
        with self._lock:
            self._canary[int(tenant)] = (
                params, int(version), min(max(float(fraction), 0.0), 1.0)
            )

    def set_shadow(self, params, version: int, tenant: int = 0) -> None:
        """Stage candidate params in shadow for ``tenant``: every tick
        ALSO runs the candidate on that tenant's live batch (same obs,
        same PRNG key) and records action divergence, but only the
        live policy's actions are served — zero blast radius
        scoring."""
        with self._lock:
            self._shadow[int(tenant)] = (params, int(version))

    def clear_candidate(self, tenant: int = 0) -> bool:
        """Drop ``tenant``'s staged canary/shadow candidate (REJECT
        verdict, or a rollback deposing it): the next tick serves all
        of that tenant's lanes from its live params again. Returns
        whether anything was staged."""
        with self._lock:
            had = (
                self._canary.pop(int(tenant), None) is not None
                or self._shadow.pop(int(tenant), None) is not None
            )
            if had:
                self._candidate_clears += 1
        return had

    # -- request ingress (connection threads) ---------------------------

    def submit(self, peer, seq: int, arrays, coded: bool, reply) -> None:
        """Queue one observation request (or replay its cached reply).

        Raises ``ConnectionError`` on malformed input — the transport
        recycles the connection and the resilient client retries, so a
        stale-config shim fails visibly instead of poisoning a batch.
        """
        t0 = time.monotonic()
        if coded:
            try:
                leaves = codec.decode_traj(
                    list(arrays), max_leaf_bytes=self._max_decode_bytes
                )
            except codec.CodecError as e:
                with self._lock:
                    self._rejected += 1
                raise ConnectionError(
                    f"undecodable coded obs request: {e}"
                ) from e
        else:
            leaves = [np.asarray(a) for a in arrays]
        if len(leaves) != len(self._request_specs):
            with self._lock:
                self._rejected += 1
            raise ConnectionError(
                f"obs request carries {len(leaves)} leaves, this "
                f"learner's config expects {len(self._request_specs)}"
            )
        for i, (leaf, (shape, dtype)) in enumerate(
            zip(leaves, self._request_specs)
        ):
            if tuple(leaf.shape) != shape or leaf.dtype != dtype:
                with self._lock:
                    self._rejected += 1
                raise ConnectionError(
                    f"obs request leaf {i} is "
                    f"{leaf.dtype.str}{tuple(leaf.shape)}, expected "
                    f"{np.dtype(dtype).str}{shape} — stale config?"
                )
        actor_key = (
            peer.actor_id if peer.actor_id >= 0 else -(1000 + peer.cid)
        )
        tenant = int(getattr(peer, "tenant", 0))
        lane_key = (tenant, actor_key)
        cached = None
        with self._lock:
            lane = self._lanes.get(lane_key)
            if lane is None:
                lane = _Lane(
                    actor_id=actor_key,
                    generation=peer.generation,
                    tenant=tenant,
                    builder=_TrajBuilder(
                        self._rollout_length,
                        self._n_obs,
                        self._obs_treedef,
                        actor_key,
                    ),
                )
                self._lanes[lane_key] = lane
            if peer.generation != lane.generation:
                # A respawned actor (fresh generation) restarts its
                # sequence space: never stitch its steps onto the old
                # incarnation's partial segment.
                lane.generation = peer.generation
                lane.builder.reset()
                lane.last_seq, lane.last_reply = -1, None
                lane.inflight = None
            if seq == lane.last_seq:
                # Idempotent replay: the actor re-asked (reconnect
                # after a lost reply). NEVER re-enters the builder —
                # this is the guard that keeps env steps exactly-once.
                self._dup_replays += 1
                if lane.inflight is not None:
                    # Original still waiting for a tick: point its
                    # reply at the live connection and let the batch
                    # answer it once.
                    lane.inflight.reply = reply
                    return
                cached = lane.last_reply
            else:
                if seq != lane.last_seq + 1:
                    # Discontinuity (server restarted and lost lane
                    # state mid-rollout, or an actor restarted without
                    # a generation bump): drop the partial segment
                    # rather than stitch across the gap.
                    if lane.last_seq != -1:
                        self._seq_resets += 1
                    lane.builder.reset()
                lane.last_seq = seq
                lane.last_reply = None
                req = _Pending(lane, seq, leaves, reply, t0)
                lane.inflight = req
                self._pending.append(req)
                self._requests += 1
                if not self._defer_wakes:
                    self._cond.notify()
        if cached is not None:
            reply(cached)

    def set_wake_batching(self, defer: bool) -> None:
        """Switch submit() to DEFERRED wakes: the caller promises to
        invoke ``wake()`` after each burst of submits (the reactor
        transport's per-readiness-pass batch wake). One boolean store
        (GIL-atomic); a request racing the flip at worst costs one
        extra notify or rides the tick's 0.2 s backstop."""
        self._defer_wakes = bool(defer)

    def wake(self) -> None:
        """Nudge the batching tick once — the deferred-wake partner of
        ``set_wake_batching`` (installed as the transport's
        ``batch_wake``)."""
        with self._cond:
            self._cond.notify()

    # -- batching tick --------------------------------------------------

    def _tick_loop(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._stop:
                    self._cond.wait(0.2)
                if not self._pending:
                    return  # stopping, nothing left to drain
                deadline = self._pending[0].t0 + self._max_wait
                while (
                    len(self._pending) < self._batch_max
                    and not self._stop
                ):
                    now = time.monotonic()
                    if now >= deadline:
                        break
                    self._cond.wait(min(deadline - now, 0.05))
                reqs = self._pending[: self._batch_max]
                del self._pending[: len(reqs)]
            try:
                self._process(reqs)
            except Exception as e:  # noqa: BLE001 — keep the tick alive
                # A failed tick strands its requests. Rewind each
                # lane's sequence cursor so the shim's retry (same
                # seq, after its idle deadline) re-enters as a NEW
                # request instead of matching a dead inflight forever
                # — the builder never advanced, so exactly-once holds.
                # Log loudly: this is a bug or a hostile frame that
                # slipped the spec check, not a steady state.
                with self._lock:
                    for r in reqs:
                        if r.lane.inflight is r:
                            r.lane.inflight = None
                            r.lane.last_seq = r.seq - 1
                self._log(
                    f"act tick failed for {len(reqs)} request(s): "
                    f"{type(e).__name__}: {e}"
                )

    def _process(self, reqs: List[_Pending]) -> None:
        # Partition the tick's requests into per-POLICY act() groups:
        # one group per (tenant, live-vs-canary) pair — a tenant's
        # canary lanes get its candidate params, everyone else their
        # tenant's live params. The tick COALESCES across tenants (one
        # wait window, one wake) but each distinct policy is one
        # dispatch, so single-tenant-no-candidate stays exactly ONE
        # group and one dispatch — the pre-tenancy hot path,
        # bit-identical at fixed seed.
        with self._lock:
            canary = dict(self._canary)
            shadow = dict(self._shadow)
        groups: Dict[Tuple[int, bool], List[_Pending]] = {}
        for r in reqs:
            t = r.lane.tenant
            cand = canary.get(t)
            routed = (
                cand is not None
                and self._lane_slot(r.lane.actor_id) < cand[2]
            )
            groups.setdefault((t, routed), []).append(r)
        if len(groups) > 1:
            # A tick that coalesced requests for MORE than one policy:
            # the multi-tenant batching win made visible. Counted at
            # partition time, before dispatch, so the metric is
            # readable the moment this tick's replies land.
            with self._lock:
                self._policy_groups += 1
        for (t, routed), grp in groups.items():
            if routed:
                self._dispatch(
                    canary[t][0], grp, is_canary=True,
                    shadow_params=None,
                )
            else:
                sh = shadow.get(t)
                self._dispatch(
                    self._params_for(t), grp, is_canary=False,
                    shadow_params=sh[0] if sh is not None else None,
                )

    def _dispatch(
        self,
        params,
        reqs: List[_Pending],
        *,
        is_canary: bool,
        shadow_params=None,
    ) -> None:
        import jax

        n = len(reqs)
        # Pad the REQUEST count to a power of two: O(log fleet)
        # compiled shapes instead of one per transient batch size.
        bucket = 1 << (n - 1).bit_length()
        cols = []
        for i in range(self._n_obs):
            col = (
                np.concatenate([r.leaves[i] for r in reqs], axis=0)
                if n > 1
                else np.asarray(reqs[0].leaves[i])
            )
            if bucket > n:
                # Pad rows replicate the first row (cheap broadcast
                # view; the concatenate below materializes it). Their
                # sampled actions are computed and discarded.
                pad = np.broadcast_to(
                    col[:1], ((bucket - n) * self._rows, *col.shape[1:])
                )
                col = np.concatenate([col, pad], axis=0)
            cols.append(col)
        obs = jax.tree_util.tree_unflatten(self._obs_treedef, cols)
        self._key, k = jax.random.split(self._key)
        shadow_actions = None
        if self._exec_lock is None:
            actions, log_probs = self._act(params, obs, k)
            if shadow_params is not None:
                # Same obs, same key: divergence measures the params
                # delta, not sampling noise.
                shadow_actions, _ = self._act(shadow_params, obs, k)
        else:
            # CPU-mesh serialize rule (see ImpalaActor._run_serialized):
            # every jitted dispatch runs to completion under the shared
            # lock so act() never interleaves the learner's collectives.
            with self._exec_lock:
                actions, log_probs = self._act(params, obs, k)
                jax.block_until_ready((actions, log_probs))
                if shadow_params is not None:
                    shadow_actions, _ = self._act(shadow_params, obs, k)
                    jax.block_until_ready(shadow_actions)
        actions = np.asarray(actions)
        log_probs = np.asarray(log_probs)
        if shadow_actions is not None:
            served = actions[: n * self._rows]
            mirror = np.asarray(shadow_actions)[: n * self._rows]
            if np.issubdtype(served.dtype, np.integer):
                div = float(np.mean(served != mirror))
            else:
                div = float(np.mean(np.abs(served - mirror)))
        segments: List[Tuple[int, int, tuple]] = []
        replies: List[Tuple[_Pending, List[np.ndarray]]] = []
        now = time.monotonic()
        with self._lock:
            for j, r in enumerate(reqs):
                sl = slice(j * self._rows, (j + 1) * self._rows)
                out = [np.ascontiguousarray(actions[sl])]
                r.lane.last_reply = out
                r.lane.inflight = None
                replies.append((r, out))
                seg = r.lane.builder.advance(
                    r.leaves, out[0], log_probs[sl]
                )
                if seg is not None:
                    segments.append(
                        (r.lane.actor_id, r.lane.tenant, seg)
                    )
                self._tenant_requests[r.lane.tenant] = (
                    self._tenant_requests.get(r.lane.tenant, 0) + 1
                )
            self._batches += 1
            self._batched_requests += n
            if is_canary:
                self._canary_batches += 1
                self._canary_requests += n
            if shadow_actions is not None:
                self._shadow_batches += 1
                self._shadow_div_sum += div
        for r, out in replies:
            # r.reply may have been repointed at a retry's live
            # connection by submit(); read it now, after the lane
            # update, so the newest closure wins.
            if not r.reply(out):
                with self._lock:
                    self._reply_failures += 1
            self._act_lat.add_s(now - r.t0)
        for actor_id, tenant, (traj_leaves, ep_leaves) in segments:
            # Outside the lock: the sink is the real trajectory path
            # and may BLOCK on queue backpressure — that stall is the
            # serving tier's flow control (the fleet's next requests
            # queue behind it), by design.
            with self._lock:
                self._segments += 1
            if self._sink_tenant:
                self._sink(traj_leaves, ep_leaves, actor_id, tenant)
            else:
                self._sink(traj_leaves, ep_leaves, actor_id)

    # -- observability / lifecycle --------------------------------------

    def reset_act_latency(self) -> None:
        """Forget recorded act latencies (benches call this at the
        start of their timed window so warmup compiles do not pollute
        the percentiles)."""
        self._act_lat.reset()

    def retire_lane(self, actor_id: int, tenant: int = 0) -> bool:
        """Drop a departed shim's lane (elastic leave): its builder's
        partial segment is discarded — the actor announced an orderly
        goodbye, so no further steps will ever complete it — and an
        in-flight request is forgotten (its reply closure fails
        harmlessly against the closed connection). Wired to the
        transport goodbye hook so a scale-down does not leave ghost
        lanes pinning ``serve_lanes`` (and builder memory) for the
        rest of the run. A later REJOIN under a fresh generation would
        have reset the lane anyway; retirement just reclaims it
        eagerly. Returns whether a lane existed."""
        with self._lock:
            lane = self._lanes.pop((int(tenant), int(actor_id)), None)
            if lane is not None:
                self._lane_retires += 1
        return lane is not None

    def metrics(self) -> dict:
        with self._lock:
            # Canary fraction reported for the DEFAULT tenant (the
            # single-job reading); canary_lanes counts across every
            # tenant's staged candidate.
            cand0 = self._canary.get(0)
            fraction = cand0[2] if cand0 is not None else 0.0
            canary_lanes = sum(
                1 for key in self._lanes
                if (cand := self._canary.get(key[0])) is not None
                and self._lane_slot(key) < cand[2]
            )
            tenants = {key[0] for key in self._lanes}
            m = {
                "serve_tenants": len(tenants),
                "serve_policy_group_ticks": self._policy_groups,
                "serve_requests": self._requests,
                "serve_dup_replays": self._dup_replays,
                "serve_seq_resets": self._seq_resets,
                "serve_rejected": self._rejected,
                "serve_batches": self._batches,
                "serve_batch_mean": round(
                    self._batched_requests / max(1, self._batches), 3
                ),
                "serve_segments": self._segments,
                "serve_reply_failures": self._reply_failures,
                "serve_param_swaps": self._param_swaps,
                "serve_lanes": len(self._lanes),
                "serve_lane_retires": self._lane_retires,
                # Candidate lanes (continuous delivery): the canary
                # slice actually routed this instant, its lifetime
                # traffic, and the shadow scorer's mean divergence
                # (action mismatch fraction for discrete policies,
                # mean |delta| for continuous ones).
                "serve_canary_fraction": fraction,
                "serve_canary_lanes": canary_lanes,
                "serve_canary_requests": self._canary_requests,
                "serve_canary_batches": self._canary_batches,
                "serve_candidate_clears": self._candidate_clears,
                "serve_shadow_batches": self._shadow_batches,
                "serve_shadow_divergence": round(
                    self._shadow_div_sum
                    / max(1, self._shadow_batches),
                    6,
                ),
            }
            for t, n in sorted(self._tenant_requests.items()):
                m[f"tenant{t}_serve_requests"] = n
        m.update(self._act_lat.summary(metric_names.SERVE_ACT))
        return m

    def close(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._tick.join(timeout=5.0)


def env_shim_actor_main(
    cfg, actor_id: int, host: str, port: int, seed: int, generation: int = 0
) -> None:
    """Entry point of one env-shim actor PROCESS.

    The SEED-style counterpart of ``impala._actor_process_main``: no
    policy, no params, no rollout program — just the vectorized env
    stepped one batch at a time, with actions fetched from the central
    inference tier per step. Exits cleanly when the learner closes the
    stream. Connects through whatever address it is given (normally
    the control plane's Redirector, so the shim fleet fails over with
    everyone else).
    """
    import jax

    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
        pin_process_to_cpu,
    )

    pin_process_to_cpu(f"env-shim {actor_id}")
    import jax.numpy as jnp  # noqa: F401  (jit inputs)

    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
    from actor_critic_algs_on_tensorflow_tpu.distributed.resilience import (
        ResilientActorClient,
        RetryPolicy,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        CAP_INFERENCE,
        ROLE_ACTOR,
        LearnerShutdown,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.metrics import (
        LatencyStats,
    )

    env, env_params = envs_lib.make(
        cfg.env, num_envs=cfg.envs_per_actor, frame_stack=cfg.frame_stack,
        fresh=cfg.env.startswith("gym:"),
    )
    reset_fn = jax.jit(env.reset)
    step_fn = jax.jit(env.step)
    # Optional request coding with the PR-6 byte-plane core: per-leaf
    # smaller-of selection means float CartPole obs ride plain while
    # pixel obs compress; no temporal delta — a single step has no
    # rollout axis to delta along.
    encoder = (
        codec.TrajEncoder(obs_delta=False) if cfg.serve_obs_codec else None
    )
    # ``port`` may be an ordered (host, port) endpoint list — the
    # redundant-redirector form, same contract as the classic actor
    # main (resilience.endpoint_list is the single normalizer).
    from actor_critic_algs_on_tensorflow_tpu.distributed.resilience import (
        endpoint_list,
    )

    host, port, endpoints = endpoint_list(host, port)
    # 6-field hello: [actor_id, generation, role, caps, epoch, tenant]
    # — the tenant rides the same optional-trailing-field trick as the
    # fencing epoch, so a tenant-0 shim's hello is parsed identically
    # by legacy learners.
    tenant = int(getattr(cfg, "tenant_id", 0))
    client = ResilientActorClient(
        host, port,
        retry=RetryPolicy(deadline_s=cfg.transport_retry_deadline_s),
        heartbeat_interval_s=cfg.transport_heartbeat_s,
        idle_timeout_s=cfg.transport_idle_timeout_s,
        max_frame_bytes=cfg.transport_max_frame_mb << 20,
        hello=(
            actor_id, generation, ROLE_ACTOR, CAP_INFERENCE, 0, tenant
        ),
        endpoints=endpoints,
    )
    lat = LatencyStats()
    b = cfg.envs_per_actor
    try:
        key = jax.random.PRNGKey(seed)
        key, k = jax.random.split(key)
        env_state, obs = reset_fn(k, env_params)
        obs_leaves = [
            np.asarray(x) for x in jax.tree_util.tree_leaves(obs)
        ]
        reward = np.zeros(b, np.float32)
        done = np.zeros(b, np.float32)
        ep_ret = np.zeros(b, np.float32)
        ep_done = np.zeros(b, np.float32)
        seq = 0
        while True:
            t0 = time.perf_counter()
            out = client.act_request(
                seq,
                [*obs_leaves, reward, done, ep_ret, ep_done],
                encoder=encoder,
            )
            lat.add_s(time.perf_counter() - t0)
            seq += 1
            actions = out[0]
            key, k = jax.random.split(key)
            env_state, obs, r, d, info = step_fn(
                k, env_state, actions, env_params
            )
            obs_leaves = [
                np.asarray(x) for x in jax.tree_util.tree_leaves(obs)
            ]
            reward = np.asarray(r, np.float32)
            done = np.asarray(d, np.float32)
            ep_ret = np.asarray(info["episode_return"], np.float32)
            ep_done = np.asarray(info["done_episode"], np.float32)
    except LearnerShutdown:
        stats = dict(client.stats())
        stats.update(lat.summary("act_"))
        if encoder is not None:
            stats.update(encoder.stats())
        print(
            f"[env-shim {actor_id}] learner closed the stream; exiting "
            f"({stats})",
            flush=True,
        )
    except (ConnectionError, OSError) as e:
        print(
            f"[env-shim {actor_id}] transport failed after retries: "
            f"{type(e).__name__}: {e} ({client.stats()})",
            flush=True,
        )
    finally:
        try:
            client.close()
        except Exception:
            pass
