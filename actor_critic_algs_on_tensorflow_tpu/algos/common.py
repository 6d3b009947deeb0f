"""Shared on-policy training machinery (the Anakin pattern).

Capability parity: the reference's on-policy trainers loop
rollout -> GAE -> update with synchronous multi-actor gradient
averaging (BASELINE.json:5, SURVEY.md §3.1). TPU-first, the WHOLE
iteration — T env steps x B envs collected by ``lax.scan`` over
vmapped pure-JAX envs, advantage estimation, and the optimizer
update with ``lax.pmean`` gradient averaging — is ONE jitted
``shard_map`` program over the ``data`` mesh axis. The host only
dispatches iterations and reads metrics, so the TPU never waits on
Python (the reference's host env-step loop is the bottleneck this
design removes; SURVEY.md §3.1 "hot loops").
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, PartitionSpec as P

from actor_critic_algs_on_tensorflow_tpu.data.rollout import Trajectory
from actor_critic_algs_on_tensorflow_tpu.models import (
    SEQUENCE_CORES,
    DiscreteActorCritic,
    GaussianActorCritic,
    RecurrentActorCritic,
    sequence_core,
)
from actor_critic_algs_on_tensorflow_tpu.models.networks import scale_pixels
from actor_critic_algs_on_tensorflow_tpu.ops import (
    BlockReveal,
    Categorical,
    DiagGaussian,
)
from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
    DATA_AXIS,
    device_count,
    put_by_specs,
    replicated_specs,
    shard_batch_specs,
    shard_map,
)
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

# policy_fn(params, obs, key) -> (action, log_prob, value)
PolicyFn = Callable[[Any, Any, jax.Array], Tuple[jax.Array, jax.Array, jax.Array]]


@struct.dataclass
class OnPolicyState:
    """Train state for A2C/PPO-style algorithms.

    ``params``/``opt_state``/``key``/``step`` are replicated across the
    mesh; ``env_state``/``obs`` are sharded on their leading (env) axis.
    ``extra`` carries replicated algorithm-specific state (e.g. PPO's
    running observation-normalization statistics); ``None`` when unused.
    """

    params: Any
    opt_state: Any
    env_state: Any
    obs: Any
    key: jax.Array
    step: jax.Array  # iteration counter; env steps = step * steps_per_iteration
    extra: Any = None
    # Recurrent policies only: {"core": <the core's own carry>,
    # "prev_done": [B]} — the policy state entering the NEXT rollout
    # step, every leaf with the env axis leading (sharded like obs). The
    # core owns its carry's shape: an LSTM's (c, h) each [B, lstm], or
    # per layer a DeltaNet state, a convolution's tail, a key/value
    # cache or a cache of latents, and the position (the sequence cores,
    # models.SEQUENCE_CORES). None for feed-forward policies.
    carry: Any = None


def state_specs(state: OnPolicyState) -> OnPolicyState:
    """PartitionSpec pytree matching ``OnPolicyState``."""
    return OnPolicyState(
        params=replicated_specs(state.params),
        opt_state=replicated_specs(state.opt_state),
        env_state=shard_batch_specs(state.env_state),
        obs=shard_batch_specs(state.obs),
        key=P(),
        step=P(),
        extra=replicated_specs(state.extra),
        carry=shard_batch_specs(state.carry),
    )


def put_state(state, specs, mesh: Mesh):
    """Place a host-built train state onto the mesh per its specs."""
    return put_by_specs(state, specs, mesh)


def build_shard_map_iteration(
    local_iteration: Callable, specs, mesh: Mesh, *, donate: bool = True
) -> Callable:
    """shard_map + jit a ``state -> (state, metrics)`` iteration."""
    mapped = shard_map(
        local_iteration,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def check_host_env_topology(env_name: str, n_dev: int) -> None:
    """Host-resident envs (``gym:``/``native:``) live in THIS process;
    a multi-device ``shard_map`` would have every device's program call
    back into one shared simulator pool with interleaved ordering.
    Fail fast with the supported alternatives instead of deadlocking
    or silently corrupting episode streams.

    The supported MuJoCo/Gym-at-scale topology is IMPALA with actor
    processes (``run_impala_distributed`` / ``--actor-processes``):
    each actor process owns a private host env pool and streams
    trajectories to the learner over the TCP transport, which is also
    how the reference scales beyond one host (BASELINE.json:11).
    """
    if n_dev > 1 and env_name.startswith(("gym:", "native:")):
        raise ValueError(
            f"host-resident env {env_name!r} cannot shard across "
            f"{n_dev} devices from one process: the simulator pool is "
            "host-side state shared by all devices. Use num_devices=1 "
            "(vectorize via num_envs), or scale host envs with IMPALA "
            "actor processes (--actor-processes), each owning its own "
            "env pool (see README: 'Host envs at scale')."
        )


def make_policy_head(action_space, *, torso, hidden_sizes, compute_dtype):
    """(model, dist_and_value) for a discrete (Categorical) or
    continuous (diagonal-Gaussian) action space — the policy-head
    dispatch shared by the on-policy and IMPALA trainers.

    ``torso`` applies to the discrete head; the continuous head is the
    MLP ``GaussianActorCritic`` (matching the reference's MuJoCo-scale
    policies, BASELINE.json:9-10).
    """
    discrete = hasattr(action_space, "n")
    if discrete:
        model = DiscreteActorCritic(
            num_actions=action_space.n,
            torso=torso,
            hidden_sizes=hidden_sizes,
            dtype=jnp.dtype(compute_dtype),
        )
    else:
        if torso not in (None, "mlp"):
            # The continuous head is MLP-only (the reference's
            # MuJoCo-scale policies); silently ignoring a configured
            # CNN/transformer torso would train a different model
            # than the user asked for.
            raise ValueError(
                f"torso={torso!r} is not supported for continuous "
                "action spaces; GaussianActorCritic is MLP-only "
                "(use torso='mlp' or a discrete-action env)"
            )
        model = GaussianActorCritic(
            action_dim=action_space.shape[-1],
            hidden_sizes=hidden_sizes,
            dtype=jnp.dtype(compute_dtype),
        )

    def dist_and_value(params, obs):
        if discrete:
            logits, value = model.apply(params, obs)
            return Categorical(logits), value
        mean, log_std, value = model.apply(params, obs)
        return DiagGaussian(mean, log_std), value

    return model, dist_and_value


def make_obs_prep(torso, compute_dtype):
    """``prep(obs)``: the pixel torsos' own input conversion
    (``scale_pixels``), for an update to run under its
    ``minibatch_prep`` scope before the forward pass — the torso then
    finds nothing left to convert. The same operations either way;
    only the phase they are traced under moves. Call it on the
    observations as the torso's first layer will read them (PPO's
    minibatch, IMPALA's merged ``[T * B]`` batch): the compiler then
    fuses the conversion into that layer's input and the frames are
    read at one byte a pixel; ahead of a reshape that moves bytes, the
    converted copy is what gets moved (PERF.md section 6, PRs 26 and
    30)."""
    if torso == "nature_cnn":
        return lambda obs: scale_pixels(obs, jnp.dtype(compute_dtype))
    # Every other torso converts its own input, and a token id (the
    # sequence cores) must reach the embedding as the integer it is.
    return lambda obs: obs


def make_recurrent_policy_head(
    action_space,
    *,
    torso,
    hidden_sizes,
    lstm_size,
    compute_dtype,
    seq_model=None,
    cache_len=0,
):
    """(model, seq_dist_value) for a recurrent discrete policy.

    ``seq_dist_value(params, obs_tb, resets_tb, carry)`` runs the
    time-major sequence forward: obs ``[T, B, ...]``, resets ``[T, B]``
    (1.0 where step t begins a new episode), ``carry`` the core's own
    pytree (``model.initialize_carry(B)``); returns ``(Categorical over
    [T, B], values [T, B], new_carry, stats)``. Single-step
    collection/eval is the ``T == 1`` case of the same function.
    ``stats`` are the core's counters of that call as scalars (the
    expert layer's; ``{}`` for the LSTM).

    The core is the LSTM over a torso (``RecurrentActorCritic``, carry
    ``(c, h)``) or, with ``torso`` a name of ``models.SEQUENCE_CORES``,
    that core's model as ``seq_model`` (its config) describes it, whose
    carry holds per layer a state or a cache of ``cache_len`` tokens.
    ``model.replays_from_empty_carry`` says that the sequence form (``T
    > 1``) starts every sequence from the empty carry and reads neither
    ``carry`` nor ``resets``. ``model.reveals_blocks`` says that a step
    is a denoising pass over a block of tokens: observations and actions
    are ``[T, B, block_length]``, the logits ``[T, B, block_length, V]``,
    and the distribution is ``BlockReveal`` over the observed block (the
    action space is then a ``TokenBlock`` of the model's block).
    """
    if not hasattr(action_space, "n"):
        raise ValueError(
            "recurrent policies support discrete action spaces only "
            "(the continuous head is the MLP GaussianActorCritic); "
            "use recurrent=False for continuous-control envs"
        )
    if torso in SEQUENCE_CORES:
        core, core_config = sequence_core(torso)
        # What the model acts on (one token, or a block of them with a
        # mask id) is what the env's action space holds.
        def acts_on(x):
            return (getattr(x, "block_length", None),
                    getattr(x, "mask_token_id", None))

        if (
            not isinstance(seq_model, core_config)
            or seq_model.vocab_size != action_space.n
            or acts_on(seq_model) != acts_on(action_space)
        ):
            raise ValueError(
                f"torso={torso!r} (models.SEQUENCE_CORES) needs seq_model, "
                f"a {core_config.__name__} whose vocab_size is the env's "
                f"number of actions ({action_space.n}) and whose block and "
                f"mask id, if it acts on blocks, are the action space's "
                f"({action_space!r}); got {seq_model!r}"
            )
        model = core(
            cfg=seq_model, cache_len=cache_len,
            dtype=jnp.dtype(compute_dtype),
        )
        reveals_blocks = getattr(model, "reveals_blocks", False)

        def seq_dist_value(params, obs_tb, resets_tb, carry):
            logits, values, carry, stats = model.apply(
                params, obs_tb, resets_tb, carry
            )
            if reveals_blocks:
                dist = BlockReveal(logits, obs_tb, seq_model.reveal,
                                   seq_model.mask_token_id)
            else:
                dist = Categorical(logits)
            return dist, values, carry, stats

        return model, seq_dist_value
    model = RecurrentActorCritic(
        num_actions=action_space.n,
        torso=torso,
        hidden_sizes=hidden_sizes,
        lstm_size=lstm_size,
        dtype=jnp.dtype(compute_dtype),
    )

    def seq_dist_value(params, obs_tb, resets_tb, carry):
        logits, values, carry = model.apply(params, obs_tb, resets_tb, carry)
        return Categorical(logits), values, carry, {}

    return model, seq_dist_value


def collect_rollout_recurrent(
    env,
    env_params,
    seq_dist_value,
    params,
    env_state,
    obs,
    carry,
    key: jax.Array,
    length: int,
    *,
    norm=None,
):
    """Recurrent analog of :func:`collect_rollout`.

    ``carry`` is the state's ``{"core": ..., "prev_done": [B]}``
    policy-state bundle; each step feeds ``prev_done`` as the reset mask
    (the core zeroes its carry where an episode just ended), calls the
    ``T == 1`` sequence forward, and threads the new carry. Returns
    ``(env_state, obs, carry, traj, ep_info, stats)`` with ``carry``
    ready for the next rollout (and, unchanged in ``traj``, everything
    the update needs to REPLAY the sequence: the caller keeps the
    rollout-entry carry for that) and ``stats`` the core's counters,
    one row a step.
    """
    norm = norm if norm is not None else (lambda o: o)

    def _step(scan_carry, step_key):
        env_state, obs, core, prev_done = scan_carry
        k_act, k_env = jax.random.split(step_key)
        with jax.named_scope(profiling.POLICY_ACT):
            dist, value, core, stats = seq_dist_value(
                params, norm(obs)[None], prev_done[None], core
            )
            with jax.named_scope(profiling.SAMPLE):
                action = dist.sample(k_act)[0]
                log_prob = dist.log_prob(action[None])[0]
        with jax.named_scope(profiling.ENV_STEP):
            env_state, next_obs, reward, done, info = env.step(
                k_env, env_state, action, env_params
            )
        traj = Trajectory(
            obs=obs,
            actions=action,
            rewards=reward,
            dones=done,
            log_probs=log_prob,
            values=value[0],
        )
        ep_info = {
            "episode_return": info["episode_return"],
            "done_episode": info["done_episode"],
            "terminated": info["terminated"],
        }
        return (env_state, next_obs, core, done), (traj, ep_info, stats)

    with jax.named_scope(profiling.ROLLOUT):
        keys = jax.random.split(key, length)
        (env_state, obs, core, prev_done), (traj, ep_info, stats) = (
            jax.lax.scan(
                _step,
                (env_state, obs, carry["core"], carry["prev_done"]),
                keys,
            )
        )
    return (
        env_state,
        obs,
        {"core": core, "prev_done": prev_done},
        traj,
        ep_info,
        stats,
    )


def replay_resets(entry_prev_done, dones):
    """Reset mask ``[T, B]`` for replaying a collected rollout: step 0
    resets where the rollout ENTERED on an episode boundary; step t > 0
    where step t-1 ended an episode."""
    return jnp.concatenate([entry_prev_done[None], dones[:-1]], axis=0)


def collect_rollout(
    env,
    env_params,
    policy_fn: PolicyFn,
    params,
    env_state,
    obs,
    key: jax.Array,
    length: int,
    *,
    keep_final_obs: bool = False,
    store_obs_fn=None,
):
    """Collect a ``[T, B]`` trajectory with one ``lax.scan``.

    Returns ``(env_state, obs, trajectory, ep_info)`` where ``ep_info``
    holds per-step episode stats from the EpisodeStats wrapper plus the
    ``terminated`` mask (and, with ``keep_final_obs``, the pre-reset
    ``final_obs`` for time-limit bootstrapping — costs a full extra
    ``[T, B, obs]`` buffer, so off by default for image envs).

    ``store_obs_fn`` reduces each step's obs before it is stacked into
    the trajectory (the policy still sees the full obs) — e.g. keeping
    only the newest frame of a frame stack so the scan never
    materialises the redundant ``[T, B, full-stack]`` buffer.
    """

    def _step(carry, step_key):
        env_state, obs = carry
        k_act, k_env = jax.random.split(step_key)
        with jax.named_scope(profiling.POLICY_ACT):
            action, log_prob, value = policy_fn(params, obs, k_act)
        with jax.named_scope(profiling.ENV_STEP):
            env_state, next_obs, reward, done, info = env.step(
                k_env, env_state, action, env_params
            )
            stored_obs = obs if store_obs_fn is None else store_obs_fn(obs)
        traj = Trajectory(
            obs=stored_obs,
            actions=action,
            rewards=reward,
            dones=done,
            log_probs=log_prob,
            values=value,
        )
        ep_info = {
            "episode_return": info["episode_return"],
            "done_episode": info["done_episode"],
            "terminated": info["terminated"],
        }
        if keep_final_obs:
            ep_info["final_obs"] = info["final_obs"]
        return (env_state, next_obs), (traj, ep_info)

    with jax.named_scope(profiling.ROLLOUT):
        keys = jax.random.split(key, length)
        (env_state, obs), (traj, ep_info) = jax.lax.scan(
            _step, (env_state, obs), keys
        )
    return env_state, obs, traj, ep_info


def guard_metrics(enabled: bool, guarded_tree) -> Dict[str, jax.Array]:
    """``{"health_finite": 0/1}`` when ``enabled``, else ``{}``.

    The in-graph all-finite guard the IMPALA learner carries (PR 3),
    shared by the on-policy and off-policy update programs: one fused
    reduction over whatever the trainer stakes its health on (loss,
    grads, updated params), read host-side by the run loop's sentinel.
    Metrics-only — the params math is untouched."""
    if not enabled:
        return {}
    from actor_critic_algs_on_tensorflow_tpu.utils import health as health_lib

    return {
        "health_finite": health_lib.all_finite(guarded_tree).astype(
            jnp.float32
        )
    }


def global_normalize_advantages(
    adv: jax.Array,
    axis_name: str | Tuple[str, ...] | None = DATA_AXIS,
    eps: float = 1e-8,
):
    """Whiten advantages with GLOBAL (cross-device) statistics.

    Inside ``shard_map`` a per-shard mean/std would make gradients
    device-count-dependent; pmean-ing the moments keeps data-parallel
    runs equivalent to single-device large-batch runs.
    """
    mean = jnp.mean(adv)
    if axis_name is not None:
        mean = jax.lax.pmean(mean, axis_name)
    var = jnp.mean((adv - mean) ** 2)
    if axis_name is not None:
        var = jax.lax.pmean(var, axis_name)
    return (adv - mean) * jax.lax.rsqrt(var + eps)


def episode_metrics(ep_info, axis_name: str | None = DATA_AXIS):
    """Mean return/length over episodes finished in this rollout.

    Cross-device reduction via psum so the result is replicated.
    """
    done = ep_info["done_episode"]
    ret_sum = jnp.sum(ep_info["episode_return"] * done)
    n = jnp.sum(done)
    if axis_name is not None:
        ret_sum = jax.lax.psum(ret_sum, axis_name)
        n = jax.lax.psum(n, axis_name)
    return {
        "episodes": n,
        "avg_return": ret_sum / jnp.maximum(n, 1.0),
    }


def evaluate(
    env,
    env_params,
    act_fn: Callable[[Any, jax.Array], jax.Array],
    key: jax.Array,
    *,
    num_envs: int,
    max_steps: int = 1000,
    record: bool = False,
    act_state=None,
):
    """Greedy/stochastic policy evaluation on a vectorized env.

    Runs until each env finishes its FIRST episode (or ``max_steps``).
    ``act_fn(obs, key) -> actions``. Returns ``(mean_return,
    per_env_returns, fraction_finished)``; jit-compiled by the caller.
    With ``record=True`` returns a fourth element: env 0's per-step
    observations ``[max_steps, ...]`` plus its ``done`` flags
    ``[max_steps]`` (for trimming to the first episode).

    ``act_state`` (recurrent policies): an initial per-env policy-state
    pytree with leaves ``[num_envs, ...]``; ``act_fn`` then has the
    stateful signature ``(obs, key, act_state) -> (actions, act_state)``
    and the state is zeroed on episode boundaries here.
    """

    def _step(carry, k):
        env_state, obs, done_seen, ep_ret, ast = carry
        k_act, k_env = jax.random.split(k)
        if act_state is None:
            actions = act_fn(obs, k_act)
        else:
            actions, ast = act_fn(obs, k_act, ast)
        env_state, next_obs, _, done, info = env.step(
            k_env, env_state, actions, env_params
        )
        if act_state is not None:
            # Zero the policy state where an episode just ended, so the
            # (auto-reset) next episode starts from a fresh carry.
            ast = jax.tree_util.tree_map(
                lambda x: x * (1.0 - done).reshape(
                    (num_envs,) + (1,) * (x.ndim - 1)
                ).astype(x.dtype),
                ast,
            )
        ep_ret = jnp.where(
            done_seen > 0.5,
            ep_ret,
            jnp.where(done > 0.5, info["episode_return"], ep_ret),
        )
        new_done_seen = jnp.maximum(done_seen, done)
        out = (obs[0], done_seen[0]) if record else None
        return (env_state, next_obs, new_done_seen, ep_ret, ast), out

    k_reset, k_run = jax.random.split(key)
    env_state, obs = env.reset(k_reset, env_params)
    init = (
        env_state,
        obs,
        jnp.zeros(num_envs),
        jnp.zeros(num_envs),
        act_state,
    )
    (env_state, obs, done_seen, ep_ret, _), rec = jax.lax.scan(
        _step, init, jax.random.split(k_run, max_steps)
    )
    if record:
        frames, done_before = rec
        return jnp.mean(ep_ret), ep_ret, jnp.mean(done_seen), (
            frames,
            done_before,
        )
    return jnp.mean(ep_ret), ep_ret, jnp.mean(done_seen)


class IterationFns(NamedTuple):
    """A compiled training program: ``init`` and one fused iteration.

    ``collect`` and ``block_grads`` (recurrent PPO only, else None) are
    the iteration's two halves as jitted programs of their own, built
    from the closures the fused iteration traces: ``collect(state) ->
    (traj, carry0)`` is the rollout alone, ``block_grads(params, block)
    -> (loss, parts, grads)`` the loss and gradient of one env block
    before the optimizer. They exist so that a check can read what the
    timed path computes rather than a re-composition of it."""

    init: Callable[[jax.Array], OnPolicyState]
    iteration: Callable[[OnPolicyState], Tuple[OnPolicyState, Dict[str, jax.Array]]]
    mesh: Mesh
    steps_per_iteration: int
    collect: Callable | None = None
    block_grads: Callable | None = None


def build_data_parallel_iteration(
    local_iteration: Callable,
    example_state: OnPolicyState,
    mesh: Mesh,
) -> Callable:
    """Wrap a per-device iteration in ``shard_map`` + ``jit``.

    ``local_iteration(state) -> (state, metrics)`` sees local env
    shards and full (replicated) params; it must pmean/psum anything
    that crosses devices (grads, metrics). Donation of the input state
    makes HBM buffers reusable across iterations.
    """
    return build_shard_map_iteration(
        local_iteration, state_specs(example_state), mesh
    )



class RateClock:
    """Windowed env-steps/sec accounting shared by the training loops.

    Excludes the compiling first iteration from every window (compile
    is a host-side dispatch cost); short tail windows fall back to the
    cumulative post-compile rate."""

    def __init__(self, steps_per_iteration: int, log_interval_iters: int):
        self.spi = steps_per_iteration
        self.interval = log_interval_iters
        now = time.perf_counter()
        self.t0 = now
        self.t1 = now
        self.last_it, self.last_t = 0, now

    def first_iteration_done(self) -> None:
        self.t1 = time.perf_counter()
        self.last_it, self.last_t = 1, self.t1

    def rate(self, it: int) -> float:
        """steps/sec at 0-based iteration ``it`` (just completed)."""
        now = time.perf_counter()
        window = it + 1 - self.last_it
        if window >= max(self.interval - 1, 1):
            r = window * self.spi / max(now - self.last_t, 1e-9)
        elif it >= 1:
            r = it * self.spi / max(now - self.t1, 1e-9)
        else:
            r = self.spi / max(now - self.t0, 1e-9)
        self.last_it, self.last_t = it + 1, now
        return r


def emit_log(env_steps, m, history, summary_writer, log_fn) -> None:
    """Append to history and fan out to the writer/printer."""
    from actor_critic_algs_on_tensorflow_tpu.utils.metrics import (
        format_metrics,
    )

    history.append((env_steps, m))
    if summary_writer is not None:
        summary_writer.add_scalars(m, env_steps)
    if log_fn is not None:
        log_fn(env_steps, m)
    else:
        print(format_metrics(env_steps, m), flush=True)


def run_loop(
    fns: IterationFns,
    *,
    total_env_steps: int,
    seed: int = 0,
    log_interval_iters: int = 20,
    log_fn: Callable[[int, Dict[str, float]], None] | None = None,
    checkpointer=None,
    checkpoint_interval_iters: int = 0,
    state: OnPolicyState | None = None,
    summary_writer=None,
    sentinel=None,
):
    """Host-side training loop: dispatch iterations, surface metrics.

    Returns ``(final_state, history)`` where ``history`` is a list of
    (env_steps, metrics-dict) tuples fetched at log intervals.
    ``summary_writer`` (utils.tensorboard.SummaryWriter) additionally
    receives every logged metric dict.

    ``sentinel`` (utils.health.TrainingHealthSentinel) reads each
    iteration's ``health_finite`` guard bit (emitted when the trainer's
    ``numerics_guards`` is on) and rolls the FULL train state back to a
    last-good snapshot on a trip — the PR-3 IMPALA sentinel glue,
    shared by every checkpointed trainer: these loops could already
    persist a poisoned state; now they refuse to keep one.
    """
    from actor_critic_algs_on_tensorflow_tpu.utils.metrics import (
        device_get_metrics,
        format_metrics,
    )

    if state is None:
        state = fns.init(jax.random.PRNGKey(seed))
    # XLA's in-process CPU communicator deadlocks when collectives from
    # multiple in-flight executions interleave (observed: rendezvous
    # timeout with 6/8 arrivals). On the virtual CPU mesh we serialize
    # executions; on real TPU meshes async dispatch pipelines freely.
    serialize = (
        jax.default_backend() == "cpu" and device_count(fns.mesh) > 1
    )
    # ``state.step`` counts ITERATIONS; total_env_steps is a global
    # budget, so a resumed state trains only the remainder — possibly
    # nothing. A fresh run always trains at least one iteration.
    iters_done0 = int(state.step)
    steps_done0 = iters_done0 * fns.steps_per_iteration
    num_iters = (total_env_steps - steps_done0) // fns.steps_per_iteration
    if iters_done0 == 0:
        num_iters = max(1, num_iters)
    if num_iters <= 0:
        return state, []
    history = []
    clock = RateClock(fns.steps_per_iteration, log_interval_iters)
    last_metrics = None
    # Episode stats are aggregated over the WHOLE log window with
    # on-device scalar accumulators (fetched only at log time), not
    # sampled from the boundary iteration: envs whose episodes all
    # truncate at the same step (e.g. the 50-step reacher) finish
    # episodes in only ~1 of every ep_len/steps_per_iter iterations,
    # so a sampled boundary iteration usually reports episodes=0.
    ep_count = ret_sum = None
    if sentinel is not None:
        # The pre-loop (or resumed) state is the first rollback target.
        sentinel.seed(state, iters_done0 - 1)
    for it in profiling.traced_steps(range(num_iters)):
        state, metrics = fns.iteration(state)
        last_metrics = metrics
        if sentinel is not None:
            with profiling.span(profiling.SENTINEL_CHECK):
                state = sentinel.after_step(iters_done0 + it, state, metrics)
        if "episodes" in metrics:
            n = metrics["episodes"]
            r = metrics["avg_return"] * n
            if ep_count is None:
                ep_count, ret_sum = n, r
            else:
                ep_count, ret_sum = ep_count + n, ret_sum + r
        if serialize:
            jax.block_until_ready(metrics)
        if it == 0:
            clock.first_iteration_done()
        if (it + 1) % log_interval_iters == 0 or it == num_iters - 1:
            fetch = dict(metrics)
            if ep_count is not None:
                fetch["episodes"] = ep_count
                fetch["_window_return_sum"] = ret_sum
            with profiling.span(profiling.LOG_FETCH):
                m = device_get_metrics(fetch)
            if ep_count is not None:
                rs = m.pop("_window_return_sum")
                m["avg_return"] = rs / m["episodes"] if m["episodes"] else 0.0
                ep_count = ret_sum = None
            env_steps = steps_done0 + (it + 1) * fns.steps_per_iteration
            m["steps_per_sec"] = clock.rate(it)
            emit_log(env_steps, m, history, summary_writer, log_fn)
        if (
            checkpointer is not None
            and checkpoint_interval_iters
            and (it + 1) % checkpoint_interval_iters == 0
        ):
            # Resolve any pending delayed-guard verdict first — a
            # checkpoint must never capture a state whose own step
            # went unchecked (the monotonic guard below would pin a
            # poisoned save as latest forever).
            if sentinel is not None:
                state = sentinel.flush(state)
            # Id from state.step, not the loop counter: a sentinel
            # rollback rewinds state.step while ``it`` marches on, and
            # orbax silently refuses non-monotonic ids anyway (same
            # hardening as the IMPALA loop). Without a rollback the two
            # derivations are identical.
            ckpt_id = (
                int(jax.device_get(state.step)) * fns.steps_per_iteration
            )
            latest = checkpointer.latest_step()
            if latest is None or ckpt_id > latest:
                checkpointer.save(ckpt_id, state)
    if sentinel is not None:
        # Delayed guard mode: resolve the last pending verdict so the
        # caller never checkpoints a state whose final step went
        # unchecked.
        state = sentinel.flush(state)
    jax.block_until_ready(last_metrics)
    return state, history
