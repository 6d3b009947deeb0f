"""Checkpoint evaluation: the reference-era "enjoy/eval script".

Capability parity: TF actor-critic repos pair every train.py with an
evaluation path that restores a checkpoint and rolls the greedy (or
stochastic) policy (SURVEY.md L6 entry-point surface; §5
checkpoint/resume row). TPU-native: the whole evaluation — env scan +
policy forward — is one jitted program via ``common.evaluate``.

Model reconstruction mirrors each trainer's construction in
``make_a2c``/``make_ppo``/``make_ddpg``/``make_td3``/``make_sac``/``make_impala``;
if a trainer's architecture wiring changes, change ``_act_fn`` to
match (the round-trip test in tests/test_cli.py catches drift).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
from actor_critic_algs_on_tensorflow_tpu.algos import common
from actor_critic_algs_on_tensorflow_tpu.models import (
    SEQUENCE_CORES,
    DeterministicActor,
    DiscreteActorCritic,
    GaussianActorCritic,
    RecurrentActorCritic,
    SquashedGaussianActor,
)
from actor_critic_algs_on_tensorflow_tpu.ops import (
    Categorical,
    DiagGaussian,
    TanhGaussian,
    rms_normalize,
)


def _act_fn(algo: str, cfg, aspace, params, stochastic: bool, norm=None,
            num_envs: int = 1):
    """``(act, act_state0)`` matching the trainer's architecture.

    ``norm`` preprocesses obs (e.g. the restored running-mean/std
    normalizer a normalize_obs=True PPO policy was trained with).
    ``act_state0`` is ``None`` for feed-forward policies; recurrent
    policies return their initial LSTM carry and a stateful ``act``
    (see ``common.evaluate``).
    """
    norm = norm if norm is not None else (lambda o: o)
    act_state0 = None
    if getattr(cfg, "torso", None) in SEQUENCE_CORES:
        raise NotImplementedError(
            "evaluation acts through RecurrentActorCritic's (c, h) carry; "
            f"acting with a sequence core (torso={cfg.torso!r}, one of "
            f"models.SEQUENCE_CORES {sorted(SEQUENCE_CORES)}: its carry "
            "held per lane) is not built yet (ROADMAP, Reach)"
        )
    if algo in ("a2c", "ppo", "impala") and getattr(cfg, "recurrent", False):
        model = RecurrentActorCritic(
            num_actions=aspace.n,
            torso=cfg.torso,
            hidden_sizes=cfg.hidden_sizes,
            lstm_size=cfg.lstm_size,
            dtype=jnp.dtype(cfg.compute_dtype),
        )
        act_state0 = model.initialize_carry(num_envs)

        def act(obs, key, carry):
            # common.evaluate zeroes the carry on episode boundaries, so
            # the in-call reset mask is constant zero.
            logits, _, carry = model.apply(
                params, norm(obs)[None], jnp.zeros((1, obs.shape[0])), carry
            )
            if stochastic:
                return Categorical(logits).sample(key)[0], carry
            return jnp.argmax(logits[0], axis=-1), carry

        return act, act_state0
    if algo in ("a2c", "ppo", "impala"):
        if hasattr(aspace, "n"):
            model = DiscreteActorCritic(
                num_actions=aspace.n,
                torso=cfg.torso,
                hidden_sizes=cfg.hidden_sizes,
                dtype=jnp.dtype(cfg.compute_dtype),
            )

            def act(obs, key):
                logits, _ = model.apply(params, norm(obs))
                if stochastic:
                    return Categorical(logits).sample(key)
                return jnp.argmax(logits, axis=-1)
        else:
            model = GaussianActorCritic(
                action_dim=aspace.shape[-1],
                hidden_sizes=cfg.hidden_sizes,
                dtype=jnp.dtype(cfg.compute_dtype),
            )

            def act(obs, key):
                mean, log_std, _ = model.apply(params, norm(obs))
                if stochastic:
                    return DiagGaussian(mean, log_std).sample(key)
                return mean
    elif algo in ("ddpg", "td3"):
        actor = DeterministicActor(aspace.shape[-1], cfg.hidden_sizes)
        scale = float(aspace.high)

        def act(obs, key):
            return actor.apply(params.actor, norm(obs)) * scale
    elif algo == "sac":
        actor = SquashedGaussianActor(aspace.shape[-1], cfg.hidden_sizes)
        scale = float(aspace.high)

        def act(obs, key):
            mean, log_std = actor.apply(params.actor, norm(obs))
            if stochastic:
                return TanhGaussian(mean, log_std).sample(key) * scale
            return jnp.tanh(mean) * scale
    else:
        raise ValueError(f"unknown algo {algo!r}")
    return act, act_state0


def _make_init(algo: str, cfg):
    if algo == "a2c":
        from actor_critic_algs_on_tensorflow_tpu.algos.a2c import make_a2c

        return make_a2c(cfg).init
    if algo == "ppo":
        from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

        return make_ppo(cfg).init
    if algo == "ddpg":
        from actor_critic_algs_on_tensorflow_tpu.algos.ddpg import make_ddpg

        return make_ddpg(cfg).init
    if algo == "td3":
        from actor_critic_algs_on_tensorflow_tpu.algos.td3 import make_td3

        return make_td3(cfg).init
    if algo == "sac":
        from actor_critic_algs_on_tensorflow_tpu.algos.sac import make_sac

        return make_sac(cfg).init
    if algo == "impala":
        from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
            make_impala,
        )

        return make_impala(cfg).init
    raise ValueError(f"unknown algo {algo!r}")


def evaluate_checkpoint(
    algo: str,
    cfg: Any,
    checkpoint_dir: str,
    *,
    num_envs: int = 32,
    max_steps: int = 1000,
    stochastic: bool = False,
    seed: int = 1234,
    render_dir: str | None = None,
) -> Tuple[float, np.ndarray, float]:
    """Restore the latest checkpoint and roll the policy.

    Returns ``(mean_return, per_env_returns, fraction_finished)`` over
    each env's first episode (capped at ``max_steps``).

    ``render_dir`` additionally records env 0's first episode: image
    observations become an animated ``episode.gif`` (newest frame of
    the stack, nearest-upscaled 3x); vector observations are saved as
    ``episode.npy`` (``[T, obs_dim]``) — the classic "enjoy script"
    artifact (SURVEY.md L6).
    """
    from actor_critic_algs_on_tensorflow_tpu.utils.checkpoint import (
        Checkpointer,
        obs_norm_restore_guard,
    )

    ckpt = Checkpointer(checkpoint_dir)
    if ckpt.latest_step() is None:
        raise FileNotFoundError(f"no checkpoint in {checkpoint_dir}")
    template = _make_init(algo, cfg)(jax.random.PRNGKey(cfg.seed))
    state = ckpt.restore(
        template, forbid_defaulted=obs_norm_restore_guard(cfg)
    )
    ckpt.close()

    env, env_params = envs_lib.make(
        cfg.env,
        num_envs=num_envs,
        frame_stack=getattr(cfg, "frame_stack", 0),
    )
    norm = None
    if getattr(cfg, "normalize_obs", False):
        # PPO keeps the running stats in state.extra; the off-policy
        # trainers (DDPG/TD3/SAC) in params.obs_rms (their state has
        # no extra slot).
        rms = (
            state.params.obs_rms
            if hasattr(state.params, "obs_rms")
            else state.extra
        )
        norm = lambda o: rms_normalize(o, rms)
    act, act_state0 = _act_fn(
        algo, cfg, env.action_space(env_params), state.params, stochastic,
        norm=norm, num_envs=num_envs,
    )
    record = render_dir is not None
    out = jax.jit(
        lambda key: common.evaluate(
            env, env_params, act, key,
            num_envs=num_envs, max_steps=max_steps, record=record,
            act_state=act_state0,
        )
    )(jax.random.PRNGKey(seed))
    if record:
        mean_ret, per_env, frac, (frames, done_before) = out
        _write_episode(
            render_dir, np.asarray(frames), np.asarray(done_before)
        )
    else:
        mean_ret, per_env, frac = out
    return float(mean_ret), np.asarray(per_env), float(frac)


def _write_episode(render_dir: str, frames: np.ndarray, done_before: np.ndarray) -> None:
    """Trim to env 0's first episode and write gif (images) or npy."""
    import os

    os.makedirs(render_dir, exist_ok=True)
    # done_before[t] == 1 once the episode has ALREADY finished.
    alive = done_before < 0.5
    frames = frames[alive]
    if frames.ndim == 4 and frames.shape[1] >= 16 and frames.shape[2] >= 16:
        try:
            from PIL import Image
        except ImportError:
            np.save(os.path.join(render_dir, "episode.npy"), frames)
            print(f"[eval] wrote {render_dir}/episode.npy (no PIL)")
            return
        imgs = []
        for f in frames:
            newest = f[..., -1]
            if newest.dtype != np.uint8:
                newest = np.clip(newest * 255.0, 0, 255).astype(np.uint8)
            img = Image.fromarray(newest, mode="L")
            imgs.append(
                img.resize((img.width * 3, img.height * 3), Image.NEAREST)
            )
        path = os.path.join(render_dir, "episode.gif")
        imgs[0].save(
            path, save_all=True, append_images=imgs[1:], duration=30, loop=0
        )
        print(f"[eval] wrote {path} ({len(imgs)} frames)")
    else:
        path = os.path.join(render_dir, "episode.npy")
        np.save(path, frames)
        print(f"[eval] wrote {path} {frames.shape}")
