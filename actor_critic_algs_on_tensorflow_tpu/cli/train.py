"""train.py-style CLI entrypoints.

Capability parity: the reference's public surface is command-line
``train.py`` invocations selecting algorithm + env + hyperparameters
(BASELINE.json:5 — "the existing train.py entrypoints"; SURVEY.md L6).
The five baseline workloads (BASELINE.json:7-11) are checked in as
named presets:

    python train.py --preset a2c-cartpole
    python train.py --preset ppo-pong
    python train.py --preset ddpg-halfcheetah
    python train.py --preset sac-humanoid
    python train.py --preset impala-cartpole

or explicitly:

    python train.py --algo ppo --env PongTPU-v0 --total-steps 1000000 \
        --set torso=nature_cnn --set frame_stack=4

``--set key=value`` overrides any config dataclass field with type
coercion from the field's declared type.

IMPALA's device-resident fast path (Podracer/Anakin) rides the same
surface: ``--preset impala-cartpole --set rollout_mode=device`` fuses
env.step + act + V-trace into one jitted program (in-process, pure-JAX
envs only); ``--set rollout_mode=mixed`` with ``--actor-processes``
interleaves device self-play with the wire actor fleet.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Any, Tuple


def apply_overrides(cfg, overrides: list[str]):
    """Apply ``key=value`` strings to a frozen config dataclass.

    Thin CLI shim over ``utils.config.apply_overrides`` (value-typed
    coercion, dotted paths for nested configs) that converts errors to
    argparse-style exits.
    """
    from actor_critic_algs_on_tensorflow_tpu.utils.config import (
        apply_overrides as _apply,
    )

    try:
        return _apply(cfg, tuple(overrides))
    except KeyError as e:
        raise SystemExit(f"unknown config field: {e.args[0]}")
    except ValueError as e:
        raise SystemExit(f"--set error: {e}")


# The TPU-tuned large-batch Atari schedule shared by the image-env
# PPO presets (see the ppo-pong comment for the measurements). Swept
# on one v5e chip: 2 epochs @ lr 2e-3 reaches Pong avg_return >= 19 in
# 45-50 s (~12-13M steps) across seeds, vs 66 s for the 4-epoch
# lr 1e-3 schedule — fewer update epochs trade sample efficiency for
# wall-clock at this batch size.
_PPO_ATARI_SCHEDULE = {
    "num_envs": 1024,
    "rollout_length": 128,
    "torso": "nature_cnn",
    "frame_stack": 4,
    "total_env_steps": 25_000_000,
    "lr": 2e-3,
    "lr_decay": False,
    "num_epochs": 2,
    "time_limit_bootstrap": False,
    "compute_dtype": "bfloat16",
}

def _qwen3_next_config(**kw):
    from actor_critic_algs_on_tensorflow_tpu.models.qwen3_next import (
        Qwen3NextConfig,
    )

    return Qwen3NextConfig(**kw)


def _kimi_vl_config(**kw):
    from actor_critic_algs_on_tensorflow_tpu.models.kimi_vl import (
        KimiVLConfig,
    )

    return KimiVLConfig(**kw)


def _sdar_config(**kw):
    from actor_critic_algs_on_tensorflow_tpu.models.sdar import SDARConfig

    return SDARConfig(**kw)


def _granite_hybrid_config(**kw):
    from actor_critic_algs_on_tensorflow_tpu.models.granite_hybrid import (
        GraniteHybridConfig,
    )

    return GraniteHybridConfig(**kw)


def _xing_config(**kw):
    from actor_critic_algs_on_tensorflow_tpu.models.xing import XingConfig

    return XingConfig(**kw)


def _lfm2_config(**kw):
    from actor_critic_algs_on_tensorflow_tpu.models.lfm2 import Lfm2Config

    return Lfm2Config(**kw)


def _snapshot_fits(fns, cfg) -> bool:
    """Whether a device has room for the sentinel's rollback target, a
    second copy of the train state: three times the state within the
    device's memory (the state, its copy, and for the iteration's own
    temporaries, gradients first, as much again). The sequence-core
    presets hold 8 GB of parameters and Adam moments on a 16 GB chip
    and do not. A backend that reports no capacity (the CPU) has room."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return True
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(cfg.seed))
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(state))
    return 3 * nbytes <= limit


def _token_recall_params(**kw):
    from actor_critic_algs_on_tensorflow_tpu.envs.token_recall import (
        TokenRecallParams,
    )

    return TokenRecallParams(**kw)


def _block_turns_params(**kw):
    from actor_critic_algs_on_tensorflow_tpu.envs.block_turns import (
        BlockTurnsParams,
    )

    return BlockTurnsParams(**kw)


# Token-level PPO as RL fine-tuning runs it: one episode one sequence,
# undiscounted return, no entropy bonus, whole sequences a minibatch.
_PPO_TOKEN_SCHEDULE = {
    "recurrent": True,
    "time_limit_bootstrap": False,
    "num_epochs": 1,
    "num_minibatches": 4,
    "shuffle": "env",
    "lr": 1e-5,
    "lr_decay": False,
    "gamma": 1.0,
    "gae_lambda": 0.95,
    "clip_eps": 0.2,
    "vf_clip": True,
    "vf_coef": 0.5,
    "ent_coef": 0.0,
    "max_grad_norm": 1.0,
    "normalize_adv": True,
}


PRESETS = {
    # 1. A2C on CartPole-v1: 2-layer MLP, sync actors (BASELINE.json:7)
    "a2c-cartpole": ("a2c", {"env": "CartPole-v1", "total_env_steps": 500_000}),
    # 2. PPO on Atari-class Pong: Nature-CNN over stacked 84x84 frames
    # (BASELINE.json:8). TPU-tuned large-batch config: 1024 on-device
    # envs, bf16 torso, whole-batch epochs (num_minibatches=1 skips
    # the 3.7 GB shuffle-gather per epoch — +43% steps/s over the
    # 4-minibatch schedule) with lr raised to 8e-3 to compensate for
    # the 4x fewer optimizer updates. Measured on one v5e chip:
    # ~370k env-steps/s, avg_return >= 19 by 20-24M steps and ~20
    # at the full 25M budget in ~67 s wall-clock (seeds 0/1/2). The
    # classic 8-env schedule needs ~100x more gradient updates per env
    # step and learns far slower at this batch size.
    "ppo-pong": (
        "ppo",
        {
            "env": "PongTPU-v0",
            **_PPO_ATARI_SCHEDULE,
            "num_minibatches": 1,
            "lr": 8e-3,
        },
    ),
    # 3. DDPG on MuJoCo HalfCheetah: OU-noise explore (BASELINE.json:9).
    # normalize_obs defaults ON (as on sac-humanoid): two full-1M
    # seeds measured final windows 7,485/7,825 vs 6,357 unnormalized,
    # greedy evals 9,111/10,462 (PERF.md). Resuming OR evaluating a
    # checkpoint trained without it needs --set normalize_obs=False.
    "ddpg-halfcheetah": (
        "ddpg",
        {
            "env": "gym:HalfCheetah-v4",
            "num_envs": 8,
            "num_devices": 1,
            "total_env_steps": 1_000_000,
            "normalize_obs": True,
        },
    ),
    # DDPG successor: twin delayed DDPG on the same MuJoCo task.
    # normalize_obs ON: final windows 8,892/7,107 vs 6,374, greedy
    # evals 9,665/8,034 across two seeds (PERF.md).
    "td3-halfcheetah": (
        "td3",
        {
            "env": "gym:HalfCheetah-v4",
            "num_envs": 8,
            "num_devices": 1,
            "total_env_steps": 1_000_000,
            "normalize_obs": True,
        },
    ),
    # 4. SAC on Humanoid: twin-Q + learned alpha (BASELINE.json:10).
    # normalize_obs defaults ON here: three full-3M seeds measured
    # post-2M means 7,752/8,419/6,594 vs 4,891/3,950 unnormalized
    # (greedy evals 7,946/9,950/3,935 vs 4,351/4,230 — PERF.md). To
    # resume OR --eval a checkpoint trained without it, pass
    # --set normalize_obs=False (the stats field changes the params
    # layout).
    "sac-humanoid": (
        "sac",
        {
            "env": "gym:Humanoid-v4",
            "num_envs": 8,
            "num_devices": 1,
            "total_env_steps": 3_000_000,
            "normalize_obs": True,
        },
    ),
    # 5. IMPALA / distributed A3C with V-trace (BASELINE.json:11).
    # batch_trajectories=1 + lr 1e-3 (r3): small frequent updates are
    # what solves CartPole at this budget — the old defaults (batch 8,
    # lr 6e-4 decayed over only 488 learner steps) plateaued at ~46;
    # this schedule reaches 386-477 windows by ~1M (solved >195).
    "impala-cartpole": (
        "impala",
        {
            "env": "CartPole-v1",
            "num_actors": 8,
            "total_env_steps": 1_000_000,
            "batch_trajectories": 1,
            "lr": 1e-3,
            # Single-learner topology: the 1-trajectory batch doesn't
            # divide wider DP meshes (scale via actors/envs instead).
            "num_devices": 1,
        },
    ),
    # 6. PPO on the second Atari-class on-device task (Breakout-style
    # brick wall, 4 actions, 5 lives). r3 schedule sweep (17 probes at
    # 4.2M steps, PERF.md "ppo-breakout schedule frontier"): breakout
    # rewards UPDATE COUNT — returns rise monotonically from mb=1
    # (collapse) through mb=4 (preset was 29.8) to a peak at mb=16
    # (50.5), falling slightly at mb=32/64 (~46); lr 1e-3 beats 5e-4,
    # 1.5e-3, 2e-3, 3e-3 at every minibatch count tried, and extra
    # entropy (0.02) or epochs (6) only hurt. The 16-minibatch epoch
    # costs no throughput at this batch size (~156k steps/s either
    # way). Full 25M budget (seed 0): avg_return 163 was the OLD mb=4
    # curve's endpoint; the shipped mb=16 schedule's curve is in
    # PERF.md. (The r1 note "88 by 4M" did not reproduce and was
    # corrected in r2; whole-batch mb=1 entropy-collapses here — the
    # brick-wall task is the anti-Pong, see PERF.md ledger.)
    # r4: shuffle="env" (contiguous env-sliced minibatches, visit order
    # permuted per epoch — no full-buffer gather) replaced the random
    # flat shuffle after a side-by-side 4.2M probe (88.7 vs 46.1) and a
    # 3-seed 25M validation: final windows 293/261/302 (mean 285) vs
    # 159.8 for the flat-shuffle schedule re-run under the same
    # (r4 window-aggregated) metric — the r3-recorded 195/238/189 were
    # boundary-iteration samples, so compare 285 vs ~160-207. Both at
    # ~163k vs ~159k steps/s: the throughput gain is small (the mb=16
    # gather was already amortized); the LEARNING gain is not — see
    # PERF.md "shuffle='env'".
    "ppo-breakout": (
        "ppo",
        {
            "env": "BreakoutTPU-v0",
            **_PPO_ATARI_SCHEDULE,
            "num_epochs": 4,
            "num_minibatches": 16,
            "lr": 1e-3,
            "shuffle": "env",
        },
    ),
    # 7. IMPALA on the Atari-class on-device Pong: the async
    # actor-learner path solving the headline task. Topology from the
    # r2 actor-width sweep: ONE 256-env actor at the same ~8k-step
    # learner batch keeps the rollout conv MXU-fed (the r1 2x64
    # config starved it at width 64; the deep-queue config measured
    # ~405-437k env-steps/s in rounds 2-3, vs 159k).
    # r4 flipped the preset to the STABLE schedule (linear lr decay +
    # queue_size=2, i.e. off-policy lag bounded at ~2 batches): at
    # the end-of-round-4 actor throughput (~240k steps/s, where the
    # deep-queue speed edge is gone — both schedules measured
    # 225-299k) the old constant-lr deep-queue schedule landed its
    # final 25M window inside a transient dip in 2 of 5 re-runs
    # (-17/-1.7), while the stable schedule reaches the plateau
    # FASTER (onset 8.8-11.1M vs 13.9-14.4M) and finals
    # 20.17/20.0/20.0 across three seeds; the 3x50M probes show zero
    # sub-15 windows past onset+2M (PERF.md "Long-budget
    # stabilization"). Constant lr + queue_size=16 remains available
    # via --set. RESUMING a pre-r4 checkpoint: pass
    # --set lr_decay=False --set queue_size=16 — the schedule change
    # alters the optimizer-state layout, and a grafted restore would
    # silently restart the decay horizon.
    "impala-pong": (
        "impala",
        {
            "env": "PongTPU-v0",
            "torso": "nature_cnn",
            "frame_stack": 4,
            "compute_dtype": "bfloat16",
            "num_actors": 1,
            "envs_per_actor": 256,
            "rollout_length": 32,
            "batch_trajectories": 1,
            "lr": 1e-3,
            "lr_decay": True,
            "queue_size": 2,
            "ent_coef": 0.01,
            "total_env_steps": 25_000_000,
        },
    ),
    # 8. SAC on the on-device two-link Reacher (multi-dim continuous
    # actions; no host env in the loop, unlike the MuJoCo presets).
    # Measured: greedy eval -8.8 -> -6.8 in 200k steps.
    "sac-reacher": (
        "sac",
        {
            "env": "ReacherTPU-v0",
            "num_envs": 32,
            "num_devices": 1,
            "warmup_env_steps": 5_000,
            "total_env_steps": 200_000,
        },
    ),
    # 9. Classic A3C: async actors, n-step targets, no off-policy
    # correction (the correction="none" mode of the IMPALA topology).
    # Same r3 schedule fix as impala-cartpole (small frequent
    # updates): 298 @ 1M (solved), vs 39 on the old batch-8 defaults.
    # r4 sweep: on the r3 batch=1 schedule, lr 2e-3 dominates 1e-3 —
    # final windows 500/500/362 across seeds 0/1/2 (500 = the env
    # cap) vs 298; 1.5e-3 scored 304 (500 with ent 0.005), 1e-3+ent
    # 0.005 scored 253.
    "a3c-cartpole": (
        "impala",
        {
            "env": "CartPole-v1",
            "num_actors": 8,
            "correction": "none",
            "total_env_steps": 1_000_000,
            "batch_trajectories": 1,
            "lr": 2e-3,
            "num_devices": 1,  # see impala-cartpole
        },
    ),
    # 10. Recurrent (LSTM) PPO on the velocity-masked CartPole POMDP —
    # the partially-observable model family (IMPALA-paper LSTM class).
    # Schedule from the r4 probe grid: lr 1e-3 is the lever (2.5e-4
    # never breaks past the uniform-policy plateau in this budget);
    # shuffle="env" supplies the whole-trajectory minibatches the
    # recurrent replay requires. Measured (seed 0, 600k steps): greedy
    # eval 499/500 (the env cap) vs ~42 for the same schedule without
    # recurrence — memory IS the task here, see PERF.md "Recurrent
    # policy family". The r4 slow-tier test pins >= 300.
    "ppo-masked-cartpole": (
        "ppo",
        {
            "env": "CartPoleMasked-v1",
            "num_envs": 8,
            "rollout_length": 128,
            "total_env_steps": 600_000,
            "recurrent": True,
            "lstm_size": 128,
            "lr": 1e-3,
            "num_minibatches": 4,
            "shuffle": "env",
            "time_limit_bootstrap": False,
            # The 8-env width doesn't divide wider meshes; the tiny
            # workload is single-device anyway.
            "num_devices": 1,
        },
    ),
    # 11. Recurrent (LSTM) PPO on flickering Pong — the Atari-class
    # POMDP benchmark (Hausknecht & Stone 2015): every observation is
    # independently blanked with p=0.5, and frame_stack=1 means even
    # unblanked frames carry no velocity information, so memory is the
    # only route to state. r4 schedule: the masked-cartpole levers
    # (lr 1e-3, shuffle="env" whole-trajectory minibatches) at 256
    # envs, PLUS linear lr decay — constant lr 1e-3 peaks ~14 by 14M
    # then collapses (final 5.3; the fs4 control collapses too), while
    # the decayed schedule lands 3-seed 25M finals 20.08/18.89/19.53
    # with greedy n=64 evals 20.36/19.81/19.91 (32/19/25 perfect 21s).
    # Controls at the same schedule: feed-forward frame_stack=4 16.66
    # (zero perfect episodes), frame_stack=1 (memoryless) -5.75 train /
    # 0.80 greedy. The seed-0 policy evaluated on CLEAN single-frame
    # PongTPU scores 20.12 — the LSTM's state tracking transfers to
    # unflickered play (PERF.md "Flickering Pong").
    "ppo-flicker-pong": (
        "ppo",
        {
            "env": "PongFlickerTPU-v0",
            **_PPO_ATARI_SCHEDULE,
            "frame_stack": 1,
            "recurrent": True,
            "lstm_size": 256,
            "num_envs": 256,
            "num_minibatches": 4,
            "shuffle": "env",
            "lr": 1e-3,
            "lr_decay": True,
        },
    ),
    # 13. Token-level PPO with a Qwen3-Next-80B-A3B policy at the
    # published widths, cut to one chip's share of a stated deployment:
    # each layer shared by 16 chips, expert-parallel (32 of the 512
    # routed experts here, the whole router, shared expert and mixer),
    # one whole period of the layer pattern (3 Gated DeltaNet + 1 gated
    # attention of the 48 layers; the rest lie on further chips as
    # pipeline stages) and 1/8 of the vocabulary: 625.7 M parameters,
    # 10 GB with gradients and Adam's moments. One episode is one
    # sequence of 256 tokens on the token-recall env; the schedule
    # (envs, epochs, minibatches, learning rate) is
    # perfbench/traffic/recall-128x256-e1mb4.json's.
    "ppo-qwen3next-recall": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=18_992, delay=64, episode_length=256
            ),
            "torso": "qwen3_next",
            "seq_model": _qwen3_next_config(
                num_hidden_layers=4, vocab_size=18_992,
                first_expert=0, experts_held=32, capacity_factor=2.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 128,
            "rollout_length": 256,
            "compute_dtype": "bfloat16",
            "total_env_steps": 10_000_000,
        },
    ),
    # The same model and schedule at widths for the CPU tests: hidden
    # 64, 4 + 2 attention heads of 16, 2 + 4 DeltaNet heads of 16, 8
    # experts top-2 of width 32 (2 held), 4 layers, vocabulary 64.
    "ppo-qwen3next-tiny": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=64, delay=4, episode_length=16
            ),
            "torso": "qwen3_next",
            "seq_model": _qwen3_next_config(
                hidden_size=64, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                linear_num_key_heads=2, linear_num_value_heads=4,
                linear_key_head_dim=16, linear_value_head_dim=16,
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32,
                shared_expert_intermediate_size=32, vocab_size=64,
                first_expert=0, experts_held=2, capacity_factor=4.0,
                chunk_size=8,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 8,
            "rollout_length": 16,
            "total_env_steps": 4_096,
            "num_devices": 1,
        },
    ),
    # 14. Token-level PPO with the language model of Kimi-VL-A3B as
    # the policy, at the published widths, cut to one chip's share of a
    # stated deployment: each layer shared by 8 chips, expert-parallel
    # (8 of the 64 routed experts here, the whole router with its
    # selection bias, both shared experts and the whole latent
    # attention), the leading dense layer and 5 of the 26 expert layers
    # (the rest lie on further chips as pipeline stages) and 1/8 of the
    # vocabulary: 668.9 M parameters, 10.7 GB with gradients and Adam's
    # moments. One episode is one sequence of 512 tokens on the
    # token-recall env; the schedule (envs, epochs, minibatches,
    # learning rate) is perfbench/traffic/recall-128x512-e1mb8.json's.
    "ppo-kimivl-recall": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=20_480, delay=64, episode_length=512
            ),
            "torso": "kimi_vl",
            "seq_model": _kimi_vl_config(
                num_hidden_layers=6, vocab_size=20_480,
                first_expert=0, experts_held=8, capacity_factor=2.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 128,
            "rollout_length": 512,
            "num_minibatches": 8,
            "compute_dtype": "bfloat16",
            "total_env_steps": 10_000_000,
        },
    ),
    # The same model and schedule at widths for the CPU tests: hidden
    # 64, 4 heads of 16 + 8 (rope) and 16 (value) over a latent of 32,
    # a dense layer of width 128, then 2 expert layers of 8 experts
    # top-2 of width 32 (2 held) with two shared experts, vocabulary 64.
    "ppo-kimivl-tiny": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=64, delay=4, episode_length=16
            ),
            "torso": "kimi_vl",
            "seq_model": _kimi_vl_config(
                hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, n_routed_experts=8,
                num_experts_per_tok=2, kv_lora_rank=32,
                qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
                vocab_size=64, first_expert=0, experts_held=2,
                capacity_factor=4.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 8,
            "rollout_length": 16,
            "total_env_steps": 4_096,
            "num_devices": 1,
        },
    ),
    # 15. Token-level PPO with SDAR-30B-A3B-Chat as the policy, a
    # language model that generates by diffusion over blocks, at the
    # published widths, cut to one chip's share of a stated deployment:
    # each layer shared by 8 chips, expert-parallel (16 of the 128
    # routed experts here, the whole router and the whole grouped-query
    # attention), 6 of the 48 identical layers (the rest lie on further
    # chips as pipeline stages) and 1/8 of the vocabulary, its last row
    # the mask token: 645.6 M parameters, 10.3 GB with gradients and
    # Adam's moments. An env step is one pass over a block of 4
    # positions: 24 turns of 6 passes (the env's block, 4 denoising
    # passes over the policy's, its commit) are one episode, one
    # sequence of 144 passes that commits 192 tokens; the schedule is
    # perfbench/traffic/turns-128x144-b4k4-e1mb8.json's.
    "ppo-sdar-turns": (
        "ppo",
        {
            "env": "BlockTurnsTPU-v0",
            "env_params": _block_turns_params(
                vocab_size=18_992, block_length=4, denoise_steps=4,
                turns=24, delay_turns=4,
            ),
            "torso": "sdar",
            "seq_model": _sdar_config(
                num_hidden_layers=6, vocab_size=18_992,
                mask_token_id=18_991, first_expert=0, experts_held=16,
                # the update's buffer (the rollout's holds every pair):
                # 10 of a turn's 24 positions are mask tokens, which
                # route alike; 4.0 holds them at all 8 of their experts
                # (PERF.md section 6, PR 33)
                capacity_factor=4.0, block_length=4, denoising_steps=4,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 128,
            "rollout_length": 144,
            "num_minibatches": 8,
            "compute_dtype": "bfloat16",
            "total_env_steps": 10_000_000,
        },
    ),
    # The same model and schedule at widths for the CPU tests: hidden
    # 64, 4 query / 2 key-value heads of 16, 2 layers of 8 experts
    # top-2 of width 32 (4 held), vocabulary 64, 4 turns.
    "ppo-sdar-tiny": (
        "ppo",
        {
            "env": "BlockTurnsTPU-v0",
            "env_params": _block_turns_params(
                vocab_size=64, block_length=4, denoise_steps=4, turns=4,
                delay_turns=1,
            ),
            "torso": "sdar",
            "seq_model": _sdar_config(
                hidden_size=64, intermediate_size=192, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, vocab_size=64, mask_token_id=63,
                first_expert=0, experts_held=4, capacity_factor=4.0,
                block_length=4, denoising_steps=4,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 8,
            "rollout_length": 24,
            "total_env_steps": 4_096,
            "num_devices": 1,
        },
    ),
    # 16. Token-level PPO with Granite-4.0-H-Micro as the policy, a
    # dense hybrid of Mamba-2 state-space layers and attention without
    # positions, at the published widths, cut to one chip's share of a
    # stated deployment: the 40 layers as four pipeline stages of one
    # period each (layers 0-9 here: five Mamba-2, the attention layer,
    # four Mamba-2, every head and the whole SwiGLU of each) and 1/8 of
    # the tied vocabulary: 772.2 M parameters, 12.35 GB with gradients
    # and Adam's moments. One episode is one sequence of 512 tokens
    # (two chunks of the scan) on the token-recall env; the schedule is
    # perfbench/traffic/recall-32x512-e1mb4.json's.
    "ppo-granite-recall": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=12_544, delay=64, episode_length=512
            ),
            "torso": "granite_hybrid",
            "seq_model": _granite_hybrid_config(
                num_hidden_layers=10,
                layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
                vocab_size=12_544,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 32,
            "rollout_length": 512,
            "num_minibatches": 4,
            "compute_dtype": "bfloat16",
            "total_env_steps": 10_000_000,
        },
    ),
    # The same model and schedule at widths for the CPU tests: hidden
    # 64, 8 Mamba-2 heads of 16 over a state of 16 in chunks of 8,
    # 4 query / 2 key-value heads of 16, a SwiGLU of 128, vocabulary
    # 64, a rollout of three chunks.
    "ppo-granite-tiny": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=64, delay=4, episode_length=24
            ),
            "torso": "granite_hybrid",
            "seq_model": _granite_hybrid_config(
                hidden_size=64, num_hidden_layers=3,
                layer_types=("mamba", "attention", "mamba"),
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=8, num_attention_heads=4,
                num_key_value_heads=2, attention_multiplier=0.0625,
                shared_intermediate_size=128, vocab_size=64,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 8,
            "rollout_length": 24,
            "total_env_steps": 4_096,
            "num_devices": 1,
        },
    ),
    # 17. Token-level PPO with Xing4.0-29B-A4B as the policy: Kimi-VL's
    # latent attention (here with a low-rank query and YaRN positions)
    # and sigmoid-routed experts, every sublayer wrapped in
    # manifold-constrained hyper-connections (four float32 residual
    # streams mixed by a Sinkhorn-projected matrix), at the published
    # widths, cut to one chip's share of a stated deployment: each
    # expert layer shared by 8 chips, expert-parallel (8 of the 64
    # routed experts here, the whole router, the shared expert, MLA and
    # mHC), the leading dense layer and 4 of the 38 expert layers (the
    # rest lie on further chips as pipeline stages) and 1/8 of the
    # vocabulary: 759.4 M parameters, 12.15 GB with gradients and
    # Adam's moments. One episode is one sequence of 256 tokens on the
    # token-recall env; the schedule is
    # perfbench/traffic/recall-128x256-e1mb8.json's.
    "ppo-xing-recall": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=16_384, delay=64, episode_length=256
            ),
            "torso": "xing",
            "seq_model": _xing_config(
                num_hidden_layers=5, first_k_dense_replace=1,
                vocab_size=16_384, first_expert=0, experts_held=8,
                capacity_factor=2.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 128,
            "rollout_length": 256,
            "num_minibatches": 8,
            "compute_dtype": "bfloat16",
            "total_env_steps": 10_000_000,
        },
    ),
    # The same model and schedule at widths for the CPU tests: hidden
    # 64, 4 streams and 3 Sinkhorn iterations, 4 heads of 16 + 8 (rope)
    # and 16 (value) over a latent of 32 and a query of rank 16, a
    # dense layer of width 128, then 2 expert layers of 8 experts top-2
    # of width 32 (4 held) with a shared one, vocabulary 64.
    "ppo-xing-tiny": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=64, delay=4, episode_length=16
            ),
            "torso": "xing",
            "seq_model": _xing_config(
                hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                num_key_value_heads=4, n_routed_experts=8,
                num_experts_per_tok=2, kv_lora_rank=32, q_lora_rank=16,
                qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
                hc_sinkhorn_iters=3, vocab_size=64, first_expert=0,
                experts_held=4, capacity_factor=4.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 8,
            "rollout_length": 16,
            "total_env_steps": 4_096,
            "num_devices": 1,
        },
    ),
    # 18. Token-level PPO with LFM2-24B-A2B as the policy: double-gated
    # short convolutions as most of the mixers, one grouped-query
    # attention layer with per-head query/key norms in every four, and
    # sigmoid-routed experts after both kinds, at the published widths,
    # cut to one chip's share of a stated deployment: each expert layer
    # shared by 8 chips, expert-parallel (8 of the 64 experts here, the
    # whole router and every mixer), published layer 0 (a convolution
    # and the dense SwiGLU; the two leading dense layers count once) and
    # layers 2-5 (one whole period: attention and three convolutions,
    # the rest on further chips as pipeline stages) and 1/8 of the tied
    # vocabulary: 469.3 M parameters, 7.5 GB with gradients and Adam's
    # moments. One episode is one sequence of 512 tokens on the
    # token-recall env; the schedule is
    # perfbench/traffic/recall-128x512-e1mb8-lfm2.json's.
    "ppo-lfm2-recall": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=8_192, delay=64, episode_length=512
            ),
            "torso": "lfm2",
            "seq_model": _lfm2_config(
                num_hidden_layers=5, num_dense_layers=1,
                layer_types=("conv", "full_attention", "conv", "conv",
                             "conv"),
                vocab_size=8_192, first_expert=0, experts_held=8,
                capacity_factor=2.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 128,
            "rollout_length": 512,
            "num_minibatches": 8,
            "compute_dtype": "bfloat16",
            "total_env_steps": 10_000_000,
        },
    ),
    # The same model and schedule at widths for the CPU tests: hidden
    # 64, convolutions of 3 taps, 4 query / 2 key-value heads of 16, a
    # dense layer of width 128, then 4 expert layers of 8 experts top-2
    # of width 32 (4 held), vocabulary 64; the cell's layer kinds and
    # order.
    "ppo-lfm2-tiny": (
        "ppo",
        {
            "env": "TokenRecallTPU-v0",
            "env_params": _token_recall_params(
                vocab_size=64, delay=4, episode_length=16
            ),
            "torso": "lfm2",
            "seq_model": _lfm2_config(
                hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_dense_layers=1,
                layer_types=("conv", "full_attention", "conv", "conv",
                             "conv"),
                num_attention_heads=4, num_key_value_heads=2, num_experts=8,
                num_experts_per_tok=2, vocab_size=64, first_expert=0,
                experts_held=4, capacity_factor=4.0,
            ),
            **_PPO_TOKEN_SCHEDULE,
            "num_envs": 8,
            "rollout_length": 16,
            "total_env_steps": 4_096,
            "num_devices": 1,
        },
    ),
    # 12. Continuous-control PPO (diagonal-Gaussian policy) on the
    # pure-JAX Pendulum — the on-device continuous counterpart of the
    # MuJoCo presets. gamma=0.9 + multi-epoch updates: measured
    # avg_return -1200 -> ~-690 by 800k steps on one chip, still
    # improving at the 3M budget.
    "ppo-pendulum": (
        "ppo",
        {
            "env": "Pendulum-v1",
            "num_envs": 64,
            "rollout_length": 128,
            "total_env_steps": 3_000_000,
            "lr": 1e-3,
            "gamma": 0.9,
            "num_epochs": 10,
            "ent_coef": 0.0,
        },
    ),
}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="train.py",
        description="TPU-native actor-critic training entrypoints",
    )
    p.add_argument("--preset", choices=sorted(PRESETS), help="named baseline config")
    p.add_argument("--algo", choices=["a2c", "ppo", "ddpg", "td3", "sac", "impala"])
    p.add_argument("--env", help="env id (pure-JAX name or gym:<id>)")
    p.add_argument("--total-steps", type=int, help="total env steps")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument(
        "--set",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="override any config field (repeatable)",
    )
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-interval", type=int, default=200,
                   help="iterations between checkpoints")
    p.add_argument("--resume", action="store_true",
                   help="restore latest checkpoint from --checkpoint-dir")
    p.add_argument("--preempt-save", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="impala: catch SIGTERM/SIGINT (pod preemption), "
                        "finish the current step, write one final atomic "
                        "checkpoint to --checkpoint-dir, broadcast the "
                        "shutdown frame to actors, and exit 0; signal "
                        "twice to force the old behavior. Sentinel knobs "
                        "are config fields: --set numerics_guards= "
                        "max_rollbacks= snapshot_interval= "
                        "loss_spike_factor= quarantine_threshold= ...")
    p.add_argument("--log-interval", type=int, default=20)
    p.add_argument("--tensorboard-dir", default=None,
                   help="write TensorBoard scalar event files here")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler device trace of the run "
                        "(view in XProf/Perfetto); use a small "
                        "--total-steps to keep the trace readable")
    p.add_argument("--eval", action="store_true",
                   help="evaluate the latest checkpoint in "
                        "--checkpoint-dir instead of training")
    p.add_argument("--eval-envs", type=int, default=32)
    p.add_argument("--eval-steps", type=int, default=1000,
                   help="max env steps per eval episode")
    p.add_argument("--stochastic", action="store_true",
                   help="sample the policy during --eval (default: greedy)")
    p.add_argument("--render-dir", default=None,
                   help="with --eval: record env 0's first episode here "
                        "(episode.gif for image envs, episode.npy for "
                        "vector envs)")
    p.add_argument("--platform", default=None, metavar="NAME",
                   help="jax platform to run on (e.g. cpu, tpu). Applied "
                        "via jax.config before first backend use, so it "
                        "wins over JAX_PLATFORMS in the environment")
    p.add_argument("--host-loop", choices=("auto", "fused", "async"),
                   default="auto",
                   help="off-policy trainers with gym:/native: envs: "
                        "'fused' (and 'auto') steps envs inside the "
                        "jitted program (ordered io_callback), 'async' "
                        "steps them host-side with the update block on "
                        "the accelerator (algos.host_async)")
    p.add_argument("--actor-processes", action="store_true",
                   help="impala: run actors as separate processes "
                        "streaming over the TCP transport (the "
                        "multi-host topology) instead of threads")
    p.add_argument("--replay-servers", type=int, default=0, metavar="N",
                   help="off-policy trainers (ddpg/td3/sac): run the "
                        "distributed Ape-X topology — N prioritized "
                        "replay-server processes, env-stepper actor "
                        "processes pushing transitions over the coded "
                        "trajectory wire path, and this process as the "
                        "learner (prioritized draws + KIND_PRIO_UPDATE "
                        "feedback + param publishes). Pure-JAX envs "
                        "only. PER knobs are config fields: --set "
                        "per_alpha= per_beta= per_eps= replay_codec=")
    p.add_argument("--replay-actors", type=int, default=None, metavar="M",
                   help="with --replay-servers: env-stepper actor "
                        "process count, default 2 (any fleet size — "
                        "ShardPlan.balanced() spreads the remainder "
                        "across shards; each actor runs num_envs envs)")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="with --replay-servers: enable the elastic "
                        "actor-fleet autoscaler — a threshold policy "
                        "over the learner's metrics stream resizes the "
                        "supervised fleet between MIN and "
                        "min(MAX, --replay-actors) (double up on "
                        "starvation, halve down on backlog; cooldown "
                        "via --set autoscaler_cooldown_s=)")
    p.add_argument("--evaluator", default=None, metavar="HOST:PORT",
                   help="run as the policy-delivery EVALUATOR tier for "
                        "the learner at HOST:PORT (a learner started "
                        "with --set delivery=True): poll candidate "
                        "weights over the wire, score them against the "
                        "env's PERF.md bar, and return signed "
                        "PROMOTE/REJECT verdicts. With "
                        "--checkpoint-dir the score is a fresh greedy "
                        "eval of the newest checkpoint (the PERF.md "
                        "methodology); without, a cheap leaf-mean "
                        "probe (tests/benches). Signing secret: --set "
                        "delivery_secret= (must match the learner)")
    p.add_argument("--evaluator-id", type=int, default=9000,
                   help="with --evaluator: this evaluator's hello "
                        "identity (default 9000). A verdict-quorum "
                        "learner (--set delivery_quorum=N) tallies one "
                        "vote per DISTINCT evaluator id, so each peer "
                        "in an N-evaluator panel needs its own id")
    p.add_argument("--replay-ports", default=None, metavar="P0,P1,..",
                   help="with --replay-servers: pin each replay "
                        "shard's bind port (default: ephemeral). "
                        "Fixed ports are the contract an off-policy "
                        "warm standby's --replay-endpoints list — and "
                        "a resumed run's surviving actor fleet — "
                        "relies on")
    p.add_argument("--actor-param-endpoints", default=None,
                   metavar="H:P[,H:P...]",
                   help="with --replay-servers: PRIORITY-ordered "
                        "param-plane endpoint list the spawned "
                        "env-stepper actors walk (this learner first, "
                        "warm standbys after) — name each standby's "
                        "--learner-bind here so actors that lose the "
                        "primary land on a standby's early listener "
                        "on their first retry")
    p.add_argument("--replay-endpoints", default=None,
                   metavar="H:P[,H:P...]",
                   help="off-policy --standby: the EXISTING replay "
                        "tier's shard endpoints (the primary's "
                        "--replay-ports). At takeover the standby "
                        "ATTACHES to these shards instead of spawning "
                        "its own tier; ring snapshots cover shards "
                        "that die unsupervised after the primary")
    p.add_argument("--standby", default=None, metavar="HOST:PORT",
                   help="impala or off-policy (ddpg/td3/sac with "
                        "--replay-endpoints): run as a WARM-STANDBY "
                        "learner for the "
                        "primary at HOST:PORT — compile up front, tail "
                        "its --checkpoint-dir (restoring each step into "
                        "memory), and on primary death (missed "
                        "heartbeats or an explicit handoff) bind "
                        "--learner-bind, publish the tailed weights, "
                        "and take the actor fleet over. Requires "
                        "--checkpoint-dir; spawns no actors of its own. "
                        "Hot-standby knobs are config fields: --set "
                        "standby_serve_early= (pre-takeover listener + "
                        "redirector fallback) standby_tail_params= "
                        "(follow the primary's publishes, not just its "
                        "checkpoints). Election/fencing knobs: --set "
                        "standby_never_seen_grace_s= (0 = 10x the "
                        "takeover deadline) election_probe_timeout_s= "
                        "election_probe_attempts=. A sharded primary "
                        "(--set shard_count=N, in-process shape) makes "
                        "the standby pre-bind all N per-shard "
                        "listeners and adopt them at takeover")
    p.add_argument("--standby-rank", type=int, default=0, metavar="K",
                   help="with --standby: this standby's rank in the "
                        "quorum (lowest live rank wins the election; "
                        "index into --standby-peers)")
    p.add_argument("--standby-peers", default=None,
                   metavar="H:P[,H:P...]",
                   help="with --standby: the rank-ordered data-plane "
                        "endpoints of EVERY standby (rank K = K-th "
                        "entry = that standby's --learner-bind / early "
                        "listener). Enables the N-standby election: on "
                        "primary death the lowest live rank takes "
                        "over, the rest re-arm as its followers, and a "
                        "fencing epoch makes the deposed primary's "
                        "late publishes/redirects rejectable. The "
                        "redirector's fallback route becomes this "
                        "whole list (walked in rank order)")
    p.add_argument("--redirector", default=None, metavar="[HOST:]PORT",
                   help="with --standby: also run the actor-facing "
                        "redirector (actors connect here, never to a "
                        "learner directly); it forwards to the primary "
                        "until takeover, then re-points at the local "
                        "learner and resets live links. Binds 0.0.0.0 "
                        "unless HOST is given — the fleet is usually "
                        "on other hosts")
    p.add_argument("--shard", default=None, metavar="N | K/N@HOST:PORT",
                   help="impala with --actor-processes: shard the "
                        "LEARNER data-parallel. Bare 'N' runs N "
                        "in-process ingest shards over device slices "
                        "of the mesh (each its own trajectory "
                        "listener, host arena and param publishes, "
                        "each owning a disjoint slice of the actor "
                        "fleet). 'K/N@HOST:PORT' joins this process "
                        "as learner-host shard K of N: HOST:PORT is "
                        "the jax.distributed rendezvous (shard 0 "
                        "hosts it), PORT+1 carries the preemption "
                        "consensus + per-step lockstep barrier "
                        "(shard 0 leads), shard 0 owns checkpoints. "
                        "Knobs: --set shard_step_barrier= "
                        "shard_barrier_timeout_s=. Requires "
                        "batch_trajectories/num_actors/devices "
                        "divisible by N; see ARCHITECTURE.md "
                        "'Sharded learner'")
    p.add_argument("--coordinate-preemption", default=None,
                   metavar="SPEC",
                   help="impala: coordinate the SIGTERM final "
                        "checkpoint across learner hosts so every host "
                        "saves at ONE agreed step. SPEC is "
                        "'lead:N@HOST:PORT' (leader; expects N "
                        "followers on HOST:PORT) or 'follow@HOST:PORT' "
                        "(connect to the leader). On preemption the "
                        "hosts exchange step reports, train up to the "
                        "agreed (max) step, save, and barrier before "
                        "exiting")
    p.add_argument("--learner-bind", default=None, metavar="HOST[:PORT]",
                   help="with --actor-processes: bind the learner's "
                        "trajectory listener here (default "
                        "127.0.0.1:ephemeral; bind a routable address "
                        "to accept actors from other hosts). Transport "
                        "fault-tolerance knobs are config fields: "
                        "--set transport_heartbeat_s=... "
                        "transport_idle_timeout_s= "
                        "transport_retry_deadline_s= "
                        "transport_max_frame_mb=. Param-sync wire "
                        "codec: --set param_delta= param_delta_ring= "
                        "param_bf16_wire= (bf16 actor fetches only; "
                        "default ON after the PR-7 A/B — see PERF.md). "
                        "Central-inference serving tier (SEED-style): "
                        "--set actor_mode=env_shim serve_batch_max= "
                        "serve_max_wait_ms= serve_obs_codec= (actors "
                        "become thin env shims; the learner batches "
                        "act() across the fleet). Mid-rollout weight "
                        "refresh for classic actors: --set "
                        "mid_rollout_fetch=True mid_rollout_chunks= "
                        "(watch param_staleness_steps)")
    return p


def parse_bind(spec: str | None) -> Tuple[str, int]:
    """``HOST[:PORT]`` -> (host, port); port 0 (ephemeral) if omitted.

    IPv6 literals use brackets (``[::1]:9000``, ``[::1]``); a bare
    multi-colon spec (``::1``) is taken as a portless IPv6 host."""
    if not spec:
        return "127.0.0.1", 0
    if spec.startswith("["):
        host, sep, rest = spec[1:].partition("]")
        if not sep or (rest and not rest.startswith(":")):
            raise SystemExit(f"--learner-bind: malformed address {spec!r}")
        port = rest[1:]
    elif spec.count(":") > 1:
        return spec, 0  # bare IPv6 literal, no port
    else:
        host, sep, port = spec.rpartition(":")
        if not sep:
            return spec, 0
    try:
        return host or "127.0.0.1", int(port) if port else 0
    except ValueError:
        raise SystemExit(f"--learner-bind: bad port in {spec!r}")


def parse_hostport(spec: str, what: str) -> Tuple[str, int]:
    """``HOST:PORT`` with a REQUIRED port (unlike parse_bind, these
    name a peer to connect to — there is no ephemeral default)."""
    host, port = parse_bind(spec)
    if port == 0:
        raise SystemExit(f"{what}: an explicit port is required ({spec!r})")
    return host, port


def make_coordinator(spec: str):
    """``lead:N@HOST:PORT`` | ``follow@HOST:PORT`` -> a preemption
    coordinator (distributed.controlplane)."""
    from actor_critic_algs_on_tensorflow_tpu.distributed.controlplane import (
        PreemptionFollower,
        PreemptionLeader,
    )

    role, sep, addr = spec.partition("@")
    if not sep:
        raise SystemExit(
            f"--coordinate-preemption: expected 'lead:N@HOST:PORT' or "
            f"'follow@HOST:PORT', got {spec!r}"
        )
    if role.startswith("lead"):
        try:
            n = int(role.split(":", 1)[1])
        except (IndexError, ValueError):
            raise SystemExit(
                f"--coordinate-preemption: leader needs a follower "
                f"count ('lead:N@...'), got {spec!r}"
            )
        # The leader BINDS (port 0 = ephemeral, printed below); only
        # followers need an explicit peer port.
        host, port = parse_bind(addr)
        coord = PreemptionLeader(n_followers=n, host=host, port=port)
        print(
            f"[train] preemption leader on {host}:{coord.port} "
            f"(expecting {n} followers)",
            flush=True,
        )
        return coord
    if role == "follow":
        host, port = parse_hostport(addr, "--coordinate-preemption")
        return PreemptionFollower(host, port)
    raise SystemExit(
        f"--coordinate-preemption: unknown role {role!r} in {spec!r}"
    )


def parse_shard(spec: str):
    """``N`` -> in-process plan args; ``K/N@HOST:PORT`` -> per-host
    plan args. Returns ``(shard_id_or_None, shard_count, host, port)``
    — host/port are the rendezvous address (None for in-process)."""
    addr_part = None
    topo = spec
    if "@" in spec:
        topo, _, addr_part = spec.partition("@")
    if "/" in topo:
        if addr_part is None:
            raise SystemExit(
                f"--shard: per-host form needs a rendezvous address "
                f"('K/N@HOST:PORT'), got {spec!r}"
            )
        k_s, _, n_s = topo.partition("/")
        try:
            k, n = int(k_s), int(n_s)
        except ValueError:
            raise SystemExit(f"--shard: bad K/N in {spec!r}")
        host, port = parse_hostport(addr_part, "--shard")
        return k, n, host, port
    if addr_part is not None:
        raise SystemExit(
            f"--shard: the in-process form is a bare count "
            f"('--shard N'), got {spec!r}"
        )
    try:
        n = int(topo)
    except ValueError:
        raise SystemExit(f"--shard: bad shard count {spec!r}")
    return None, n, None, None


def make_shard_runtime(args, cfg):
    """--shard -> (cfg with shard_count set, ShardPlan | None,
    coordinator | None). The per-host form joins the jax.distributed
    runtime NOW (before any backend use) and wires the preemption
    coordinator that doubles as the per-step lockstep barrier: shard 0
    leads on rendezvous-port+1, everyone else follows."""
    if args.shard is None:
        return cfg, None, None
    if not args.actor_processes:
        raise SystemExit("--shard requires --actor-processes (the "
                         "sharded learner ingests over the transport)")
    if args.standby:
        raise SystemExit("--shard is incompatible with --standby")
    shard_id, shard_count, host, port = parse_shard(args.shard)
    if shard_count < 1:
        raise SystemExit(f"--shard: count must be >= 1, got {shard_count}")
    cfg = dataclasses.replace(cfg, shard_count=shard_count)
    if shard_count == 1 and shard_id is None:
        return cfg, None, None

    from actor_critic_algs_on_tensorflow_tpu.distributed.sharding import (
        ShardPlan,
    )

    plan = ShardPlan(shard_count, shard_id=shard_id)
    if shard_id is None:
        return cfg, plan, None
    if args.coordinate_preemption:
        raise SystemExit(
            "--shard K/N@... already wires the preemption coordinator "
            "(it carries the lockstep barrier); drop "
            "--coordinate-preemption"
        )
    from actor_critic_algs_on_tensorflow_tpu.distributed.controlplane import (
        PreemptionFollower,
        PreemptionLeader,
    )
    from actor_critic_algs_on_tensorflow_tpu.parallel import multihost

    multihost.initialize(
        coordinator_address=f"{host}:{port}",
        num_processes=shard_count,
        process_id=shard_id,
    )
    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import device_line

    print(f"[train] {device_line()}", flush=True)
    if shard_id == 0:
        coord = PreemptionLeader(
            n_followers=shard_count - 1, host="", port=port + 1
        )
        print(
            f"[train] shard 0/{shard_count}: lockstep leader on "
            f"port {coord.port} ({shard_count - 1} followers)",
            flush=True,
        )
    else:
        coord = PreemptionFollower(host, port + 1)
        print(
            f"[train] shard {shard_id}/{shard_count}: following the "
            f"lockstep leader at {host}:{port + 1}",
            flush=True,
        )
    return cfg, plan, coord


def make_config(args) -> Tuple[str, Any]:
    from actor_critic_algs_on_tensorflow_tpu.algos.a2c import A2CConfig
    from actor_critic_algs_on_tensorflow_tpu.algos.ddpg import DDPGConfig
    from actor_critic_algs_on_tensorflow_tpu.algos.impala import ImpalaConfig
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import PPOConfig
    from actor_critic_algs_on_tensorflow_tpu.algos.sac import SACConfig
    from actor_critic_algs_on_tensorflow_tpu.algos.td3 import TD3Config

    classes = {
        "a2c": A2CConfig,
        "ppo": PPOConfig,
        "ddpg": DDPGConfig,
        "td3": TD3Config,
        "sac": SACConfig,
        "impala": ImpalaConfig,
    }
    if args.preset:
        algo, base = PRESETS[args.preset]
        cfg = classes[algo](**base)
    elif args.algo:
        algo = args.algo
        cfg = classes[algo]()
    else:
        raise SystemExit("pass --preset or --algo (see --help)")
    if args.env:
        cfg = dataclasses.replace(cfg, env=args.env)
    if args.total_steps:
        cfg = dataclasses.replace(cfg, total_env_steps=args.total_steps)
    if args.seed is not None:
        cfg = dataclasses.replace(cfg, seed=args.seed)
    cfg = apply_overrides(cfg, args.set)
    return algo, cfg


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax

    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import device_line
    from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache

    compile_cache.enable()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    algo, cfg = make_config(args)
    if args.shard is None or "/" not in args.shard:
        # (A per-host learner shard must join jax.distributed before
        # anything touches the backend; it reports after joining.)
        print(f"[train] {device_line()}", flush=True)
    print(f"[train] algo={algo} config={cfg}", flush=True)

    writer = None
    if args.tensorboard_dir:
        from actor_critic_algs_on_tensorflow_tpu.utils.tensorboard import (
            SummaryWriter,
        )

        writer = SummaryWriter(args.tensorboard_dir)
    try:
        if args.profile_dir:
            from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
                trace,
            )

            with trace(args.profile_dir):
                return _run(args, algo, cfg, writer)
        return _run(args, algo, cfg, writer)
    finally:
        if writer is not None:
            writer.close()


def _open_checkpointer(args, make_template, cfg=None, wait_for_step_s=None,
                       solo_process=False):
    """(checkpointer, restored_state) from --checkpoint-dir/--resume.

    ``make_template`` is called lazily only when a restore happens; it
    must return a state pytree with the structure (and, where sharding
    matters, the shardings) the restored arrays should adopt. ``cfg``
    (when given) guards against grafting fresh obs-normalization stats
    into a normalize_obs=True run (utils.checkpoint.obs_norm_restore_guard).
    ``wait_for_step_s`` (non-zero learner shards resuming a sharded
    run) blocks until shard 0's latest step dir is durable instead of
    racing the writer — see ``Checkpointer.wait_for_step``.
    ``solo_process`` (per-host sharded runs) keeps orbax's own
    multiprocess coordination out of the manager — the shard plane
    owns cross-host checkpoint semantics explicitly.
    """
    if not args.checkpoint_dir:
        return None, None
    from actor_critic_algs_on_tensorflow_tpu.utils.checkpoint import (
        Checkpointer,
        obs_norm_restore_guard,
    )

    checkpointer = Checkpointer(
        args.checkpoint_dir, solo_process=solo_process
    )
    state = None
    if (
        args.resume
        and wait_for_step_s is not None
        and checkpointer.latest_step() is None
    ):
        checkpointer.wait_for_step(timeout_s=wait_for_step_s)
    if args.resume and checkpointer.latest_step() is not None:
        state = checkpointer.restore(
            make_template(),
            forbid_defaulted=obs_norm_restore_guard(cfg),
        )
        print(f"[train] resumed from step {checkpointer.last_restored_step}")
    return checkpointer, state


def _finalize_checkpointer(checkpointer, env_steps: int, state) -> None:
    """Save the final state (unless an equal-or-newer step is already
    retained — orbax silently refuses non-monotonic ids, which a
    sentinel rollback can produce), flush async saves, and close."""
    if checkpointer is None:
        return
    latest = checkpointer.latest_step()
    if latest is None or int(env_steps) > latest:
        checkpointer.save(int(env_steps), state)
    checkpointer.wait()
    checkpointer.close()


def format_return_hist(per_env) -> str:
    """Per-episode return distribution line.

    Integer-valued scores (Pong's -21..21) print exact counts — the
    evidence format PERF.md's reward-21 analysis uses. Float-valued
    returns (MuJoCo) print 8 equal-width bins over [min, max] so
    multi-modal outcomes (e.g. Humanoid falls vs full survivals) are
    visible instead of hidden behind a mean (VERDICT r3 next#3)."""
    import collections

    rounded = per_env.round().astype(int)
    if (abs(per_env - rounded) < 1e-6).all():
        hist = collections.Counter(rounded.tolist())
        if len(hist) <= 32:
            return "[eval] return_hist " + " ".join(
                f"{k}:{v}" for k, v in sorted(hist.items())
            )
    lo, hi = float(per_env.min()), float(per_env.max())
    if hi <= lo:
        return f"[eval] return_hist {lo:.0f}:{len(per_env)}"
    import numpy as np

    counts, edges = np.histogram(per_env, bins=8, range=(lo, hi))
    cells = [
        # np.histogram's bins are half-open except the LAST, which is
        # closed (it contains the max) — label it to match.
        f"[{edges[i]:.0f},{edges[i + 1]:.0f}{']' if i == len(counts) - 1 else ')'}:{c}"
        for i, c in enumerate(counts)
        if c
    ]
    return "[eval] return_hist " + " ".join(cells)


def _run_standby(args, cfg, writer, coordinator) -> int:
    """``--standby`` mode: warm-standby learner (+ optional actor
    redirector) for the primary at ``args.standby``."""
    from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
        run_impala_standby,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.checkpoint import (
        Checkpointer,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.health import (
        ShutdownSignal,
    )

    if not args.checkpoint_dir:
        raise SystemExit(
            "--standby requires --checkpoint-dir (the primary's "
            "checkpoint directory — the warm restore source)"
        )
    phost, pport = parse_hostport(args.standby, "--standby")
    host, port = parse_bind(args.learner_bind)
    # Quorum mode: the rank-ordered endpoint list of EVERY standby's
    # data plane (rank = list index). One entry (or none) = the
    # legacy single-standby pair.
    peers = None
    if args.standby_peers:
        peers = [
            parse_hostport(s.strip(), "--standby-peers")
            for s in args.standby_peers.split(",")
            if s.strip()
        ]
        if not peers:
            raise SystemExit("--standby-peers: empty endpoint list")
        if not 0 <= args.standby_rank < len(peers):
            raise SystemExit(
                f"--standby-rank {args.standby_rank} outside the "
                f"{len(peers)}-entry --standby-peers list"
            )
    elif args.standby_rank:
        raise SystemExit(
            "--standby-rank needs --standby-peers (the rank indexes "
            "that list)"
        )
    if args.redirector is not None and cfg.shard_count > 1:
        raise SystemExit(
            "--redirector supports single-stack standbys only: one "
            "redirector has one target, so with shard_count > 1 its "
            "last-wins re-point would route EVERY through-redirector "
            "actor to shard N-1 and starve the other slices. Give the "
            "actors per-shard priority endpoint lists instead (or "
            "wire one redirector per shard programmatically)"
        )
    if peers is not None and port != peers[args.standby_rank][1]:
        # The peers list IS the probe surface: elections and the
        # redirector fallback walk ask peers[rank], so a standby
        # whose listener binds anywhere else (the default is an
        # EPHEMERAL port) is "dead" to every peer while alive to
        # itself — on its election round that is a guaranteed dual
        # primary at one epoch.
        raise SystemExit(
            f"--learner-bind must pin this standby's own "
            f"--standby-peers entry (rank {args.standby_rank} = "
            f"{peers[args.standby_rank][0]}:"
            f"{peers[args.standby_rank][1]}, got port "
            f"{port or 'ephemeral'}): the election and the redirector "
            f"fallbacks probe the peers list, so an unmatched bind is "
            f"an unreachable standby"
        )
    if cfg.shard_count > 1 and port == 0:
        raise SystemExit(
            "a sharded standby needs an explicit --learner-bind "
            "port: its N shard listeners bind port..port+N-1 — the "
            "contract actor endpoint lists rely on — and ephemeral "
            "ports land anywhere"
        )
    checkpointer = Checkpointer(args.checkpoint_dir)
    redirector = None
    redirect = None
    if args.redirector is not None:
        from actor_critic_algs_on_tensorflow_tpu.distributed.controlplane import (  # noqa: E501
            Redirector,
        )

        if ":" not in args.redirector:
            # Bare PORT: bind all interfaces — the actor fleet this
            # endpoint exists for is usually on OTHER hosts.
            try:
                rhost, rport = "0.0.0.0", int(args.redirector)
            except ValueError:
                raise SystemExit(
                    f"--redirector: bad port {args.redirector!r}"
                )
        else:
            rhost, rport = parse_bind(args.redirector)
        redirector = Redirector(phost, pport, host=rhost, port=rport)
        print(
            f"[train] actor redirector on {rhost}:{redirector.port} -> "
            f"{phost}:{pport} (until takeover)",
            flush=True,
        )

        def redirect(h, p, epoch=None, rank=None):
            # The takeover path passes its fencing epoch (and rank)
            # so a deposed — or equal-epoch outranked — primary's
            # later re-point is refused by the redirector.
            redirector.redirect(
                "127.0.0.1" if h in ("0.0.0.0", "") else h, p,
                epoch=epoch, rank=rank,
            )

    def on_serving(h, p):
        # The standby's pre-takeover listener is up: arm the
        # redirector's fallback route so actors that lose the primary
        # land on the standby on their FIRST retry (reconnect backoff
        # paid before the failover) instead of backing off against a
        # dead address until takeover re-points the target.
        h = "127.0.0.1" if h in ("0.0.0.0", "") else h
        print(
            f"[train] standby data plane serving on {h}:{p} "
            f"(pre-takeover: absorbs pushes, serves tailed params)",
            flush=True,
        )
        if redirector is not None:
            if peers is not None:
                # Quorum: the fallback route is the WHOLE rank-ordered
                # standby list — walked front to back, it lands actors
                # on the lowest live rank, the same host the election
                # elects, even before any explicit re-point arrives.
                redirector.set_fallbacks(peers)
            else:
                redirector.set_fallback(h, p)

    shutdown = None
    if args.preempt_save:
        shutdown = ShutdownSignal().install()
    try:
        out = run_impala_standby(
            cfg,
            checkpointer=checkpointer,
            primary_host=phost,
            primary_port=pport,
            host=host,
            port=port,
            redirect=redirect,
            log_interval=args.log_interval,
            summary_writer=writer,
            checkpoint_interval=args.checkpoint_interval,
            stop_event=shutdown.event if shutdown is not None else None,
            coordinator=coordinator,
            on_serving=on_serving,
            standby_id=args.standby_rank,
            peers=peers,
        )
    finally:
        if shutdown is not None:
            shutdown.uninstall()
        if redirector is not None:
            redirector.close()
        if coordinator is not None:
            coordinator.close()
    if out is None:
        checkpointer.wait()
        checkpointer.close()
        print("[train] standby: primary finished; no takeover needed")
        return 0
    state, _ = out
    steps_per_batch = (
        cfg.batch_trajectories * cfg.envs_per_actor * cfg.rollout_length
    )
    _finalize_checkpointer(
        checkpointer, int(state.step) * steps_per_batch, state
    )
    print(
        f"[train] standby run ended at learner steps={int(state.step)} "
        f"(took over as primary)"
    )
    return 0


def _run_offpolicy_standby(args, fns, cfg, writer) -> int:
    """Off-policy ``--standby`` mode: warm-standby learner for the
    Ape-X replay topology (``run_offpolicy_standby``). The standby
    tails the primary's checkpoints + acting publishes, and at
    takeover attaches to the EXISTING replay tier named by
    ``--replay-endpoints`` — fixed shard ports (the primary's
    ``--replay-ports``) are the contract that makes that list valid
    across shard respawns."""
    from actor_critic_algs_on_tensorflow_tpu.algos.offpolicy_distributed import (  # noqa: E501
        run_offpolicy_standby,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.checkpoint import (
        Checkpointer,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.health import (
        ShutdownSignal,
    )

    if not args.checkpoint_dir:
        raise SystemExit(
            "--standby requires --checkpoint-dir (the primary's "
            "checkpoint directory — the warm restore source)"
        )
    phost, pport = parse_hostport(args.standby, "--standby")
    host, port = parse_bind(args.learner_bind)
    endpoints = [
        parse_hostport(s.strip(), "--replay-endpoints")
        for s in args.replay_endpoints.split(",")
        if s.strip()
    ]
    if not endpoints:
        raise SystemExit("--replay-endpoints: empty endpoint list")
    peers = None
    if args.standby_peers:
        peers = [
            parse_hostport(s.strip(), "--standby-peers")
            for s in args.standby_peers.split(",")
            if s.strip()
        ]
        if not peers:
            raise SystemExit("--standby-peers: empty endpoint list")
        if not 0 <= args.standby_rank < len(peers):
            raise SystemExit(
                f"--standby-rank {args.standby_rank} outside the "
                f"{len(peers)}-entry --standby-peers list"
            )
        if port != peers[args.standby_rank][1]:
            raise SystemExit(
                f"--learner-bind must pin this standby's own "
                f"--standby-peers entry (rank {args.standby_rank} = "
                f"{peers[args.standby_rank][0]}:"
                f"{peers[args.standby_rank][1]}, got port "
                f"{port or 'ephemeral'}): the election probes the "
                f"peers list, so an unmatched bind is an unreachable "
                f"standby"
            )
    elif args.standby_rank:
        raise SystemExit(
            "--standby-rank needs --standby-peers (the rank indexes "
            "that list)"
        )
    checkpointer = Checkpointer(args.checkpoint_dir)
    shutdown = None
    if args.preempt_save:
        shutdown = ShutdownSignal().install()
    try:
        out = run_offpolicy_standby(
            fns,
            checkpointer=checkpointer,
            primary_host=phost,
            primary_port=pport,
            replay_endpoints=endpoints,
            total_env_steps=cfg.total_env_steps,
            n_actors=(
                args.replay_actors if args.replay_actors is not None
                else 2
            ),
            seed=cfg.seed,
            host=host,
            port=port,
            log_interval=args.log_interval,
            summary_writer=writer,
            checkpoint_interval=args.checkpoint_interval,
            stop_event=shutdown.event if shutdown is not None else None,
            standby_id=args.standby_rank,
            peers=peers,
        )
    finally:
        if shutdown is not None:
            shutdown.uninstall()
        checkpointer.wait()
        checkpointer.close()
    if out is None:
        print("[train] standby: primary finished; no takeover needed")
        return 0
    result, history = out
    final = history[-1][1] if history else {}
    print(
        f"[train] standby run ended at env_steps={result.env_steps} "
        f"updates={result.updates} "
        f"avg_return={final.get('avg_return', float('nan')):.2f} "
        f"(took over as primary)"
    )
    return 0


def _run_evaluator(args, algo, cfg) -> int:
    """The delivery evaluator tier: poll, score, signed verdict."""
    import numpy as np

    from actor_critic_algs_on_tensorflow_tpu.distributed.delivery import (
        bar_for,
        greedy_checkpoint_scorer,
        run_evaluator,
    )

    host, _, port_s = args.evaluator.rpartition(":")
    try:
        host, port = host or "127.0.0.1", int(port_s)
    except ValueError:
        raise SystemExit(
            f"--evaluator: want HOST:PORT, got {args.evaluator!r}"
        )
    bar = bar_for(cfg.env)
    if not np.isfinite(bar):
        print(
            f"[train] WARNING: no PERF.md bar for env {cfg.env!r} — "
            f"every finite-scoring candidate will promote",
            flush=True,
        )
    if args.checkpoint_dir:
        score_fn = greedy_checkpoint_scorer(
            algo, cfg, args.checkpoint_dir,
            num_envs=args.eval_envs, max_steps=args.eval_steps,
            stochastic=args.stochastic,
        )
    else:
        def score_fn(meta, leaves):
            leaf = np.asarray(leaves[0], np.float64)
            return float(leaf.mean()) if leaf.size else float("nan")

    verdicts = run_evaluator(
        host, port,
        score_fn=score_fn,
        bar=bar,
        secret=getattr(cfg, "delivery_secret", "") or None,
        evaluator_id=args.evaluator_id,
    )
    print(f"[train] evaluator exited after {verdicts} verdict(s)")
    return 0


def _run(args, algo, cfg, writer) -> int:
    if args.render_dir and not args.eval:
        raise SystemExit("--render-dir requires --eval")
    if args.evaluator is not None:
        return _run_evaluator(args, algo, cfg)
    if args.learner_bind and not (
        (algo == "impala" and (args.actor_processes or args.standby))
        or args.replay_servers
        or (args.standby and algo in ("ddpg", "td3", "sac"))
    ):
        raise SystemExit(
            "--learner-bind requires impala with --actor-processes "
            "or --standby, or an off-policy run with --replay-servers "
            "or --standby"
        )
    offpolicy_standby = args.standby and algo in ("ddpg", "td3", "sac")
    if args.replay_servers:
        if args.replay_actors is None:
            args.replay_actors = 2
        if algo not in ("ddpg", "td3", "sac"):
            raise SystemExit(
                "--replay-servers is off-policy-only (ddpg/td3/sac); "
                "the IMPALA stream has no replay buffer"
            )
        if args.actor_processes:
            raise SystemExit(
                "--actor-processes is the IMPALA wire fleet; "
                "--replay-servers spawns its own env-stepper actors "
                "(--replay-actors)"
            )
        if args.host_loop == "async":
            raise SystemExit(
                "--replay-servers runs its own learner loop; drop "
                "--host-loop async"
            )
        if args.replay_servers < 1 or args.replay_actors < 1:
            raise SystemExit(
                "--replay-servers/--replay-actors must be >= 1"
            )
        # No divisibility requirement between actors and shards:
        # ShardPlan.balanced() spreads the remainder, so any fleet
        # size maps onto any shard count (the elastic-fleet
        # precondition — an autoscaler-ramped fleet cannot promise
        # divisibility).
        if args.autoscale is not None:
            try:
                lo_s, _, hi_s = args.autoscale.partition(":")
                lo, hi = int(lo_s), int(hi_s)
            except ValueError:
                raise SystemExit(
                    f"--autoscale: want MIN:MAX, got {args.autoscale!r}"
                )
            if not 1 <= lo <= hi:
                raise SystemExit(
                    f"--autoscale: need 1 <= MIN <= MAX, got {lo}:{hi}"
                )
            cfg = dataclasses.replace(
                cfg,
                autoscaler_enabled=True,
                autoscaler_min_actors=lo,
                autoscaler_max_actors=hi,
            )
        if args.resume and not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        if args.replay_ports is not None:
            try:
                ports = [
                    int(s) for s in args.replay_ports.split(",") if s.strip()
                ]
            except ValueError:
                raise SystemExit(
                    f"--replay-ports: bad port list {args.replay_ports!r}"
                )
            if len(ports) != args.replay_servers:
                raise SystemExit(
                    f"--replay-ports names {len(ports)} port(s) for "
                    f"--replay-servers {args.replay_servers}"
                )
            # Stash the VALIDATED list for the run call below — one
            # parse, one truth.
            args.replay_ports = ports
    elif args.replay_actors is not None and not offpolicy_standby:
        # The off-policy standby consumes --replay-actors (the fleet
        # it validates against at takeover); everyone else needs the
        # tier.
        raise SystemExit("--replay-actors requires --replay-servers")
    elif args.replay_ports is not None:
        raise SystemExit("--replay-ports requires --replay-servers")
    elif args.actor_param_endpoints is not None:
        raise SystemExit(
            "--actor-param-endpoints requires --replay-servers (it "
            "configures the spawned env-stepper fleet)"
        )
    elif args.autoscale is not None:
        raise SystemExit(
            "--autoscale requires --replay-servers (it resizes the "
            "spawned env-stepper fleet)"
        )
    if args.standby and not (algo == "impala" or offpolicy_standby):
        raise SystemExit(
            "--standby supports impala and the off-policy trainers "
            "(ddpg/td3/sac, with --replay-endpoints)"
        )
    if args.coordinate_preemption and algo != "impala":
        raise SystemExit(
            "--coordinate-preemption is impala-only "
            "(the actor-learner control plane)"
        )
    if offpolicy_standby and not args.replay_endpoints:
        raise SystemExit(
            "an off-policy --standby needs --replay-endpoints (the "
            "existing replay tier it attaches to at takeover; pin the "
            "primary's shard ports with --replay-ports)"
        )
    if offpolicy_standby and args.replay_servers:
        raise SystemExit(
            "--standby attaches to the primary's replay tier; drop "
            "--replay-servers (shard count = the --replay-endpoints "
            "list)"
        )
    if args.replay_endpoints and not offpolicy_standby:
        raise SystemExit(
            "--replay-endpoints requires an off-policy --standby "
            "(ddpg/td3/sac)"
        )
    if offpolicy_standby and args.redirector is not None:
        raise SystemExit(
            "--redirector is the IMPALA standby's actor-facing tier; "
            "off-policy env-stepper actors fail over via their "
            "param-plane priority endpoint lists (the primary's "
            "actor_param_endpoints naming each standby's "
            "--learner-bind) — drop --redirector"
        )
    if args.redirector is not None and not args.standby:
        raise SystemExit("--redirector requires --standby")
    if (args.standby_rank or args.standby_peers) and not args.standby:
        raise SystemExit(
            "--standby-rank/--standby-peers require --standby"
        )
    if args.shard is not None and algo != "impala":
        raise SystemExit("--shard is impala-only (the sharded learner)")
    if args.eval:
        if not args.checkpoint_dir:
            raise SystemExit("--eval requires --checkpoint-dir")
        from actor_critic_algs_on_tensorflow_tpu.algos.evaluation import (
            evaluate_checkpoint,
        )

        mean_ret, per_env, frac = evaluate_checkpoint(
            algo, cfg, args.checkpoint_dir,
            num_envs=args.eval_envs,
            max_steps=args.eval_steps,
            stochastic=args.stochastic,
            seed=args.seed if args.seed is not None else 1234,
            render_dir=args.render_dir,
        )
        print(
            f"[eval] avg_return={mean_ret:.2f} "
            f"min={per_env.min():.2f} max={per_env.max():.2f} "
            f"episodes_finished={frac * args.eval_envs:.0f}/{args.eval_envs}"
        )
        # Unfinished episodes report return 0 and would pollute the
        # distribution, so the hist only prints for complete evals.
        hist_line = format_return_hist(per_env) if frac >= 1.0 else None
        if hist_line:
            print(hist_line)
        return 0

    if algo == "impala":
        from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
            make_impala,
            run_impala,
            run_impala_distributed,
        )

        # Device-resident fast path (rollout_mode="device"/"mixed"):
        # flag-combination refusals up front, with the fix in the
        # message — the config-level constraints (env_shim, recurrent,
        # host envs, shards) are validated by make_impala itself.
        rollout_mode = getattr(cfg, "rollout_mode", "host")
        if rollout_mode != "host":
            if args.standby:
                raise SystemExit(
                    f"--standby requires rollout_mode='host' (the warm "
                    f"standby tails the wire-ingest topology; device "
                    f"env state cannot be tailed across a failover) — "
                    f"drop --set rollout_mode={rollout_mode}"
                )
            if args.shard is not None:
                raise SystemExit(
                    f"--shard requires rollout_mode='host': the fused "
                    f"program already shards envs over the data mesh "
                    f"inside one dispatch — drop --shard or --set "
                    f"rollout_mode={rollout_mode}"
                )
            if rollout_mode == "device" and args.actor_processes:
                raise SystemExit(
                    "rollout_mode='device' is the in-process Anakin "
                    "fast path (no actor fleet); drop "
                    "--actor-processes, or use rollout_mode='mixed' "
                    "to pair device self-play with wire actors"
                )
            if rollout_mode == "mixed" and not args.actor_processes:
                raise SystemExit(
                    "rollout_mode='mixed' interleaves device "
                    "self-play with wire-attached actor processes; "
                    "pass --actor-processes (or use "
                    "rollout_mode='device' for pure device-resident)"
                )

        # Sharded learner first: the per-host form must join the
        # jax.distributed runtime BEFORE anything touches the backend
        # (make_template below compiles against the global mesh).
        cfg, shard_plan, shard_coord = make_shard_runtime(args, cfg)

        coordinator = shard_coord
        if args.coordinate_preemption:
            coordinator = make_coordinator(args.coordinate_preemption)

        if args.standby:
            return _run_standby(args, cfg, writer, coordinator)

        def make_template():
            import jax

            # Structure only — restore converts to shape/dtype structs.
            return jax.eval_shape(
                make_impala(cfg).init, jax.random.PRNGKey(cfg.seed)
            )

        checkpointer, initial_state = _open_checkpointer(
            args, make_template,
            # Deliberately SHORT and decoupled from the barrier budget:
            # a fresh start under a restart wrapper that always passes
            # --resume finds an EMPTY dir on every shard — a non-zero
            # shard must give the (possibly mid-final-save) writer a
            # beat to surface its step, then proceed fresh well inside
            # the leader's first step-barrier deadline. A diverged
            # restore is caught loudly by that barrier's step check.
            wait_for_step_s=(
                min(15.0, cfg.shard_barrier_timeout_s / 4)
                if shard_plan is not None
                and shard_plan.multihost
                and shard_plan.shard_id != 0
                else None
            ),
            solo_process=shard_plan is not None and shard_plan.multihost,
        )
        if (
            checkpointer is not None
            and shard_plan is not None
            and shard_plan.multihost
        ):
            # Shard 0 owns the writes (through host numpy); peers skip
            # with a debug log — reads/restores delegate unchanged.
            from actor_critic_algs_on_tensorflow_tpu.distributed.sharding import (  # noqa: E501
                ShardCheckpointer,
            )

            checkpointer = ShardCheckpointer(
                checkpointer, shard_plan.shard_id
            )
        kwargs = {"coordinator": coordinator}
        if args.actor_processes:
            runner = run_impala_distributed
            kwargs["host"], kwargs["port"] = parse_bind(args.learner_bind)
            if shard_plan is not None:
                kwargs["shard"] = shard_plan
        else:
            runner = run_impala
        # Preemption-safe shutdown: SIGTERM/SIGINT set an event the
        # learner loop polls; it saves a final atomic checkpoint at the
        # interrupted step and tears down cleanly (KIND_CLOSE broadcast
        # to actor processes — no ConnectionError tail), exit code 0.
        shutdown = None
        if args.preempt_save:
            from actor_critic_algs_on_tensorflow_tpu.utils.health import (
                ShutdownSignal,
            )

            shutdown = ShutdownSignal().install()
            kwargs["stop_event"] = shutdown.event
        try:
            state, _ = runner(
                cfg,
                log_interval=args.log_interval,
                summary_writer=writer,
                checkpointer=checkpointer,
                checkpoint_interval=args.checkpoint_interval,
                initial_state=initial_state,
                **kwargs,
            )
        finally:
            if shutdown is not None:
                shutdown.uninstall()
            if coordinator is not None:
                coordinator.close()
        steps_per_batch = (
            cfg.batch_trajectories * cfg.envs_per_actor * cfg.rollout_length
        )
        _finalize_checkpointer(
            checkpointer, int(state.step) * steps_per_batch, state
        )
        if shutdown is not None and shutdown.event.is_set():
            print(
                f"[train] preempted: clean shutdown at learner "
                f"steps={int(state.step)} (resume with --resume)"
            )
        else:
            print(f"[train] done: learner steps={int(state.step)}")
        return 0

    from actor_critic_algs_on_tensorflow_tpu.algos import common

    if algo == "a2c":
        from actor_critic_algs_on_tensorflow_tpu.algos.a2c import make_a2c

        fns = make_a2c(cfg)
    elif algo == "ppo":
        from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

        fns = make_ppo(cfg)
    elif algo == "ddpg":
        from actor_critic_algs_on_tensorflow_tpu.algos.ddpg import make_ddpg

        fns = make_ddpg(cfg)
    elif algo == "td3":
        from actor_critic_algs_on_tensorflow_tpu.algos.td3 import make_td3

        fns = make_td3(cfg)
    else:
        from actor_critic_algs_on_tensorflow_tpu.algos.sac import make_sac

        fns = make_sac(cfg)

    if args.standby and algo in ("ddpg", "td3", "sac"):
        return _run_offpolicy_standby(args, fns, cfg, writer)

    if args.replay_servers:
        from actor_critic_algs_on_tensorflow_tpu.algos.offpolicy_distributed import (  # noqa: E501
            run_offpolicy_distributed,
        )

        checkpointer = None
        if args.checkpoint_dir:
            from actor_critic_algs_on_tensorflow_tpu.utils.checkpoint import (  # noqa: E501
                Checkpointer,
            )

            checkpointer = Checkpointer(args.checkpoint_dir)
        shutdown = None
        if args.preempt_save:
            from actor_critic_algs_on_tensorflow_tpu.utils.health import (
                ShutdownSignal,
            )

            shutdown = ShutdownSignal().install()
        host, port = parse_bind(args.learner_bind)
        try:
            result, history = run_offpolicy_distributed(
                fns,
                total_env_steps=cfg.total_env_steps,
                seed=cfg.seed,
                n_replay_shards=args.replay_servers,
                n_actors=args.replay_actors,
                host=host,
                port=port,
                log_interval=args.log_interval,
                summary_writer=writer,
                stop_event=(
                    shutdown.event if shutdown is not None else None
                ),
                checkpointer=checkpointer,
                checkpoint_interval=args.checkpoint_interval,
                resume=args.resume,
                replay_ports_fixed=args.replay_ports,
                actor_param_endpoints=(
                    [
                        parse_hostport(
                            s.strip(), "--actor-param-endpoints"
                        )
                        for s in args.actor_param_endpoints.split(",")
                        if s.strip()
                    ]
                    if args.actor_param_endpoints else None
                ),
            )
        finally:
            if shutdown is not None:
                shutdown.uninstall()
            if checkpointer is not None:
                checkpointer.wait()
                checkpointer.close()
        final = history[-1][1] if history else {}
        if shutdown is not None and shutdown.event.is_set():
            print(
                f"[train] preempted: clean shutdown at env_steps="
                f"{result.env_steps} (learner checkpoint + final "
                f"replay-ring snapshots flushed; resume with --resume)"
            )
        else:
            print(
                f"[train] done: env_steps={result.env_steps} "
                f"updates={result.updates} "
                f"avg_return={final.get('avg_return', float('nan')):.2f}"
            )
        return 0

    use_async = False
    if algo in ("ddpg", "td3", "sac") and args.host_loop == "async":
        from actor_critic_algs_on_tensorflow_tpu.algos import host_async

        if not host_async.host_async_supported(cfg):
            raise SystemExit(
                "--host-loop async needs a gym:/native: env and "
                "num_devices<=1"
            )
        use_async = True

    def make_template():
        import jax

        if use_async:
            # The async loop owns the host simulator: structure only.
            return jax.eval_shape(fns.init, jax.random.PRNGKey(cfg.seed))
        return fns.init(jax.random.PRNGKey(cfg.seed))

    checkpointer, state = _open_checkpointer(args, make_template, cfg)
    # The PR-3 sentinel glue, now shared by every checkpointed trainer:
    # the update programs emit the in-graph health_finite bit
    # (numerics_guards) and the loop rolls back to a last-good snapshot
    # on a trip instead of training — and checkpointing — NaNs. The
    # delayed check hides the guard fetch behind dispatch run-ahead.
    sentinel = None
    if getattr(cfg, "numerics_guards", False) and not _snapshot_fits(
        fns, cfg
    ):
        print("[train] sentinel: the train state does not fit a device "
              "beside a rollback copy of itself; the health_finite guard "
              "is logged, nothing is rolled back", flush=True)
    elif getattr(cfg, "numerics_guards", False):
        import jax

        from actor_critic_algs_on_tensorflow_tpu.utils import (
            health as health_lib,
        )

        if algo in ("ddpg", "td3", "sac") and not use_async:
            # Off-policy through the synchronous loop: snapshot ONLY
            # (params, opt_state). The replay ring is data, not derived
            # math — a full-state snapshot would double replay HBM per
            # ring slot — and ``merge`` grafts the restored slice onto
            # the current state at rollback so the ring/env carry stay.
            # (The async loop needs none of this: it hands the sentinel
            # a bare params/opt_state pair already.)
            sentinel = health_lib.TrainingHealthSentinel(
                copy_state=jax.jit(
                    lambda t: jax.tree_util.tree_map(
                        jax.numpy.copy, (t.params, t.opt_state)
                    )
                ),
                merge=lambda current, restored: current.replace(
                    params=restored[0], opt_state=restored[1]
                ),
                publish=lambda p: None,  # no actor fleet to re-point here
                delayed=True,
            )
        else:
            sentinel = health_lib.TrainingHealthSentinel(
                copy_state=jax.jit(
                    lambda t: jax.tree_util.tree_map(jax.numpy.copy, t)
                ),
                publish=lambda p: None,  # no actor fleet to re-point here
                delayed=True,
            )
    if use_async:
        from actor_critic_algs_on_tensorflow_tpu.algos.host_async import (
            run_host_async,
        )

        print("[train] host-async loop: envs on host CPU, updates on "
              f"{__import__('jax').devices()[0].platform}", flush=True)
        state, history = run_host_async(
            fns,
            total_env_steps=cfg.total_env_steps,
            seed=cfg.seed,
            log_interval_iters=args.log_interval,
            checkpointer=checkpointer,
            checkpoint_interval_iters=args.checkpoint_interval,
            initial_state=state,
            summary_writer=writer,
            sentinel=sentinel,
        )
    else:
        state, history = common.run_loop(
            fns,
            total_env_steps=cfg.total_env_steps,
            seed=cfg.seed,
            log_interval_iters=args.log_interval,
            checkpointer=checkpointer,
            checkpoint_interval_iters=args.checkpoint_interval,
            state=state,
            summary_writer=writer,
            sentinel=sentinel,
        )
    _finalize_checkpointer(
        checkpointer, int(state.step) * fns.steps_per_iteration, state
    )
    if history:
        final = history[-1][1]
        print(
            f"[train] done: env_steps={history[-1][0]} "
            f"steps_per_sec={final.get('steps_per_sec', 0):.0f} "
            f"avg_return={final.get('avg_return', float('nan')):.2f}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
