"""Recurrent-path throughput harness (VERDICT r4 next#1).

Measures env-steps/sec for the recurrent flicker-pong workload (the
``ppo-flicker-pong`` preset's schedule) under a config knob matrix, in
the same best-of-N-windows discipline as ``scaling_bench.py`` so one
host hiccup cannot masquerade as a config effect.

Usage:
  python scripts/recurrent_bench.py                  # shipped config
  python scripts/recurrent_bench.py epochs=1         # knob overrides
  python scripts/recurrent_bench.py recurrent=0 frame_stack=4   # ff control

Knobs (key=value): num_envs, rollout, epochs, minibatches, lstm_size,
recurrent, frame_stack, dtype, shuffle, windows, iters_per_window,
torso.

Prints one line per window plus a summary {best, median, spread}.
"""

from __future__ import annotations

import statistics
import sys
import time


def main() -> int:
    knobs = dict(kv.split("=", 1) for kv in sys.argv[1:])
    num_envs = int(knobs.get("num_envs", 256))
    rollout = int(knobs.get("rollout", 128))
    epochs = int(knobs.get("epochs", 4))
    minibatches = int(knobs.get("minibatches", 4))
    lstm_size = int(knobs.get("lstm_size", 256))
    recurrent = bool(int(knobs.get("recurrent", 1)))
    frame_stack = int(knobs.get("frame_stack", 1))
    dtype = knobs.get("dtype", "bfloat16")
    shuffle = knobs.get("shuffle", "env")
    windows = int(knobs.get("windows", 5))
    iters_per_window = int(knobs.get("iters_per_window", 5))

    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )

    cfg = PPOConfig(
        env="PongFlickerTPU-v0",
        num_envs=num_envs,
        rollout_length=rollout,
        total_env_steps=10**9,
        frame_stack=frame_stack,
        torso=knobs.get("torso", "nature_cnn"),
        num_epochs=epochs,
        num_minibatches=minibatches,
        shuffle=shuffle if minibatches > 1 else "full",
        lr=1e-3,
        recurrent=recurrent,
        lstm_size=lstm_size,
        time_limit_bootstrap=False,
        compute_dtype=dtype,
        num_devices=len(jax.devices()),
    )
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))

    state, metrics = fns.iteration(state)  # compile + warmup
    jax.block_until_ready(metrics)

    rates = []
    for w in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters_per_window):
            state, metrics = fns.iteration(state)
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        rate = iters_per_window * fns.steps_per_iteration / dt
        rates.append(rate)
        print(f"window {w}: {rate:,.0f} env-steps/s", flush=True)

    best, med = max(rates), statistics.median(rates)
    print(
        f"summary: best={best:,.0f} median={med:,.0f} "
        f"spread={(best - min(rates)) / med:.1%} "
        f"config={ {k: v for k, v in knobs.items()} }",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
