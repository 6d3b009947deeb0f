"""Device-trace profile of one recurrent flicker-pong update
(VERDICT r4 next#1): where does the recurrent iteration's time go?

Captures a jax.profiler trace of 2 steady-state iterations for the
given knobs (same knob syntax as recurrent_bench.py), then aggregates
the device-side trace events by op-name family and prints the top
buckets — the same methodology as the r2 PPO profile (PERF.md "Where
the time goes").

Usage: python scripts/recurrent_profile.py [knobs...] out=/tmp/rectrace
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import sys


def main() -> int:
    knobs = dict(kv.split("=", 1) for kv in sys.argv[1:])
    out = knobs.pop("out", "/tmp/rectrace")

    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import trace

    cfg = PPOConfig(
        env="PongFlickerTPU-v0",
        num_envs=int(knobs.get("num_envs", 256)),
        rollout_length=int(knobs.get("rollout", 128)),
        total_env_steps=10**9,
        frame_stack=int(knobs.get("frame_stack", 1)),
        torso=knobs.get("torso", "nature_cnn"),
        num_epochs=int(knobs.get("epochs", 4)),
        num_minibatches=int(knobs.get("minibatches", 4)),
        shuffle="env" if int(knobs.get("minibatches", 4)) > 1 else "full",
        lr=1e-3,
        recurrent=bool(int(knobs.get("recurrent", 1))),
        lstm_size=int(knobs.get("lstm_size", 256)),
        time_limit_bootstrap=False,
        compute_dtype=knobs.get("dtype", "bfloat16"),
        num_devices=len(jax.devices()),
    )
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)  # compile
    jax.block_until_ready(metrics)
    state, metrics = fns.iteration(state)  # warm
    jax.block_until_ready(metrics)

    with trace(out):
        for _ in range(2):
            state, metrics = fns.iteration(state)
        jax.block_until_ready(metrics)

    # Aggregate the Perfetto JSON: device-lane complete events by name.
    paths = sorted(glob.glob(f"{out}/**/*.trace.json.gz", recursive=True))
    if not paths:
        print(f"no trace written under {out}", file=sys.stderr)
        return 1
    with gzip.open(paths[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pid_names = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    device_pids = {
        pid
        for pid, name in pid_names.items()
        if any(k in name.lower() for k in ("tpu", "device", "xla"))
        and "host" not in name.lower()
    }
    buckets = collections.Counter()
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        dur = e.get("dur", 0) / 1e3  # us -> ms
        name = e.get("name", "?")
        # family = leading fusion/op stem, e.g. "fusion", "while",
        # "copy", "convolution", "dot"
        fam = name.split(".")[0].split("(")[0]
        buckets[fam] += dur
        total += dur
    print(f"trace: {paths[-1]}")
    print(f"total device time over 2 iterations: {total:.1f} ms")
    for fam, ms in buckets.most_common(25):
        print(f"  {fam:40s} {ms:9.1f} ms  {100 * ms / max(total, 1e-9):5.1f}%")
    # Top individual ops, for naming the exact while loops / fusions.
    ops = collections.Counter()
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            ops[e.get("name", "?")] += e.get("dur", 0) / 1e3
    print("top ops:")
    for name, ms in ops.most_common(15):
        print(f"  {name[:70]:70s} {ms:9.1f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
