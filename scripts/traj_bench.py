"""Trajectory-plane wire measurements (ISSUE 6, PERF.md "Trajectory
data plane").

Legs, each printed as one line of evidence:

  1. wire — a fleet of real ``ActorClient``s pushes REAL pixel-obs
     rollouts (``SyntheticPixels-v0`` through the actual jitted actor
     programs) at one ``LearnerServer``, codec on vs off: inbound
     MB/s, wire bytes per frame, compression ratio from the server's
     own inbound counters, plus single-threaded encode/decode cost per
     frame and a bit-exactness check of the decoded stream.
  2. e2e — a small ``run_impala_distributed`` run on the pixel fixture
     with ``traj_codec`` on vs off: learner stall share and inbound
     MB from the ordinary metrics stream (does hiding 10x fewer bytes
     behind compute change the stall picture).

Run: JAX_PLATFORMS=cpu python scripts/traj_bench.py [wire|e2e|all]
"""

import os
import sys
import threading
import time

import jax

# A host-side drill: stay off the chip whatever the environment says.
jax.config.update("jax_platforms", "cpu")
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from actor_critic_algs_on_tensorflow_tpu.algos import impala
from actor_critic_algs_on_tensorflow_tpu.utils import metric_names
from actor_critic_algs_on_tensorflow_tpu.distributed import codec
from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
    ActorClient,
    LearnerServer,
)


def _pixel_cfg(env: str, rollout_length: int, envs_per_actor: int):
    return impala.ImpalaConfig(
        env=env,
        num_actors=1,
        envs_per_actor=envs_per_actor,
        rollout_length=rollout_length,
        batch_trajectories=2,
        queue_size=8,
        num_devices=1,
        lr_decay=False,
    )


def synthetic_rollouts(
    n: int,
    *,
    env: str = "SyntheticPixels-v0",
    rollout_length: int = 32,
    envs_per_actor: int = 8,
    seed: int = 0,
):
    """``n`` REAL rollouts off the jitted actor programs (init policy,
    fresh env stream): ``(traj_leaves, ep_leaves, tdelta_ok)`` per
    rollout, leaves as numpy — exactly what an actor process pushes."""
    cfg = _pixel_cfg(env, rollout_length, envs_per_actor)
    programs = impala.make_impala(cfg)
    rollout_fn, reset_fn = programs.make_actor_programs(0)
    params = programs.init(jax.random.PRNGKey(cfg.seed)).params
    key = jax.random.PRNGKey(seed)
    key, k = jax.random.split(key)
    env_state, obs, carry = reset_fn(k)
    out = []
    tdelta_ok = None
    for _ in range(n):
        key, k = jax.random.split(key)
        env_state, obs, carry, traj, ep = rollout_fn(
            params, env_state, obs, carry, k
        )
        if tdelta_ok is None:
            tdelta_ok = [
                ax == 1
                for ax in jax.tree_util.tree_leaves(
                    impala.trajectory_batch_axes(traj)
                )
            ]
        out.append(
            (
                [np.asarray(x) for x in jax.tree_util.tree_leaves(traj)],
                [np.asarray(x) for x in jax.tree_util.tree_leaves(ep)],
                tdelta_ok,
            )
        )
    return out


def wire_leg(
    *,
    n_actors: int = 16,
    pushes_per_actor: int = 8,
    rollout_length: int = 32,
    envs_per_actor: int = 8,
    env: str = "SyntheticPixels-v0",
) -> dict:
    """Fleet push throughput, codec on vs off, one real server."""
    rollouts = synthetic_rollouts(
        max(4, n_actors // 2),
        env=env,
        rollout_length=rollout_length,
        envs_per_actor=envs_per_actor,
    )
    raw_frame_mb = sum(x.nbytes for x in rollouts[0][0]) / 1e6

    # Single-threaded codec cost + bit-exactness on the same stream.
    enc = codec.TrajEncoder()
    coded_frames = [
        enc.encode(traj, td) for traj, _, td in rollouts
    ]
    # Time the decode ALONE; the bit-exactness assert runs after the
    # clock stops (it costs several x the decode itself and would
    # dominate the reported per-frame figure).
    t0 = time.perf_counter()
    decoded_frames = [codec.decode_traj(a) for a in coded_frames]
    decode_s = (time.perf_counter() - t0) / len(coded_frames)
    for decoded, (traj, _, _) in zip(decoded_frames, rollouts):
        for a, b in zip(traj, decoded):
            np.testing.assert_array_equal(a, b)  # lossless, bit-exact

    out = {
        "actors": n_actors,
        "raw_frame_mb": round(raw_frame_mb, 3),
        "encode_ms_per_frame": round(
            enc.encode_s / enc.frames * 1e3, 2
        ),
        "decode_ms_per_frame": round(decode_s * 1e3, 2),
    }
    for label, use_codec in (("coded", True), ("plain", False)):
        server = LearnerServer(
            lambda traj, ep: True, log=lambda m: None
        )
        encoders = [
            codec.TrajEncoder() if use_codec else None
            for _ in range(n_actors)
        ]
        barrier = threading.Barrier(n_actors + 1)
        errors = []

        def pusher(i):
            try:
                client = ActorClient("127.0.0.1", server.port)
                barrier.wait()
                for j in range(pushes_per_actor):
                    traj, ep, td = rollouts[(i + j) % len(rollouts)]
                    if encoders[i] is not None:
                        arrays = encoders[i].encode(traj, td)
                        client.push_trajectory_coded(
                            arrays, len(traj), ep
                        )
                    else:
                        client.push_trajectory(traj, ep)
                client.close()
            except BaseException as e:  # surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=pusher, args=(i,), daemon=True)
            for i in range(n_actors)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join(timeout=600.0)
        wall = time.perf_counter() - t0
        m = server.metrics()
        server.close()
        if errors:
            raise errors[0]
        frames = n_actors * pushes_per_actor
        out[label] = {
            "wire_mb_in": round(
                m[metric_names.TRANSPORT + "traj_mb_in"], 3
            ),
            "wire_mb_per_sec": round(
                m[metric_names.TRANSPORT + "traj_mb_in"] / wall, 2
            ),
            "goodput_mb_per_sec": round(raw_frame_mb * frames / wall, 2),
            "frames_per_sec": round(frames / wall, 1),
        }
    out["wire_reduction"] = round(
        out["plain"]["wire_mb_in"] / max(out["coded"]["wire_mb_in"], 1e-9),
        2,
    )
    return out


def e2e_leg(
    *,
    iters: int = 12,
    env: str = "SyntheticPixels-v0",
    num_actors: int = 4,
) -> dict:
    """Learner stall share + inbound MB with the codec on vs off, on a
    real distributed run over the pixel fixture."""
    out = {}
    for label, on in (("codec_on", True), ("codec_off", False)):
        cfg = impala.ImpalaConfig(
            env=env,
            num_actors=num_actors,
            envs_per_actor=4,
            rollout_length=16,
            batch_trajectories=4,
            queue_size=8,
            num_devices=1,
            lr_decay=False,
            traj_codec=on,
            total_env_steps=4 * 4 * 16 * iters,
        )
        t0 = time.perf_counter()
        _, history = impala.run_impala_distributed(
            cfg, log_interval=1, log_fn=lambda s, m: None
        )
        wall = time.perf_counter() - t0
        stall = sum(
            m.get(metric_names.PIPELINE + "stall_s", 0.0)
            for _, m in history
        )
        last = history[-1][1]
        out[label] = {
            "steps_per_sec": round(last["steps_per_sec"], 1),
            "stall_share": round(stall / max(wall, 1e-9), 4),
            "wire_mb_in": round(
                last[metric_names.TRANSPORT + "traj_mb_in"], 3
            ),
            "codec_ratio": last.get("traj_codec_ratio", 1.0),
        }
    return out


def main() -> int:
    leg = sys.argv[1] if len(sys.argv) > 1 else "all"
    if leg in ("wire", "all"):
        print({"traj_wire": wire_leg()}, flush=True)
    if leg in ("e2e", "all"):
        print({"traj_e2e": e2e_leg()}, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
