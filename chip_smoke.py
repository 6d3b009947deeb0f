#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through ``cli.train.main`` (what
``train.py`` calls), at the shipped preset widths on the TPU this
process owns. Sizes come from the presets; only the step budget is cut.

  A  ``--preset ppo-pong`` (1024 envs x 128 steps, Nature-CNN, bf16):
     4 fused iterations, a checkpoint, then a second invocation with
     ``--resume`` for 2 more — the resume runs on executables read back
     from the compile cache the first invocation filled.
  B  ``--preset impala-pong``: host-thread actor + learner dispatching
     to the same chip, donated ``learner_step``, 40 learner steps.
  C  the serving tier: the same preset with ``--actor-processes`` and
     two env-shim processes, until the InferenceServer has answered a
     few hundred ``act()`` requests. Every child must come up on the
     CPU: one process owns the chip.

Exits non-zero unless ``jax.devices()[0].platform == "tpu"``; a failed
check in any leg raises. Each leg prints its wall time, its compile
seconds (this process's tracing + lowering + backend compile or cache
read, from ``jax.monitoring``, summed over threads — leg B's actor
and learner compile side by side) and a one-line result. Reads tracked
files only; writes only under ``chiprun_out/chip_smoke/`` and the
compile cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``).
The last stdout line is one JSON object naming the device.
"""

from __future__ import annotations

import collections
import contextlib
import faulthandler
import json
import math
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

PPO_ITER_STEPS = 1024 * 128      # ppo-pong: num_envs * rollout_length
IMPALA_BATCH_STEPS = 256 * 32    # impala-pong: one 256-env trajectory

# jax.monitoring duration events that make up "compile time" as a user
# waits for it; the last one is the cache read when the cache is warm.
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_durations: collections.Counter = collections.Counter()
_counts: collections.Counter = collections.Counter()


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def _capture(log_path: str):
    """Send fds 1 and 2 — this process's AND its children's — to
    ``log_path`` for the duration."""
    sys.stdout.flush()
    sys.stderr.flush()
    saved = os.dup(1), os.dup(2)
    try:
        with open(log_path, "wb") as f:
            os.dup2(f.fileno(), 1)
            os.dup2(f.fileno(), 2)
            try:
                yield
            finally:
                sys.stdout.flush()
                sys.stderr.flush()
                os.dup2(saved[0], 1)
                os.dup2(saved[1], 2)
    finally:
        os.close(saved[0])
        os.close(saved[1])


def _train(log_name: str, argv: list) -> str:
    """One ``train.py`` invocation, in this process. Everything it and
    its children print is captured, replayed on stdout and returned, so
    a leg's result is read back from what the program printed."""
    from actor_critic_algs_on_tensorflow_tpu.cli import train

    _say("$ python train.py " + " ".join(argv))
    log_path = os.path.join(OUT_DIR, log_name)
    try:
        with _capture(log_path):
            rc = train.main(argv)
    finally:
        with open(log_path, errors="replace") as f:
            log = f.read()
        sys.stdout.write(log)
        sys.stdout.flush()
    _check(rc == 0, f"train.main exit code {rc}")
    return log


def _metric_lines(log: str) -> list:
    """The ``step=N key=value ...`` lines of a training log."""
    rows = []
    for line in log.splitlines():
        if line.startswith("step="):
            rows.append(
                {k: float(v) for k, v in
                 (kv.split("=", 1) for kv in line.split())}
            )
    return rows


def _check_finite(rows: list, min_rows: int, what: str) -> None:
    _check(len(rows) >= min_rows, f"{what}: {len(rows)} log lines")
    for row in rows:
        _check(math.isfinite(row["loss"]), f"{what}: loss {row['loss']}")
        _check(row.get("health_finite") == 1.0,
               f"{what}: health_finite {row.get('health_finite')}")


class _Phase:
    """Wall and compile seconds of one leg, from construction to
    ``done``."""

    def __init__(self, name: str):
        self.name = name
        _say(f"--- leg {name} ---")
        self._t0 = time.perf_counter()
        self._d0 = _durations.copy()
        self._c0 = _counts.copy()

    def done(self, result: str) -> dict:
        d = _durations - self._d0
        c = _counts - self._c0
        out = {
            "wall_s": round(time.perf_counter() - self._t0, 1),
            "compile_s": round(
                sum(d[e] for e in _TRACE_EVENTS) + d[_BACKEND_EVENT], 1
            ),
            "backend_compile_s": round(d[_BACKEND_EVENT], 1),
            "cache_hits": c["/jax/compilation_cache/cache_hits"],
            "cache_misses": c["/jax/compilation_cache/cache_misses"],
            "result": result,
        }
        _say(
            f"leg {self.name} ok: wall={out['wall_s']}s "
            f"compile={out['compile_s']}s "
            f"(backend {out['backend_compile_s']}s, cache "
            f"{out['cache_hits']} hits / {out['cache_misses']} misses) "
            f"| {result}"
        )
        return out


def leg_a_ppo(jax) -> dict:
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo
    from actor_critic_algs_on_tensorflow_tpu.cli import train

    phase = _Phase("A ppo-pong")
    # Placement, on the preset's own config: env leaves sharded
    # over EVERY visible device, params replicated on every one.
    _, cfg = train.make_config(
        train.build_parser().parse_args(["--preset", "ppo-pong"])
    )
    every = set(jax.devices())
    state = make_ppo(cfg).init(jax.random.PRNGKey(cfg.seed))
    for leaf in jax.tree_util.tree_leaves((state.obs, state.env_state)):
        _check(leaf.sharding.device_set == every,
               f"env leaf on {leaf.sharding.device_set}")
        _check(len(every) == 1 or not leaf.sharding.is_fully_replicated,
               "env leaf replicated, not sharded")
    for leaf in jax.tree_util.tree_leaves(state.params):
        _check(leaf.sharding.device_set == every
               and leaf.sharding.is_fully_replicated,
               f"params leaf not replicated on all: {leaf.sharding}")
    _check(state.obs.shape[0] == cfg.num_envs == 1024, "preset width")
    del state

    ckpt = os.path.join(OUT_DIR, "ckpt_ppo")
    try:
        first = 4 * PPO_ITER_STEPS
        log = _train("legA_train.log", [
            "--preset", "ppo-pong", "--total-steps", str(first),
            "--log-interval", "1", "--checkpoint-dir", ckpt,
            "--checkpoint-interval", "2",
        ])
        _check("device: platform=tpu" in log, "device line")
        rows = _metric_lines(log)
        _check_finite(rows, 4, "leg A")
        _check(rows[-1]["step"] == first, f"last step {rows[-1]['step']}")
        rate = rows[-1]["steps_per_sec"]

        total = first + 2 * PPO_ITER_STEPS
        log = _train("legA_resume.log", [
            "--preset", "ppo-pong", "--total-steps", str(total),
            "--log-interval", "1", "--checkpoint-dir", ckpt,
            "--checkpoint-interval", "2", "--resume",
        ])
        _check(f"resumed from step {first}" in log, "resume line")
        rows = _metric_lines(log)
        _check_finite(rows, 2, "leg A resume")
        _check(rows[0]["step"] == first + PPO_ITER_STEPS
               and rows[-1]["step"] == total,
               f"resumed steps {[r['step'] for r in rows]}")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    # This runtime counts live arrays under peak_bytes_in_use and the
    # programs' temporaries under peak_bytes_reserved (it matches the
    # fused iteration's memory_analysis().temp_size_in_bytes).
    stats = [d.memory_stats() or {} for d in jax.devices()]
    arrays = max(s.get("peak_bytes_in_use", 0) for s in stats)
    temps = max(s.get("peak_bytes_reserved", 0) for s in stats)
    out = phase.done(
        f"6 iterations of {PPO_ITER_STEPS} steps on {len(every)} "
        f"device(s), loss finite, health_finite=1, resumed at "
        f"{first}; steps_per_sec of the 4th iteration {rate:.0f}; "
        f"peak HBM per device {arrays / 2**30:.2f} GiB arrays + "
        f"{temps / 2**30:.2f} GiB program temporaries"
    )
    out["peak_bytes_in_use"] = arrays
    out["peak_bytes_reserved"] = temps
    return out


def leg_b_impala() -> dict:
    phase = _Phase("B impala-pong")
    steps = 40
    log = _train("legB_impala.log", [
        "--preset", "impala-pong",
        "--total-steps", str(steps * IMPALA_BATCH_STEPS),
        "--log-interval", "10",
    ])
    rows = _metric_lines(log)
    _check_finite(rows, 3, "leg B")
    _check(f"[train] done: learner steps={steps}" in log, "done line")
    return phase.done(
        f"{steps} donated learner steps beside a host-thread actor, "
        f"loss finite, health_finite=1; last window "
        f"{rows[-1]['steps_per_sec']:.0f} steps_per_sec"
    )


def leg_c_serving() -> dict:
    phase = _Phase("C serving tier")
    log = _train("legC_serving.log", [
        "--preset", "impala-pong", "--actor-processes",
        "--set", "actor_mode=env_shim", "--set", "num_actors=2",
        "--total-steps", str(12 * IMPALA_BATCH_STEPS),
        "--log-interval", "4",
    ])
    rows = _metric_lines(log)
    _check_finite(rows, 2, "leg C")
    served = rows[-1].get("serve_requests", 0.0)
    _check(served >= 200, f"serve_requests {served}")
    # One process owns the chip: the parent's is the only device
    # line that says tpu, and both shims said cpu.
    lines = re.findall(r"^\[([^\]]+)\] device: platform=(\w+)", log, re.M)
    on_tpu = [who for who, plat in lines if plat == "tpu"]
    shims = [who for who, plat in lines if who.startswith("env-shim")]
    _check(on_tpu == ["train"], f"processes on the chip: {on_tpu}")
    _check(len(shims) >= 2, f"env-shim device lines: {lines}")
    _check(all(plat == "cpu" for who, plat in lines if who != "train"),
           f"a child off the cpu: {lines}")
    _check(log.count("learner closed the stream") >= 2,
           "shims did not exit on the learner's close")
    return phase.done(
        f"{served:.0f} act() requests answered for {len(shims)} "
        f"env-shim processes, all children on cpu, loss finite"
    )


def main() -> int:
    # A hang must end inside the driver's 1200 s, with a traceback.
    faulthandler.dump_traceback_later(1150, exit=True)
    import jax

    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import device_line
    from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    _say(device_line())
    if devices[0].platform != "tpu":
        print(
            f"[chip_smoke] FAIL: this check runs on a TPU; JAX found "
            f"platform={devices[0].platform}",
            file=sys.stderr, flush=True,
        )
        return 1
    _say(f"compile cache: {cache_dir}")
    os.makedirs(OUT_DIR, exist_ok=True)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: _durations.update({event: secs})
    )
    jax.monitoring.register_event_listener(
        lambda event, **kw: _counts.update({event: 1})
    )

    legs = {
        "A": leg_a_ppo(jax),
        "B": leg_b_impala(),
        "C": leg_c_serving(),
    }
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump({"device": device, "legs": legs}, f, indent=1)
    _say("legs " + json.dumps(legs))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
