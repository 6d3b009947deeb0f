"""Control-plane ledger measurements (ISSUE 4 + ISSUE 5, PERF.md
"Control plane" / "Param data plane").

Legs, each printed as one line of evidence:

  1. failover gap — kill the primary learner mid-run and measure
     kill -> first learner step completed by the successor, for BOTH
     recovery modes: the warm standby (programs compiled + checkpoint
     tailed in memory before the kill; since ISSUE 5 also param-tailed
     and serving early, with the redirector's fallback landing actors
     on it pre-takeover) and the old-world restart-from-disk (fresh
     process: import jax, compile, restore, then serve). Same actor
     fleet, same redirector, same config.
  2. delayed guard check — sentinel metrics fetch same-step vs
     one-step-late over the identical learner_step stream (no actors:
     isolates the fetch stall the delay exists to hide).
  3. wire checksum cost — zlib.crc32 throughput over a typical
     trajectory frame's payload bytes (the per-leaf CRC is one pass
     over data that crosses the kernel boundary anyway).
  4. param wire codec — bytes per publish-fetch through the REAL wire
     on a converging CartPole run (delta + shuffle + zlib vs the full
     frame), split by training phase (deltas shrink as lr decays).
  5. publish -> actor-visible latency — KIND_PARAMS_NOTIFY wake +
     delta fetch, measured publish() to fetch-complete.
  6. election (ISSUE 10) — the N-standby quorum drill: primary killed
     mid-run with N warm standbys armed; measure kill -> the WINNER's
     first completed learner step, and assert exactly one standby
     took over (losers re-arm then stand down; the fencing epoch is
     read back from the winner's run).

Run: JAX_PLATFORMS=cpu python scripts/controlplane_bench.py [leg]
(legs: checksum guard warm cold params notify election all)
"""

import dataclasses
import os
import signal
import socket
import sys
import time
import zlib

import jax

# A host-side drill: stay off the chip whatever the environment says.
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from actor_critic_algs_on_tensorflow_tpu.algos import impala
from actor_critic_algs_on_tensorflow_tpu.distributed.controlplane import (
    Redirector,
)
from actor_critic_algs_on_tensorflow_tpu.utils import health
from actor_critic_algs_on_tensorflow_tpu.utils import metric_names
from actor_critic_algs_on_tensorflow_tpu.utils.checkpoint import Checkpointer


def _cfg(total_iters):
    return impala.ImpalaConfig(
        env="CartPole-v1", num_actors=2, envs_per_actor=4,
        rollout_length=8, batch_trajectories=2, queue_size=4,
        total_env_steps=2 * 4 * 8 * total_iters, num_devices=1,
        transport_heartbeat_s=0.2, transport_idle_timeout_s=10.0,
    )


def _primary_main(cfg, port, ckpt_dir):
    jax.config.update("jax_platforms", "cpu")
    ckpt = Checkpointer(ckpt_dir, async_save=False)
    impala.run_impala_distributed(
        cfg, log_interval=1, log_fn=lambda s, m: None,
        host="127.0.0.1", port=port,
        checkpointer=ckpt, checkpoint_interval=2, external_actors=True,
    )


def _cold_restart_main(cfg, port, ckpt_dir, t0):
    """The old world: fresh process restores from disk and serves."""
    print(f"COLD_ENTER {time.time() - t0:.3f}", flush=True)  # spawn+imports
    jax.config.update("jax_platforms", "cpu")
    ckpt = Checkpointer(ckpt_dir, async_save=False)
    template = jax.eval_shape(
        impala.make_impala(cfg).init, jax.random.PRNGKey(cfg.seed)
    )
    state = ckpt.restore(template)
    print(f"COLD_RESTORED {time.time() - t0:.3f}", flush=True)
    first = []

    def log_fn(s, m):
        if not first:
            first.append(time.time())
            print(f"COLD_FIRST_STEP {first[0] - t0:.3f}", flush=True)

    impala.run_impala_distributed(
        cfg, log_interval=1, log_fn=log_fn,
        host="127.0.0.1", port=port,
        checkpointer=ckpt, checkpoint_interval=10**9,
        initial_state=state, external_actors=True,
    )


def failover_leg(mode: str) -> float:
    """Seconds from primary kill to the successor's first completed
    learner step. mode: 'warm' | 'cold'."""
    import multiprocessing as mp
    import tempfile

    ctx = mp.get_context("spawn")
    tmp = tempfile.mkdtemp(prefix=f"failover-{mode}-")
    cfg = _cfg(400)
    probe = socket.create_server(("127.0.0.1", 0))
    primary_port = probe.getsockname()[1]
    probe.close()
    redirector = Redirector("127.0.0.1", primary_port)
    primary = ctx.Process(
        target=_primary_main, args=(cfg, primary_port, tmp), daemon=True
    )
    primary.start()
    actors = [
        ctx.Process(
            target=impala._actor_process_main,
            args=(cfg, i, "127.0.0.1", redirector.port, 1000 + i, 0),
            daemon=True,
        )
        for i in range(cfg.num_actors)
    ]
    for a in actors:
        a.start()

    reader = Checkpointer(tmp, async_save=False)
    spb = cfg.batch_trajectories * cfg.envs_per_actor * cfg.rollout_length
    while True:
        reader.refresh()
        latest = reader.latest_step()
        if latest is not None and latest >= 4 * spb:
            break
        time.sleep(0.1)

    gap = None
    if mode == "warm":
        # Standby compiles + tails BEFORE the kill (the steady state).
        import threading

        result = {}

        def redirect(h, p):
            result.setdefault("redirect_t", time.monotonic())
            redirector.redirect(h, p, force=True)

        def on_serving(h, p):
            # The hot-standby data plane is up: arm the fallback route
            # so actors that lose the primary land on the standby on
            # their FIRST retry (reconnect paid pre-takeover).
            redirector.set_fallback(h, p)

        ready = threading.Event()

        def on_ready(monitor):
            result["monitor"] = monitor
            ready.set()

        def standby():
            first = []

            def log_fn(s, m):
                if not first:
                    first.append(time.monotonic())
                    result["first_step_t"] = first[0]

            impala.run_impala_standby(
                cfg,
                checkpointer=Checkpointer(tmp, async_save=False),
                primary_host="127.0.0.1", primary_port=primary_port,
                redirect=redirect,
                heartbeat_interval_s=0.2, takeover_deadline_s=1.0,
                log_interval=1, log_fn=log_fn,
                checkpoint_interval=10**9,
                on_serving=on_serving, on_ready=on_ready,
            )

        t = threading.Thread(target=standby, daemon=True)
        t.start()
        # Steady state first: the warm compile's duration varies, so a
        # fixed sleep can kill the primary BEFORE the monitor's first
        # contact — that measures the never-seen takeover grace, not
        # the failover. ``on_ready`` is the supervisor contract for
        # "the pair is armed"; one pong proves first contact, and a
        # short settle lets the param tailer land steady-state fetches.
        if not ready.wait(timeout=240.0):
            raise RuntimeError("standby never armed (warm compile hung?)")
        mon = result["monitor"]
        arm_deadline = time.monotonic() + 60.0
        while mon.pongs < 1 and time.monotonic() < arm_deadline:
            time.sleep(0.05)
        time.sleep(2.0)
        os.kill(primary.pid, signal.SIGKILL)
        t_kill = time.monotonic()
        t.join(timeout=570.0)
        gap = result["first_step_t"] - t_kill
        print(
            f"FAILOVER_WARM_SPLIT detect+bind={result['redirect_t'] - t_kill:.3f}s "
            f"redirect->first_step={result['first_step_t'] - result['redirect_t']:.3f}s "
            f"fallback_preconnects={redirector.fallback_connections}",
            flush=True,
        )
    else:
        os.kill(primary.pid, signal.SIGKILL)
        t0 = time.time()
        # The cold learner reuses the primary's (now free) fixed port;
        # it prints COLD_FIRST_STEP (seconds since the kill) to the
        # inherited stdout — that line IS the measurement.
        cold = ctx.Process(
            target=_cold_restart_main,
            args=(cfg, primary_port, tmp, t0), daemon=True,
        )
        cold.start()
        redirector.redirect("127.0.0.1", primary_port, force=True)
        cold.join(timeout=570.0)
        gap = float("nan")
    primary.join(timeout=5.0)
    redirector.close()
    for a in actors:
        a.join(timeout=5.0)
        if a.is_alive():
            a.terminate()
    reader.close()
    return gap


def election_leg(
    n_standbys: int = 3, total_iters: int = 400
) -> dict:
    """Seconds from primary kill to the ELECTION WINNER's first
    completed learner step, with ``n_standbys`` warm quorum standbys
    (rank-ordered peers list, shared checkpoint dir, fencing epochs).
    Returns the JSON-able dict ``bench.py --measure-election``
    merges; also printed as a FAILOVER_ELECTION line."""
    import multiprocessing as mp
    import tempfile
    import threading

    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        LearnerServer,
    )

    ctx = mp.get_context("spawn")
    tmp = tempfile.mkdtemp(prefix="failover-election-")
    cfg = dataclasses.replace(
        _cfg(total_iters),
        election_probe_timeout_s=0.5,
        election_probe_attempts=2,
    )
    spb = cfg.batch_trajectories * cfg.envs_per_actor * cfg.rollout_length
    probe = socket.create_server(("127.0.0.1", 0))
    primary_port = probe.getsockname()[1]
    probe.close()
    # Rank-ordered standby endpoints (each standby's early listener).
    peer_probes = [socket.create_server(("127.0.0.1", 0)) for _ in
                   range(n_standbys)]
    peers = [("127.0.0.1", p.getsockname()[1]) for p in peer_probes]

    redirector = Redirector("127.0.0.1", primary_port)
    redirector.set_fallbacks(peers)
    primary = ctx.Process(
        target=_primary_main, args=(cfg, primary_port, tmp), daemon=True
    )
    primary.start()
    actors = [
        ctx.Process(
            target=impala._actor_process_main,
            args=(cfg, i, "127.0.0.1", redirector.port, 1000 + i, 0),
            daemon=True,
        )
        for i in range(cfg.num_actors)
    ]
    for a in actors:
        a.start()

    result = {"takeovers": [], "ready": 0}
    lock = threading.Lock()
    armed = threading.Event()

    def redirect(h, p, epoch=None):
        result.setdefault("redirect_t", time.monotonic())
        result.setdefault("redirect_epoch", epoch)
        redirector.redirect(h, p, epoch=epoch)

    def on_ready(monitor):
        with lock:
            result["ready"] += 1
            if result["ready"] >= n_standbys:
                armed.set()
        result.setdefault("monitor", monitor)

    def standby(rank):
        ckpt = Checkpointer(tmp, async_save=False)
        first = []

        def log_fn(s, m):
            if not first:
                first.append(time.monotonic())
                result["first_step_t"] = first[0]

        peer_probes[rank].close()  # hand the reserved port over
        out = impala.run_impala_standby(
            cfg,
            checkpointer=ckpt,
            primary_host="127.0.0.1", primary_port=primary_port,
            host="127.0.0.1", port=peers[rank][1],
            redirect=redirect,
            heartbeat_interval_s=0.2, takeover_deadline_s=1.0,
            log_interval=1, log_fn=log_fn,
            checkpoint_interval=10**9,
            standby_id=rank, peers=peers,
            on_ready=on_ready,
        )
        if out is not None:
            with lock:
                result["takeovers"].append(rank)
            # Final save so the losers' completion check recognizes
            # the finished job and stands down (the CLI's finalize).
            ckpt.save(int(out[0].step) * spb, out[0])
            ckpt.wait()
        ckpt.close()

    threads = [
        threading.Thread(target=standby, args=(r,), daemon=True)
        for r in range(n_standbys)
    ]
    for t in threads:
        t.start()

    reader = Checkpointer(tmp, async_save=False)
    while True:
        reader.refresh()
        latest = reader.latest_step()
        if latest is not None and latest >= 4 * spb:
            break
        time.sleep(0.1)
    if not armed.wait(timeout=240.0):
        raise RuntimeError("standby quorum never armed")
    mon = result["monitor"]
    arm_deadline = time.monotonic() + 60.0
    while mon.pongs < 1 and time.monotonic() < arm_deadline:
        time.sleep(0.05)
    time.sleep(2.0)

    os.kill(primary.pid, signal.SIGKILL)
    t_kill = time.monotonic()
    # The freed port must stay DEAD for the drill (probe-close
    # honesty): hold it bound-but-not-listening.
    dead = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    dead.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        dead.bind(("127.0.0.1", primary_port))
    except OSError:
        pass
    for t in threads:
        t.join(timeout=570.0)
    dead.close()
    primary.join(timeout=5.0)
    redirector.close()
    for a in actors:
        a.join(timeout=10.0)
        if a.is_alive():
            a.terminate()
    reader.close()

    gap = result["first_step_t"] - t_kill
    out = {
        "election_gap_s": round(gap, 3),
        "standbys": n_standbys,
        "takeovers": sorted(result["takeovers"]),
        "winner_rank": (
            result["takeovers"][0] if result["takeovers"] else None
        ),
        "losers_stood_down": len(result["takeovers"]) == 1,
        "fencing_epoch": result.get("redirect_epoch"),
        "detect_elect_bind_s": round(
            result["redirect_t"] - t_kill, 3
        ),
    }
    print(
        f"FAILOVER_ELECTION gap={out['election_gap_s']}s "
        f"standbys={n_standbys} winner_rank={out['winner_rank']} "
        f"takeovers={out['takeovers']} "
        f"detect+elect+bind={out['detect_elect_bind_s']}s "
        f"fencing_epoch={out['fencing_epoch']} "
        f"(kill -> winner's first learner step; losers re-armed then "
        f"stood down)",
        flush=True,
    )
    return out


def guard_fetch_leg():
    cfg = _cfg(1)
    programs = impala.make_impala(cfg)
    state = programs.init(jax.random.PRNGKey(0))
    rollout, env_reset = programs.make_actor_programs(0)
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    env_state, obs, carry, traj, _ = rollout(
        state.params, env_state, obs, carry, jax.random.PRNGKey(2)
    )
    batch = impala.stack_trajectories(
        [traj] * cfg.batch_trajectories
    )
    step = programs.learner_step

    def run(delayed, n=300):
        s = programs.init(jax.random.PRNGKey(0))
        sent = health.TrainingHealthSentinel(
            copy_state=programs.copy_state, publish=lambda p: None,
            delayed=delayed, snapshot_interval=50, log=lambda m: None,
        )
        sent.seed(s, -1)
        s, m = step(s, batch)  # compile
        t0 = time.perf_counter()
        for i in range(n):
            s, m = step(s, batch)
            s = sent.after_step(i, s, m)
        s = sent.flush(s)
        jax.block_until_ready(s.params)
        return n / (time.perf_counter() - t0)

    # Interleaved reps (PERF.md measurement discipline).
    imm, dly = [], []
    for _ in range(3):
        imm.append(run(False))
        dly.append(run(True))
    print(
        f"GUARD_FETCH immediate={max(imm):.1f}/s delayed={max(dly):.1f}/s "
        f"(best of 3 interleaved; speedup {max(dly) / max(imm):.3f}x)"
    )


def checksum_leg():
    T, B = 32, 64
    leaves = [
        np.random.default_rng(0).random((T, B, 4)).astype(np.float32),
        np.zeros((T, B), np.int32),
        np.ones((T, B), np.float32),
        np.zeros((T, B), np.float32),
        -np.ones((T, B), np.float32),
        np.zeros((B, 4), np.float32),
    ]
    total = sum(x.nbytes for x in leaves)
    reps = 2000
    t0 = time.perf_counter()
    for _ in range(reps):
        for x in leaves:
            zlib.crc32(memoryview(x).cast("B"))
    dt = time.perf_counter() - t0
    per_frame = dt / reps
    print(
        f"CHECKSUM frame={total / 1024:.0f}KiB crc_per_frame="
        f"{per_frame * 1e6:.1f}us throughput={total * reps / dt / 1e9:.2f}GB/s"
    )


def _converging_param_stream(n_versions: int):
    """(leaves_per_version, cfg) from a REAL converging CartPole run:
    single-process IMPALA (rollout -> learner_step), host-fetched
    params after every step — the publish stream the distributed
    learner would put on the wire."""
    cfg = _cfg(1)
    programs = impala.make_impala(cfg)
    state = programs.init(jax.random.PRNGKey(0))
    rollout, env_reset = programs.make_actor_programs(0)
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    versions = []
    for _ in range(n_versions):
        key, k = jax.random.split(key)
        env_state, obs, carry, traj, _ = rollout(
            state.params, env_state, obs, carry, k
        )
        batch = impala.stack_trajectories([traj] * cfg.batch_trajectories)
        state, _ = programs.learner_step(state, batch)
        versions.append(
            [np.asarray(x) for x in jax.tree_util.tree_leaves(
                jax.device_get(state.params)
            )]
        )
    return versions, cfg


def _wire_fetch_bytes(versions, *, param_delta, param_bf16=False):
    """Replay the publish stream through a REAL LearnerServer +
    ActorClient pair (one fetch per publish, the actor steady state);
    returns (per-fetch param bytes, per-fetch wall seconds, leaves of
    the last fetch). Bytes come from the server's own outbound
    accounting — the same counter the codec win is logged with."""
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        ActorClient,
        LearnerServer,
        ROLE_ACTOR,
    )

    server = LearnerServer(
        lambda traj, ep: True,
        param_delta=param_delta,
        param_bf16=param_bf16,
        log=lambda m: None,
    )
    try:
        client = ActorClient(
            "127.0.0.1", server.port, hello=(0, 0, ROLE_ACTOR)
        )
        per_fetch, times = [], []
        last = None
        for leaves in versions:
            server.publish(leaves, notify=False)
            before = server.metrics()[
                metric_names.TRANSPORT + "param_mb_out"
            ]
            t0 = time.perf_counter()
            _, last = client.fetch_params()
            times.append(time.perf_counter() - t0)
            after = server.metrics()[
                metric_names.TRANSPORT + "param_mb_out"
            ]
            per_fetch.append((after - before) * 1e6)
        client.close()
        return per_fetch, times, last
    finally:
        server.close()


def params_leg(n_versions: int = 60):
    """Wire bytes per steady-state publish-fetch on a converging
    CartPole run: lossless XOR-delta + shuffle + zlib vs the full
    frame, split by training phase (early deltas churn more). Also
    verifies the delta stream decodes bit-exact at the end, and
    reports the opt-in bf16 wire variant."""
    versions, _ = _converging_param_stream(n_versions)
    full_b, _, _ = _wire_fetch_bytes(versions, param_delta=False)
    delta_b, _, last = _wire_fetch_bytes(versions, param_delta=True)
    for a, b in zip(last, versions[-1]):
        np.testing.assert_array_equal(a, b)  # lossless, end of stream
    bf16_b, _, _ = _wire_fetch_bytes(
        versions, param_delta=True, param_bf16=True
    )
    full = np.mean(full_b)

    def phase(xs):
        third = max(1, len(xs) // 3)
        return np.mean(xs[1:1 + third]), np.mean(xs[-third:])

    d_early, d_late = phase(delta_b)
    print(
        f"PARAM_WIRE full={full / 1024:.1f}KiB/fetch "
        f"delta={np.mean(delta_b[1:]) / 1024:.1f}KiB/fetch "
        f"({full / np.mean(delta_b[1:]):.2f}x) "
        f"early={d_early / 1024:.1f}KiB late={d_late / 1024:.1f}KiB "
        f"bf16+delta={np.mean(bf16_b[1:]) / 1024:.1f}KiB/fetch "
        f"({full / np.mean(bf16_b[1:]):.2f}x, opt-in lossy) "
        f"[n={n_versions}, fetch 0 is the full-frame bootstrap]",
        flush=True,
    )


def _notify_latencies(versions, n_publishes: int) -> list:
    """publish() -> fetch-complete latencies (seconds): one warm
    client holds v1 and sleeps on the KIND_PARAMS_NOTIFY broadcast
    while a publisher thread pushes the stream; each wake delta-
    fetches and the latency is publish-call to fetch-complete.
    Shared by ``notify_leg`` here and ``bench.py --measure-params``
    (single source of truth for the wait-loop/bookkeeping)."""
    import threading

    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        ActorClient,
        LearnerServer,
        ROLE_ACTOR,
    )

    server = LearnerServer(
        lambda traj, ep: True, param_delta=True, log=lambda m: None
    )
    try:
        server.publish(versions[0], notify=False)
        client = ActorClient(
            "127.0.0.1", server.port, hello=(0, 0, ROLE_ACTOR)
        )
        client.fetch_params()  # hold v1: steady-state delta fetches
        lat = []
        t_pub = {}
        done = threading.Event()

        def publisher():
            for i in range(n_publishes):
                time.sleep(0.02)
                t_pub[i + 2] = time.perf_counter()  # version = i + 2
                server.publish(versions[(i + 1) % len(versions)])
            done.set()

        t = threading.Thread(target=publisher, daemon=True)
        t.start()
        seen = 1
        while seen < n_publishes + 1:
            v = client.wait_params_notify(2.0)
            if v <= seen:
                if done.is_set():
                    break
                continue
            version, _ = client.fetch_params()
            lat.append(time.perf_counter() - t_pub[version])
            seen = version
        t.join(timeout=5.0)
        client.close()
        return lat
    finally:
        server.close()


def notify_leg(n_publishes: int = 50):
    """publish() -> actor-visible latency through KIND_PARAMS_NOTIFY:
    the client sleeps on the notify broadcast and delta-fetches on
    wake; measured from the publish call to fetch-complete. The
    pre-notify world paid up to a full rollout+push round before the
    piggybacked ack even revealed the version."""
    from actor_critic_algs_on_tensorflow_tpu.utils.metrics import (
        LatencyStats,
    )

    versions, _ = _converging_param_stream(8)
    stats = LatencyStats()
    for s in _notify_latencies(versions, n_publishes):
        stats.add_s(s)
    m = stats.summary()
    print(
        f"PARAM_NOTIFY publish->visible p50={m['p50_ms']:.2f}ms "
        f"p99={m['p99_ms']:.2f}ms max={m['max_ms']:.2f}ms "
        f"(notify wake + delta fetch, n={m['count']})",
        flush=True,
    )


if __name__ == "__main__":
    leg = sys.argv[1] if len(sys.argv) > 1 else "all"
    if leg in ("all", "checksum"):
        checksum_leg()
    if leg in ("all", "guard"):
        guard_fetch_leg()
    if leg in ("all", "params"):
        params_leg()
    if leg in ("all", "notify"):
        notify_leg()
    if leg in ("all", "warm"):
        g = failover_leg("warm")
        print(f"FAILOVER_WARM gap={g:.3f}s (kill -> first learner step)")
    if leg in ("all", "cold"):
        failover_leg("cold")  # prints COLD_FIRST_STEP from the child
    if leg in ("all", "election"):
        election_leg()
