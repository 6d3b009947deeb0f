"""Continuous-delivery promotion bench: eval-gated promotion latency
plus the poisoned-candidate drill, reported as the ``BENCH_PROMOTION``
ledger leg.

One leg, four acts on a REAL wire control plane (in-process threads,
production ``LearnerServer`` + ``InferenceServer`` + evaluator over
``KIND_CANDIDATE``/``KIND_VERDICT``):

  - ``latency``: a stream of good candidates flows submit -> canary
    stage -> evaluator poll -> signed PROMOTE -> fleet publish;
    ``promote_p50_ms``/``promote_p99_ms`` are the controller's
    submit-to-promote latencies (the headline numbers).
  - ``poison``: a candidate scoring far below the bar is staged while
    scripted live + canary lanes keep requesting; the gate must
    auto-reject it (``rejected_by_gate``) with ZERO reply gaps on
    either lane — ``canary_served_frac`` reports the canary share of
    the drill window's traffic (0.5 with one canary of two lanes).
  - ``rollback``: a bad candidate is force-promoted past the gate,
    then the one knob (``rollback(depose_live=True)``) returns the
    fleet to last-good under a single epoch bump
    (``rollback_epoch_bumps``); a late verdict from the deposed reign
    must land as a stale drop (``late_publish_fenced``).
  - ``kill``: a REAL evaluator subprocess is SIGKILLed mid-verdict
    (it polled the candidate, then died scoring it); the candidate
    must quarantine on timeout with serving still answering from the
    live params (``quarantined_on_kill``).

``cpu_limited`` flags hosts where the tiers timeshare too few cores
for the latency percentiles to mean anything (BENCH_SHARD discipline).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

B, D = 2, 3  # env rows per request / obs feature dim
LIVE_ID, CANARY_ID = 1, 2  # Knuth slots ~0.618 / ~0.236 (fraction 0.5)


def _cpu_budget() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def _leaves(value: float):
    return [np.full((64,), float(value), np.float32) for _ in range(2)]


def _pid_act(params, obs, key):
    obs = np.asarray(obs)
    return (
        np.full(obs.shape[0], int(params["pid"]), np.int32),
        np.full(obs.shape[0], 0.25, np.float32),
    )


def _request_leaves(t: int):
    from actor_critic_algs_on_tensorflow_tpu.distributed.serving import (
        N_STEP_LEAVES,
    )

    leaves = [np.full((B, D), float(t), np.float32)]
    leaves += [np.full((B,), float(t - 1), np.float32)] * N_STEP_LEAVES
    return leaves


def _drive(serving, peer, seq: int, *, timeout_s: float = 10.0):
    """One scripted request; returns the served action id (the pid)."""
    box = []
    done = threading.Event()

    def reply(arrays):
        box.append(arrays)
        done.set()
        return True

    serving.submit(peer, seq, _request_leaves(seq), False, reply)
    if not done.wait(timeout_s):
        raise TimeoutError(f"serving reply gap at seq {seq}")
    return int(box[0][0][0])


def promotion_leg(
    *,
    good_candidates: int = 8,
    verdict_timeout_s: float = 3.0,
) -> dict:
    import jax

    from actor_critic_algs_on_tensorflow_tpu.distributed.delivery import (
        DEPOSED,
        PENDING,
        QUARANTINED,
        REJECTED,
        DeliveryController,
        PolicyStore,
        run_evaluator,
        sign_verdict,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.serving import (
        N_STEP_LEAVES,
        InferenceServer,
    )
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        KIND_VERDICT,
        LearnerServer,
        PeerInfo,
    )

    secret = b"bench-delivery"
    server = LearnerServer(
        lambda t, e: True, host="127.0.0.1", log=lambda m: None
    )
    specs = [((B, D), np.dtype(np.float32))] + [
        ((B,), np.dtype(np.float32))
    ] * N_STEP_LEAVES
    serving = InferenceServer(
        _pid_act,
        None,
        obs_treedef=jax.tree_util.tree_structure(np.zeros(1)),
        request_specs=specs,
        rollout_length=3,
        batch_max=4,
        max_wait_s=0.01,
        sink=lambda t, e: True,
        seed=0,
        log=lambda m: None,
    )
    ctl = DeliveryController(
        PolicyStore(), server, serving=serving, secret=secret,
        canary_fraction=0.5, verdict_timeout_s=verdict_timeout_s,
        log=lambda m: None,
    )
    server.set_delivery_handler(ctl.handle)
    live_peer = PeerInfo(1, LIVE_ID, 0, 0)
    canary_peer = PeerInfo(2, CANARY_ID, 0, 0)
    seqs = {LIVE_ID: 0, CANARY_ID: 0}

    def drive(peer) -> int:
        seqs[peer.actor_id] += 1
        return _drive(serving, peer, seqs[peer.actor_id])

    def judge_next(meta) -> None:
        """Run one evaluator pass over the wire (exactly one verdict)
        and wait for the server thread to apply it — candidates are
        judged synchronously so every drill window is deterministic."""
        run_evaluator(
            "127.0.0.1", server.port,
            score_fn=lambda _m, leaves: float(
                np.asarray(leaves[0]).mean()
            ),
            bar=0.0, secret=secret, poll_interval_s=0.005,
            max_candidates=1, log=lambda m: None,
        )
        deadline = time.monotonic() + 30.0
        while meta.status == PENDING:  # the verdict frame is one-way
            if time.monotonic() > deadline:
                raise TimeoutError("verdict never applied")
            time.sleep(0.002)

    out: dict = {}
    try:
        # -- latency: good candidates promote through the full wire --
        ctl.submit(_leaves(1.0), step=0, tree={"pid": 0})  # bootstrap
        for i in range(good_candidates):
            meta = ctl.submit(
                _leaves(1.0 + i), step=i + 1, tree={"pid": i + 1}
            )
            judge_next(meta)

        # -- poison: auto-reject under live canary traffic ------------
        base = serving.metrics()
        bad = ctl.submit(
            _leaves(-99.0), step=100, tree={"pid": 66}
        )
        served_canary_pids = set()
        for _ in range(10):
            # Both lanes keep getting answers THROUGHOUT the verdict
            # window — a reply gap raises out of the leg.
            drive(live_peer)
            served_canary_pids.add(drive(canary_peer))
        judge_next(bad)
        assert bad.status == REJECTED, bad.status
        # The canary lane actually exercised the candidate.
        poisoned_canary_served = 66 in served_canary_pids
        # ...and is back on live params after the reject.
        restored = drive(canary_peer) != 66 and drive(live_peer) != 66
        m = serving.metrics()
        window_requests = m["serve_requests"] - base["serve_requests"]
        window_canary = (
            m["serve_canary_requests"] - base["serve_canary_requests"]
        )
        canary_served_frac = window_canary / max(1, window_requests)

        # -- rollback: one knob after a slipped bad promotion ---------
        slipped = ctl.submit(_leaves(50.0), step=200, tree={"pid": 77})
        judge_next(slipped)  # mean 50 >= bar: it slips the gate
        epoch_before = int(server.epoch)
        ctl.rollback(depose_live=True)
        rollback_epoch_bumps = int(server.epoch) - epoch_before
        rolled_back = drive(live_peer) != 77 and drive(canary_peer) != 77
        # A late verdict from the deposed reign must be fenced.
        stale_before = ctl.metrics()["delivery_stale_verdicts"]
        sig = sign_verdict(
            secret, slipped.version, slipped.step, slipped.epoch,
            True, 50.0,
        )
        ctl.handle(
            None, KIND_VERDICT, 0,
            [
                np.asarray(
                    [slipped.version, 1, slipped.epoch, slipped.step],
                    np.int64,
                ),
                np.asarray([50.0, 0.0], np.float64),
                sig,
            ],
            None,
        )
        late_publish_fenced = (
            slipped.status == DEPOSED
            and ctl.metrics()["delivery_stale_verdicts"] == stale_before + 1
        )

        # -- kill: SIGKILL a real evaluator process mid-verdict -------
        polls_before = server.metrics()["transport_candidate_polls"]
        doomed = ctl.submit(_leaves(7.0), step=300, tree={"pid": 88})
        code = (
            "import sys, time; sys.path.insert(0, {root!r})\n"
            "from actor_critic_algs_on_tensorflow_tpu.distributed."
            "delivery import run_evaluator\n"
            "run_evaluator('127.0.0.1', {port}, "
            "score_fn=lambda m, l: time.sleep(600) or 0.0, "
            "bar=0.0, secret={secret!r}, poll_interval_s=0.01, "
            "log=lambda m: None)\n"
        ).format(
            root=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            ),
            port=server.port,
            secret=secret,
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        try:
            deadline = time.monotonic() + 30.0
            while (
                server.metrics()["transport_candidate_polls"]
                <= polls_before
            ):
                if time.monotonic() > deadline:
                    raise TimeoutError("evaluator never polled")
                time.sleep(0.02)
            # It holds the candidate and is deep in score_fn: kill it.
            proc.send_signal(signal.SIGKILL)
            proc.wait(10.0)
        finally:
            if proc.poll() is None:
                proc.kill()
        deadline = time.monotonic() + verdict_timeout_s + 30.0
        while doomed.status == PENDING:
            ctl.check_timeouts()
            if time.monotonic() > deadline:
                break
            time.sleep(0.05)
        quarantined_on_kill = (
            doomed.status == QUARANTINED
            # ...with serving untouched by the whole affair.
            and drive(live_peer) != 88
            and drive(canary_peer) != 88
        )

        dm = ctl.metrics()
        out = {
            "promote_p50_ms": float(dm["promo_p50_ms"]),
            "promote_p99_ms": float(dm["promo_p99_ms"]),
            "rejected_by_gate": int(dm["delivery_rejections"]),
            "canary_served_frac": round(float(canary_served_frac), 4),
            "rollback_epoch_bumps": int(rollback_epoch_bumps),
            "late_publish_fenced": bool(late_publish_fenced),
            "quarantined_on_kill": bool(quarantined_on_kill),
            # Witness detail (not schema-required, key-stable):
            "promotions": int(dm["delivery_promotions"]),
            "poison_canary_served": bool(poisoned_canary_served),
            "lanes_restored_after_reject": bool(restored),
            "lanes_restored_after_rollback": bool(rolled_back),
            "drill_window_requests": int(window_requests),
        }
    finally:
        serving.close()
        server.close()
    return out


def bench(*, leg_kwargs=None) -> dict:
    """The BENCH_PROMOTION payload (key set pinned by
    ``analysis/bench_schema.py:PROMOTION_REQUIRED``)."""
    out = promotion_leg(**(leg_kwargs or {}))
    # Learner, serving, evaluator, and the driver timeshare the host;
    # under ~4 cores the promote percentiles measure the scheduler.
    out["cpu_limited"] = _cpu_budget() < 4
    return out


def main() -> int:
    import json

    print(json.dumps(bench(), indent=1))
    return 0


if __name__ == "__main__":
    import jax

    # A host-side drill: stay off the chip whatever the environment says.
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    sys.exit(main())
