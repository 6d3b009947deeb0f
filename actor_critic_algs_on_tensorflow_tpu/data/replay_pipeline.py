"""Learner-side replay pipeline: prefetched prioritized draws,
overlapped device transfer, coalesced asynchronous priority write-back.

The serial off-policy hot loop pays, per update: one blocking
round-robin ``group.sample()`` RPC, a host->device transfer, the jitted
update, a synchronous ``np.asarray(td)`` device fetch, and one
``KIND_PRIO_UPDATE`` frame — strictly one after another. Ape-X (Horgan
et al. 2018) decouples exactly these: sampling, learning, and priority
write-back proceed concurrently. This module applies the PR-2
``LearnerPipeline`` overlap discipline to the replay tier:

1.  **Prefetched draws** — a bounded window (``depth``) of in-flight
    prioritized draws across ALL live shards concurrently: one worker
    thread per shard issues ``group.sample_shard(k, ...)``, so one slow
    or refilling shard no longer serializes the rotation. The pacing
    gate is honored at *issue* time (``pace(outstanding)``): a
    warming-up or paced-out learner never makes a shard serve (and
    ship) a batch the learner would discard — issued draws are capped
    so every one of them is consumed by a real update.

2.  **Staged transfer** — sample replies decode straight into a
    double-buffered ``HostArena`` slot (no per-draw allocation) and
    ``device_put`` of batch N+1 runs under batch N's update compute.
    Slot reuse is TOKEN-GATED on the consuming update: the worker
    blocks on the update's metrics (a jit output that is never
    donated) before rewriting a slot, because a CPU-backend
    ``device_put`` may alias the slot's host memory zero-copy — the
    PR-6 aliasing discipline.

3.  **Async write-back** — the TD fetch rides a one-step-delayed
    token: ``write_back(batch_N, td_N)`` materializes ``td_{N-1}``
    (whose compute retired behind update N's dispatch) instead of
    barriering on its own update. Per-shard priorities are COALESCED
    into ONE multi-entry ``KIND_PRIO_UPDATE`` frame per shard per
    flush tick; one frame carries one epoch tag, so the shard fences
    the whole tick's write-backs with a single reign decision, and
    stale-id drops make the added staleness (bounded by
    ``depth + 1`` updates) safe.

**Lockstep mode** (``depth <= 1`` and ``coalesce=False``) reproduces
the serial loop BIT-IDENTICALLY at a fixed seed: a single prefetch
thread draws through the serial rotation (``group.sample``), and the
next draw is gated on the previous batch's *synchronous* write-back —
so every sum-tree descent sees exactly the priorities the serial loop
would have seen. The pinning test drives both loops against preloaded
shards and compares params bitwise.

**Failover** — an in-flight draw against a dying shard is aborted by
``group.interrupt(k)`` (the supervisor calls it before respawning);
the worker sees ``OperationInterrupted``, counts a reissue, and draws
again once the respawn serves. The aborted draw produced no reply, so
the meter reconciliation never saw it — nothing is double-counted. A
takeover drain is ``close(flush=False)``: abort every in-flight draw
without goodbye frames, so the tier stays up for the next reign.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from actor_critic_algs_on_tensorflow_tpu.data.pipeline import HostArena
from actor_critic_algs_on_tensorflow_tpu.utils.metric_names import (
    REPLAY_PIPELINE,
)
from actor_critic_algs_on_tensorflow_tpu.utils.metrics import TimeSplit


class PrefetchedBatch:
    """One staged draw as the learner consumes it: device-resident
    leaves + weights, the wire-side draw (ids/indices/shard for the
    write-back), and the arena slot pinned until ``mark_consumed``."""

    __slots__ = ("leaves", "weights", "sampled", "slot")

    def __init__(self, leaves, weights, sampled, slot):
        self.leaves = leaves
        self.weights = weights
        self.sampled = sampled
        self.slot = slot


class ReplayPipeline:
    """Bounded prefetch window over a ``ReplayClientGroup``.

    ``pace(outstanding)`` is the issue-time gate: called with the
    number of draws issued but not yet consumed, it answers whether
    ONE MORE draw would still be consumed by a paced update (the
    runner's closure folds in warmup and the update-ratio target).
    ``validate`` is the runner's batch-layout check; a failing batch
    is counted in ``rejects`` and never staged.
    """

    def __init__(
        self,
        group,
        *,
        batch_size: int,
        beta: float,
        pace: Callable[[int], bool],
        depth: int = 2,
        coalesce: bool = True,
        device: Any = None,
        validate: Optional[Callable[[Sequence[np.ndarray]], bool]] = None,
        part_specs: Optional[Sequence[Tuple[tuple, Any]]] = None,
        poll_interval_s: float = 0.002,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._group = group
        self._batch_size = int(batch_size)
        self._beta = float(beta)
        self._pace = pace
        self.depth = int(depth)
        self._coalesce = bool(coalesce)
        self._device = device
        self._validate = validate
        self._poll_s = float(poll_interval_s)
        # Lockstep = the bit-identity shape: serial rotation, one draw
        # in flight, next draw gated on the previous SYNC write-back.
        self._lockstep = self.depth <= 1 and not self._coalesce

        # depth ready/in-flight batches + 1 pinned by the in-flight
        # update; weights ride as one extra leaf so the whole batch is
        # a single slot write.
        n_leaves = None
        specs = None
        if part_specs is not None:
            specs = [
                (tuple(s), np.dtype(d)) for s, d in part_specs
            ] + [((self._batch_size,), np.dtype(np.float32))]
            n_leaves = len(specs)
        self._n_leaves = n_leaves
        self._arena_specs = specs
        self._arena: Optional[HostArena] = None
        if specs is not None:
            self._arena = HostArena(
                [0] * len(specs), 1, self.depth + 1, part_specs=specs
            )

        self._lock = threading.Lock()
        self._closed = threading.Event()
        self._outstanding = 0          # issued, not yet consumed
        self._drawn = 0                # staged batches (lockstep gate)
        self._wb_done = 0              # sync write-backs landed
        self._ready: "queue.Queue[PrefetchedBatch]" = queue.Queue()
        # (slot, token): token = the consuming update's metrics dict,
        # blocked on before the slot is rewritten. None = never used.
        self._free: "queue.Queue[Tuple[int, Any]]" = queue.Queue()
        for i in range(self.depth + 1):
            self._free.put((i, None))

        # Coalesced write-back state (runner thread only).
        self._pending_wb: "collections.deque" = collections.deque()
        self._prio_buf: Dict[int, List[Tuple[Any, Any, Any]]] = {}

        self._ts = TimeSplit(REPLAY_PIPELINE)
        self.batches = 0
        self.rejects = 0
        self.reissues = 0
        self.prio_frames = 0
        self.prio_entries = 0
        self.prio_frames_coalesced = 0
        self._t_start = time.perf_counter()

        self._threads: List[threading.Thread] = []
        if self._lockstep:
            self._threads.append(threading.Thread(
                target=self._run_lockstep,
                name="replay-prefetch",
                daemon=True,
            ))
        else:
            for k in range(len(group)):
                self._threads.append(threading.Thread(
                    target=self._run_shard,
                    args=(k,),
                    name=f"replay-prefetch-{k}",
                    daemon=True,
                ))
        for t in self._threads:
            t.start()

    # -- issue-side gate ------------------------------------------------

    def _try_issue(self) -> bool:
        """Atomically pass the pacing gate and claim an issue credit.
        Two workers racing the last credit must not both issue — the
        check and the increment share the lock, and ``pace`` only ever
        gets MORE permissive as ingest grows, so a claim that passed
        stays valid."""
        with self._lock:
            if self._lockstep and self._wb_done < self._drawn:
                # The previous batch's priorities have not landed: a
                # draw now would descend a sum tree the serial loop
                # would already have updated.
                return False
            if self._outstanding >= self.depth:
                return False
            if not self._pace(self._outstanding):
                return False
            self._outstanding += 1
            return True

    def _unissue(self) -> None:
        with self._lock:
            self._outstanding -= 1

    # -- worker threads -------------------------------------------------

    def _run_lockstep(self) -> None:
        while not self._closed.is_set():
            if not self._try_issue():
                time.sleep(self._poll_s)
                continue
            try:
                with self._ts.span("sample_wait_s"):
                    sampled = self._group.sample(
                        self._batch_size, self._beta
                    )
            except Exception:
                self._unissue()
                if self._closed.is_set():
                    return
                self.reissues += 1
                time.sleep(self._poll_s)
                continue
            if sampled is None:
                self._unissue()
                time.sleep(self._poll_s)
                continue
            if not self._stage(sampled):
                self._unissue()

    def _run_shard(self, shard_idx: int) -> None:
        while not self._closed.is_set():
            if not self._try_issue():
                time.sleep(self._poll_s)
                continue
            try:
                with self._ts.span("sample_wait_s"):
                    sampled = self._group.sample_shard(
                        shard_idx, self._batch_size, self._beta
                    )
            except (ConnectionError, OSError):
                # Dead shard, or a deliberate interrupt (failover /
                # takeover drain): drop the draw and reissue after the
                # respawn serves. The draw produced no reply, so no
                # meter ever counted it.
                self._unissue()
                if self._closed.is_set():
                    return
                self.reissues += 1
                time.sleep(self._poll_s)
                continue
            if sampled is None:
                self._unissue()         # refilling: no batch to consume
                time.sleep(self._poll_s)
                continue
            if not self._stage(sampled):
                self._unissue()

    def _stage(self, sampled) -> bool:
        """Decode a draw into a free arena slot and transfer it.
        Returns False when the batch was rejected (layout) — the
        caller releases the issue credit."""
        leaves = list(sampled.leaves)
        if self._validate is not None and not self._validate(leaves):
            self.rejects += 1
            return False
        # jax import is deferred so the module stays importable from
        # check.py / bench subprocesses that never touch a device.
        import jax

        with self._ts.span("slot_wait_s"):
            while True:
                try:
                    slot, token = self._free.get(timeout=0.1)
                    break
                except queue.Empty:
                    if self._closed.is_set():
                        return False
            if token is not None:
                # The consuming update has this slot's buffers aliased
                # (CPU zero-copy device_put): its retirement is the
                # ONLY safe point to rewrite them.
                jax.block_until_ready(token)

        part = leaves + [np.asarray(sampled.weights, np.float32)]
        with self._ts.span("assemble_s"):
            arena = self._arena
            if arena is None:
                with self._lock:
                    if self._arena is None:
                        self._arena = HostArena(
                            [0] * len(part), 1, self.depth + 1
                        )
                    arena = self._arena
            try:
                arena.write_part(slot, 0, part)
            except ValueError:
                # Off-layout batch a caller-supplied validator did not
                # catch (or none was given): the arena's
                # first-layout-wins pin rejects it. The slot was never
                # corrupted past this batch — recycle it.
                self.rejects += 1
                self._free.put((slot, None))
                return False
            host = arena.slot_leaves(slot)

        with self._ts.span("transfer_s"):
            dev = [jax.device_put(x, self._device) for x in host]
            jax.block_until_ready(dev)

        with self._lock:
            self._drawn += 1
        self.batches += 1
        self._ready.put(
            PrefetchedBatch(dev[:-1], dev[-1], sampled, slot)
        )
        return True

    # -- consumer side (runner thread) ----------------------------------

    def get(self, timeout: float = 0.1) -> Optional[PrefetchedBatch]:
        """Next staged batch, or None after ``timeout`` with nothing
        ready (the runner breaks its burst and takes the idle path).
        The issue credit stays held until ``mark_consumed`` — the
        runner bumps its update counter first, so a worker's pacing
        check can never see the credit freed while the update it paid
        for is still uncounted (which would let one draw slip past
        the paced target and be discarded)."""
        with self._ts.span("stall_s"):
            try:
                return self._ready.get(timeout=timeout)
            except queue.Empty:
                return None

    def mark_consumed(self, pb: PrefetchedBatch, token: Any) -> None:
        """Release ``pb``'s issue credit and return its slot to the
        free pool, reuse gated on ``token`` — the consuming update's
        (never-donated) metrics output; a worker blocks on it before
        rewriting the slot. Call AFTER counting the update: the jit
        dispatch is async, so the freed credit still overlaps the
        update's compute."""
        self._unissue()
        self._free.put((pb.slot, token))

    def write_back(self, sampled, td) -> None:
        """Priority write-back for one consumed batch.

        Sync mode (``coalesce=False``): materialize ``td`` NOW (the
        serial barrier) and send the single-entry frame — this is the
        bit-identity shape. Coalesce mode: hold ``td`` as a device
        token; the PREVIOUS update's token (one step delayed, its
        compute already retired behind this update's dispatch) is
        materialized and buffered per shard for ``flush_priorities``.
        """
        if not self._coalesce:
            self._group.update_priorities(
                sampled.shard_idx,
                sampled.ids,
                sampled.indices,
                np.asarray(td),
            )
            self.prio_frames += 1
            self.prio_entries += int(np.shape(sampled.ids)[0])
            with self._lock:
                self._wb_done += 1
            return
        self._pending_wb.append((sampled, td))
        while len(self._pending_wb) > 1:
            sb, tok = self._pending_wb.popleft()
            self._buffer_prio(sb, np.asarray(tok))

    def _buffer_prio(self, sampled, td_host: np.ndarray) -> None:
        self._prio_buf.setdefault(sampled.shard_idx, []).append(
            (sampled.ids, sampled.indices, td_host)
        )

    def flush_priorities(self) -> None:
        """Drain every held TD token and send ONE coalesced
        ``KIND_PRIO_UPDATE`` frame per shard. The runner calls this at
        burst boundaries (before publishing params), bounding
        priority staleness to one burst + the one-step token delay."""
        while self._pending_wb:
            sb, tok = self._pending_wb.popleft()
            self._buffer_prio(sb, np.asarray(tok))
        for shard_idx, entries in self._prio_buf.items():
            if not entries:
                continue
            self._group.update_priorities_multi(shard_idx, entries)
            self.prio_frames += 1
            self.prio_entries += sum(
                int(np.shape(ids)[0]) for ids, _, _ in entries
            )
            if len(entries) > 1:
                self.prio_frames_coalesced += 1
        self._prio_buf.clear()

    # -- observability --------------------------------------------------

    def metrics(self) -> Dict[str, float]:
        out = self._ts.window()
        cum = self._ts.cumulative()
        with self._lock:
            inflight = self._outstanding
        out[REPLAY_PIPELINE + "batches"] = self.batches
        out[REPLAY_PIPELINE + "depth"] = self.depth
        out[REPLAY_PIPELINE + "inflight"] = inflight
        out[REPLAY_PIPELINE + "rejects"] = self.rejects
        out[REPLAY_PIPELINE + "reissues"] = self.reissues
        out[REPLAY_PIPELINE + "prio_frames"] = self.prio_frames
        out[REPLAY_PIPELINE + "prio_entries"] = self.prio_entries
        out[REPLAY_PIPELINE + "prio_frames_coalesced"] = (
            self.prio_frames_coalesced
        )
        # Overlap: the share of staging work (assemble + transfer)
        # hidden behind update compute — 1.0 means the learner never
        # waited on an empty pipeline (same derivation as the
        # on-policy ingest path's pipeline_overlap_frac).
        ingest = cum.get(REPLAY_PIPELINE + "assemble_s", 0.0) + cum.get(
            REPLAY_PIPELINE + "transfer_s", 0.0
        )
        stall = cum.get(REPLAY_PIPELINE + "stall_s", 0.0)
        if ingest > 0:
            out[REPLAY_PIPELINE + "overlap_frac"] = round(
                max(0.0, 1.0 - stall / ingest), 4
            )
        wall = time.perf_counter() - self._t_start
        if wall > 0:
            out[REPLAY_PIPELINE + "sample_wait_share"] = round(
                stall / wall, 4
            )
        return out

    # -- teardown -------------------------------------------------------

    def close(self, flush: bool = False) -> None:
        """Stop the prefetchers. ``flush=True`` is the orderly exit:
        held TD tokens drain into final coalesced frames FIRST (the
        shards are alive to apply them). ``flush=False`` is the
        takeover/failure drain: in-flight draws are ABORTED via the
        group's interrupt (no goodbye frames — the tier stays up for
        the next reign) and buffered priorities are dropped; stale
        priorities age out shard-side by design."""
        self._closed.set()
        if flush:
            try:
                self.flush_priorities()
            except Exception:
                pass
        else:
            self._pending_wb.clear()
            self._prio_buf.clear()
        try:
            self._group.interrupt()
        except Exception:
            pass
        for t in self._threads:
            t.join(timeout=5.0)
        # Unpin anything still staged so gc can reclaim the arena.
        while True:
            try:
                self._ready.get_nowait()
            except queue.Empty:
                break
