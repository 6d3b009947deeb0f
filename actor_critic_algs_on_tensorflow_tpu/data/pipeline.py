"""Device-feed pipeline: overlap batch ingest with learner compute.

The IMPALA learner is the single consumer for every actor, and its
serial loop (drain queue -> host-assemble batch -> dispatch
``learner_step``) leaves the accelerator idle for the whole host-side
assemble + host->device transfer of every batch. This module hides
that work under the previous step's compute:

  - ``HostArena`` — a preallocated, reusable host buffer set: ONE
    contiguous numpy buffer per batch leaf per slot, filled with
    indexed writes (no N-way ``concatenate``, no per-batch
    allocation). Two slots double-buffer: the next batch is assembled
    while the previous one is still in flight.
  - ``LearnerPipeline`` — a background prefetch thread that drains the
    trajectory source, assembles the NEXT batch into an arena slot,
    issues ``jax.device_put`` with the learner's ``NamedSharding`` so
    the transfer rides under the current ``learner_step``, and hands
    the device-resident batch to the learner through a depth-1 queue.
    Slot reuse is token-gated: a slot is rewritten only after BOTH its
    transfer completed AND the learner step that consumed the batch
    retired (``mark_consumed``) — an arena slot can never alias a
    batch still in flight, even when the device batch is donated.
  - ``AsyncParamPublisher`` — parameter broadcast off the critical
    path: the learner submits a weights reference (newest wins) and a
    side thread performs the blocking device->host fetch + publish.

Run-ahead is bounded (1 ready batch + 1 being assembled), so the
pipeline adds at most 2 batches of off-policy lag on top of the
trajectory queue — still inside what V-trace's rho/c clipping
corrects.

Trajectory leaves arriving as numpy (the cross-process/DCN mode) take
the arena path; leaves already device-resident (in-process actor
threads) are stacked on device instead — re-staging them through the
host would add two copies, not remove one.

Coded wire trajectories (``distributed.codec.CodedTrajectory`` — the
trajectory codec's compressed frames, PR 6) ride the queue STILL
COMPRESSED and are decoded by the prefetch thread DIRECTLY into the
arena part views (``HostArena.part_views``): the slot is the decode
destination, so no assembled trajectory ever exists outside the arena
and the queue holds ~10x fewer bytes for image observations. A part
whose decode fails or whose post-decode validation rejects it is
simply overwritten by the next polled item (torn-slot safety).
"""

from __future__ import annotations

import queue as queue_lib
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from actor_critic_algs_on_tensorflow_tpu.distributed.codec import (
    CodecError,
    CodedTrajectory,
)
from actor_critic_algs_on_tensorflow_tpu.utils import metric_names, profiling
from actor_critic_algs_on_tensorflow_tpu.utils.metrics import TimeSplit

__all__ = [
    "AsyncParamPublisher",
    "DeviceRolloutSource",
    "HostArena",
    "InterleavedSource",
    "LearnerPipeline",
    "TimeSplit",
]

# Batch-source interface (what the learner loop consumes, and what
# anything that feeds it must implement):
#
#     got = source.get(stop=stop_event)      # None once stop fires
#     batch, eps, handle = got
#     state, metrics = learner_step(state, batch)
#     source.mark_consumed(handle, metrics)  # token-gated slot reuse
#     ...
#     source.metrics(); source.close()
#
# ``LearnerPipeline`` (wire trajectories through the host arena),
# ``distributed.sharding.ShardedIngest`` (N pipelines stitched into one
# global batch), ``DeviceRolloutSource`` (device-resident self-play —
# the batch never touches the host), and ``InterleavedSource`` (a
# deterministic schedule over two sources) all speak it.


class HostArena:
    """Preallocated host-side batch buffers: ``n_slots`` independent
    copies of the stacked-batch leaf set, each leaf ONE contiguous
    numpy buffer written with indexed slice assignment.

    ``axes[i]`` is the concatenation axis of flat leaf ``i`` (1 for
    time-major ``[T, B]`` trajectory fields, 0 for per-env fields like
    ``last_obs``); ``n_parts`` trajectories of identical shape fill a
    slot. Shapes/dtypes come from the first trajectory seen.
    """

    def __init__(
        self,
        axes: Sequence[int],
        n_parts: int,
        n_slots: int = 2,
        *,
        part_specs: Optional[Sequence[Tuple[tuple, Any]]] = None,
    ):
        if n_slots < 2:
            raise ValueError(f"need >= 2 slots to double-buffer, got {n_slots}")
        self.axes = list(axes)
        self.n_parts = n_parts
        self.n_slots = n_slots
        self._slots: List[Optional[List[np.ndarray]]] = [None] * n_slots
        self._part_shapes: Optional[List[tuple]] = None
        self._part_dtypes: Optional[List[np.dtype]] = None
        if part_specs is not None:
            # Seed the layout from a TRUSTED local source (the wire
            # plan's eval_shape trace) rather than the first frame off
            # the wire: a stale-config actor whose frame happens to
            # land first must be the one rejected, not the one that
            # defines the layout every later legitimate frame is
            # judged against.
            if len(part_specs) != len(self.axes):
                raise ValueError(
                    f"{len(part_specs)} part specs for "
                    f"{len(self.axes)} leaves"
                )
            self._part_shapes = [tuple(s) for s, _ in part_specs]
            self._part_dtypes = [np.dtype(d) for _, d in part_specs]

    def ensure_slot(
        self,
        slot: int,
        part_shapes: Sequence[tuple],
        part_dtypes: Sequence[np.dtype],
    ) -> List[np.ndarray]:
        """Allocate slot ``slot``'s buffers from explicit per-leaf
        layout (shapes/dtypes of ONE trajectory part) — the entry point
        for ingest paths that know the layout before any decoded leaf
        exists (a coded frame's meta, or the wire plan's eval_shape
        trace)."""
        if len(part_shapes) != len(self.axes):
            raise ValueError(
                f"trajectory has {len(part_shapes)} leaves, arena "
                f"expects {len(self.axes)}"
            )
        shapes = [tuple(s) for s in part_shapes]
        dtypes = [np.dtype(d) for d in part_dtypes]
        if self._part_shapes is None:
            self._part_shapes = shapes
            self._part_dtypes = dtypes
        elif shapes != self._part_shapes or dtypes != self._part_dtypes:
            # The FIRST layout seen is the arena's layout for life; a
            # later frame claiming a different one (corrupt meta, an
            # actor on a stale config) must be dropped, never allowed
            # to poison the established buffers or livelock every
            # subsequent legitimate frame.
            raise ValueError(
                f"trajectory leaf layout {shapes} != arena part "
                f"layout {self._part_shapes} (all actors must share "
                f"one config)"
            )
        bufs = self._slots[slot]
        if bufs is None:
            bufs = []
            for s, dt, ax in zip(shapes, dtypes, self.axes):
                shape = list(s)
                shape[ax] *= self.n_parts
                bufs.append(np.empty(shape, dtype=dt))
            self._slots[slot] = bufs
        return bufs

    def _ensure(self, slot: int, leaves: Sequence[np.ndarray]) -> List[np.ndarray]:
        return self.ensure_slot(
            slot,
            [tuple(np.shape(x)) for x in leaves],
            [np.asarray(x).dtype for x in leaves],
        )

    def part_views(self, slot: int, part: int) -> List[np.ndarray]:
        """Per-leaf DESTINATION views of part ``part`` in slot ``slot``
        (each shaped exactly like one trajectory leaf; strided along
        the concat axis). These are what the trajectory codec decodes
        INTO — the slot is the destination, so a decoded wire batch
        never exists anywhere but the arena."""
        bufs = self._slots[slot]
        assert bufs is not None and self._part_shapes is not None, (
            "slot never allocated"
        )
        views = []
        for buf, ax, pshape in zip(bufs, self.axes, self._part_shapes):
            w = pshape[ax]
            sl = [slice(None)] * len(pshape)
            sl[ax] = slice(part * w, (part + 1) * w)
            views.append(buf[tuple(sl)])
        return views

    def write_part(
        self, slot: int, part: int, leaves: Sequence[np.ndarray]
    ) -> None:
        """Scatter one trajectory's leaves into slot ``slot`` at part
        index ``part`` — a strided write per leaf, no concatenation."""
        bufs = self._ensure(slot, leaves)
        for buf, x, ax, pshape in zip(
            bufs, leaves, self.axes, self._part_shapes
        ):
            x = np.asarray(x)
            if x.shape != pshape:
                raise ValueError(
                    f"trajectory leaf shape {x.shape} != arena part "
                    f"shape {pshape} (all actors must share one config)"
                )
            w = x.shape[ax]
            sl = [slice(None)] * x.ndim
            sl[ax] = slice(part * w, (part + 1) * w)
            buf[tuple(sl)] = x

    def slot_leaves(self, slot: int) -> List[np.ndarray]:
        bufs = self._slots[slot]
        assert bufs is not None, "slot never written"
        return bufs


class LearnerPipeline:
    """Background prefetch: assemble the next batch while the current
    ``learner_step`` executes.

    ``poll(n)`` (caller-supplied) returns up to ``n`` ``(traj, ep)``
    items, or an empty list on timeout — it is where the caller runs
    health checks; exceptions it raises abort the pipeline and
    re-raise from ``get()``. ``assemble_device(parts)`` stacks
    device-resident trajectories (the in-process path);
    ``shardings``/``axes`` drive the arena + sharded ``device_put``
    path for numpy trajectories (the wire path). ``validate(traj, ep)``
    (optional — the training-health sentinel's pre-arena quarantine)
    filters each polled trajectory BEFORE it joins a batch; rejected
    items are simply skipped (the validator records them).

    Contract with the consumer::

        batch, eps, handle = pipeline.get()
        state, metrics = learner_step(state, batch)   # may donate batch
        pipeline.mark_consumed(handle, metrics)

    ``mark_consumed``'s token gates arena-slot reuse: the prefetch
    thread blocks on the token's readiness before rewriting the slot,
    so donation can recycle the device buffers without the host arena
    ever aliasing a batch still in flight. The token must be an output
    of the consuming step (its readiness implies the step retired) —
    the metrics pytree is ideal; it is never donated.
    """

    def __init__(
        self,
        *,
        poll: Callable[[int], Sequence[Tuple[Any, Any]]],
        batch_parts: int,
        treedef: Any = None,
        axes_leaves: Optional[Sequence[int]] = None,
        shardings_leaves: Optional[Sequence[Any]] = None,
        assemble_device: Optional[Callable[[List[Any]], Any]] = None,
        n_slots: int = 2,
        exec_lock: Optional[threading.Lock] = None,
        validate: Optional[Callable[[Any, Any], bool]] = None,
        validate_coded: Optional[Callable[[Any, Any, int], bool]] = None,
        max_decode_bytes: int = 1 << 30,
        part_specs: Optional[Sequence[Tuple[tuple, Any]]] = None,
        transfer: Optional[Callable[[Sequence[np.ndarray]], Any]] = None,
        wrap_batch: bool = True,
        name: str = "learner-pipeline",
    ):
        self._poll = poll
        self._validate = validate
        # Post-decode validation for coded wire trajectories: they
        # arrive compressed, so the poison check can only run once the
        # leaves exist — which is the moment they land in the arena
        # slot. Signature: (traj_tree, ep, source_actor_id) -> bool; a
        # rejected part's slot space is simply reused by the next
        # polled item.
        self._validate_coded = validate_coded
        self._max_decode_bytes = max_decode_bytes
        # Sharded-learner hooks (distributed.sharding): ``transfer``
        # replaces the whole-buffer sharded ``device_put`` with a
        # shard-aware placement — per-device chunks of THIS shard's
        # device slice (in-process shards), or a process-local wrap
        # into the global multi-host batch. ``wrap_batch=False`` hands
        # the consumer the raw transferred leaves instead of the
        # unflattened pytree (the in-process stitcher combines N
        # shards' leaves BEFORE the tree exists).
        self._transfer = transfer
        self._wrap_batch = wrap_batch
        self._batch_parts = batch_parts
        self._treedef = treedef
        self._axes = axes_leaves
        self._shardings = shardings_leaves
        self._assemble_device = assemble_device
        self._exec_lock = exec_lock
        self._arena = (
            HostArena(
                axes_leaves, batch_parts, n_slots, part_specs=part_specs
            )
            if axes_leaves is not None
            else None
        )
        self._n_slots = n_slots
        # Slot-reuse tokens: slot k is rewritable once the token from
        # the step that consumed its previous batch is device-ready.
        self._tokens: List["queue_lib.Queue[Any]"] = [
            queue_lib.Queue(1) for _ in range(n_slots)
        ]
        for tq in self._tokens:
            tq.put(None)  # first use of each slot never blocks
        self._ready: "queue_lib.Queue[tuple]" = queue_lib.Queue(1)
        self._closed = threading.Event()
        self._error: Optional[BaseException] = None
        self.split = TimeSplit()
        self.batches = 0
        # Trajectory-codec decode accounting (the receive side of the
        # inbound wire ledger: coded bytes in vs decoded bytes out).
        self.coded_parts = 0
        self.decode_errors = 0
        self.decode_rejects = 0
        self.traj_coded_bytes = 0
        self.traj_decoded_bytes = 0
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    # -- prefetch thread ------------------------------------------------

    def _filtered_poll(self, n: int) -> List[Tuple[Any, Any]]:
        """Poll up to ``n`` items, applying the pre-arena validation
        hook to DECODED trajectories. Coded wire trajectories pass
        through unvalidated here — their leaves do not exist yet; the
        post-decode hook runs once they land in the slot."""
        out = []
        for traj, ep in self._poll(n):
            if self._validate is not None and not isinstance(
                traj, CodedTrajectory
            ):
                with profiling.span(profiling.VALIDATE_BATCH):
                    if not self._validate(traj, ep):
                        continue
            out.append((traj, ep))
        return out

    def _run(self) -> None:
        slot = 0
        # Polled-but-not-yet-placed items: the arena path places parts
        # incrementally (a rejected decode reuses its part index), so
        # anything over-polled carries into the next batch.
        pending: List[Tuple[Any, Any]] = []
        try:
            while not self._closed.is_set():
                with self.split.span("queue_wait_s"):
                    while not pending:
                        if self._closed.is_set():
                            return
                        pending.extend(
                            self._filtered_poll(self._batch_parts)
                        )

                first = pending[0][0]
                use_arena = self._arena is not None and (
                    isinstance(first, CodedTrajectory)
                    or all(
                        isinstance(x, np.ndarray)
                        for x in jax.tree_util.tree_leaves(first)
                    )
                )
                if use_arena:
                    item = self._assemble_arena(pending, slot)
                    slot = (slot + 1) % self._n_slots
                else:
                    with self.split.span("queue_wait_s"):
                        while len(pending) < self._batch_parts:
                            if self._closed.is_set():
                                return
                            pending.extend(
                                self._filtered_poll(
                                    self._batch_parts - len(pending)
                                )
                            )
                    parts = [t for t, _ in pending[: self._batch_parts]]
                    eps = [e for _, e in pending[: self._batch_parts]]
                    del pending[: self._batch_parts]
                    # Episode stats to numpy HERE (prefetch thread), so
                    # the learner loop's logging never touches device
                    # arrays.
                    eps_np = [
                        {k: np.asarray(v) for k, v in ep.items()}
                        for ep in eps
                    ]
                    with self.split.span("assemble_s"):
                        if self._exec_lock is not None:
                            with self._exec_lock:
                                batch = self._assemble_device(parts)
                                jax.block_until_ready(batch)
                        else:
                            batch = self._assemble_device(parts)
                    item = (batch, eps_np, None)
                    del batch, parts, eps, eps_np

                while not self._closed.is_set():
                    try:
                        self._ready.put(item, timeout=0.2)
                        self.batches += 1
                        break
                    except queue_lib.Full:
                        continue
                del item  # ready queue owns it now
        except _PipelineClosed:
            pass  # ordered shutdown observed mid-assembly; not an error
        except BaseException as e:
            self._error = e
            self._closed.set()

    def _decode_into(self, slot: int, part: int, coded: CodedTrajectory):
        """Decode a coded wire trajectory DIRECTLY into the arena part
        views — the zero-copy receive contract: the slot is the
        destination, no assembled-trajectory staging buffer exists
        between the (CRC-verified) wire bytes and the arena. Returns
        the decoded pytree (leaves alias the slot), or ``None`` when
        the frame is undecodable / shaped for a different config — the
        part index is simply reused by the next polled item, so a
        failed decode can never leave a torn part inside a batch."""
        try:
            infos = coded.infos(max_leaf_bytes=self._max_decode_bytes)
            if len(infos) != len(self._axes):
                raise CodecError(
                    f"coded trajectory has {len(infos)} leaves, arena "
                    f"expects {len(self._axes)}"
                )
            self._arena.ensure_slot(
                slot,
                [i.shape for i in infos],
                [i.dtype for i in infos],
            )
            leaves = coded.decode(
                self._arena.part_views(slot, part),
                max_leaf_bytes=self._max_decode_bytes,
            )
        except (CodecError, ValueError) as e:
            self.decode_errors += 1
            print(
                f"[learner-pipeline] dropping undecodable coded "
                f"trajectory from actor {coded.actor_id}: {e}",
                flush=True,
            )
            return None
        self.coded_parts += 1
        self.traj_coded_bytes += coded.coded_nbytes
        self.traj_decoded_bytes += sum(int(x.nbytes) for x in leaves)
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _assemble_arena(self, pending: List[Tuple[Any, Any]], slot: int):
        # Wait until this slot's previous batch fully retired: its
        # consumer step's token is device-ready (covers the transfer
        # too — the step read the transferred buffers).
        with self.split.span("slot_wait_s"):
            token = None
            while not self._closed.is_set():
                try:
                    token = self._tokens[slot].get(timeout=0.2)
                    break
                except queue_lib.Empty:
                    continue
            if self._closed.is_set():
                raise _PipelineClosed()
            if token is not None:
                jax.block_until_ready(token)

        # Incremental fill: each polled item is placed (decoded or
        # strided-written) the moment it is available; a part whose
        # decode fails or whose post-decode validation rejects it is
        # overwritten by the next item, so only fully-landed,
        # admitted parts ever make up a batch (torn-slot safety).
        eps: List[Any] = []
        placed = 0
        while placed < self._batch_parts:
            with self.split.span("queue_wait_s"):
                while not pending:
                    if self._closed.is_set():
                        raise _PipelineClosed()
                    pending.extend(
                        self._filtered_poll(self._batch_parts - placed)
                    )
            traj, ep = pending.pop(0)
            if isinstance(traj, CodedTrajectory):
                with self.split.span("decode_s"):
                    tree = self._decode_into(slot, placed, traj)
                if tree is None:
                    continue
                if self._validate_coded is not None and not (
                    self._validate_coded(tree, ep, traj.actor_id)
                ):
                    # Dropped-and-recorded by the validator; the slot
                    # space is reused, nothing downstream ever sees it.
                    self.decode_rejects += 1
                    continue
            else:
                try:
                    with self.split.span("assemble_s"):
                        self._arena.write_part(
                            slot, placed, jax.tree_util.tree_leaves(traj)
                        )
                except ValueError as e:
                    # Same fault envelope as the coded path: a plain
                    # frame whose layout does not match this learner's
                    # config (stale-config legacy actor) is dropped
                    # and its part index reused — never fatal.
                    self.decode_errors += 1
                    print(
                        f"[learner-pipeline] dropping mis-laid-out "
                        f"plain trajectory: {e}",
                        flush=True,
                    )
                    continue
            eps.append(ep)
            placed += 1

        eps_np = [
            {k: np.asarray(v) for k, v in ep.items()} for ep in eps
        ]
        with self.split.span("transfer_s"):
            if self._transfer is not None:
                dev_leaves = self._transfer(self._arena.slot_leaves(slot))
            else:
                dev_leaves = [
                    jax.device_put(buf, s)
                    for buf, s in zip(
                        self._arena.slot_leaves(slot), self._shardings
                    )
                ]
            # Block THIS thread (not the learner) until the host->device
            # copies land — the transfer rides under the learner's
            # compute, and once ready the slot's host memory is
            # provably unread.
            jax.block_until_ready(dev_leaves)
        batch = (
            jax.tree_util.tree_unflatten(self._treedef, dev_leaves)
            if self._wrap_batch
            else dev_leaves
        )
        return batch, eps_np, slot

    # -- consumer side --------------------------------------------------

    def get(
        self,
        timeout: float = 0.5,
        stop: Optional[threading.Event] = None,
        max_wait_s: Optional[float] = None,
    ):
        """Next ``(batch, eps, handle)``; blocks until one is staged.
        Raises whatever the prefetch thread raised (health-check
        failures included). With ``stop`` given, returns ``None`` once
        it is set and nothing is staged — a preemption mid-batch-wait
        (actors likely killed by the same signal) must not hang the
        shutdown path forever. With ``max_wait_s``, a wait that
        exceeds it raises ``TimeoutError`` instead of blocking on —
        the sharded stitcher's straggler bound (``ShardedIngest``
        turns it into a loud ``ShardDesync``); plain consumers never
        pass it and keep the block-forever contract."""
        t0 = time.perf_counter()
        with self.split.span("stall_s"):
            while True:
                if self._error is not None:
                    raise self._error
                try:
                    return self._ready.get(timeout=timeout)
                except queue_lib.Empty:
                    if stop is not None and stop.is_set():
                        return None
                    if self._closed.is_set() and self._error is None:
                        raise RuntimeError("pipeline closed while waiting")
                    if (
                        max_wait_s is not None
                        and time.perf_counter() - t0 > max_wait_s
                    ):
                        raise TimeoutError(
                            f"no batch staged within {max_wait_s:.1f}s"
                        )

    def mark_consumed(self, handle, token) -> None:
        """Release the arena slot behind ``handle`` once ``token`` (an
        output of the consuming step) becomes device-ready. No-op for
        device-stacked batches (``handle is None``)."""
        if handle is None:
            return
        self._tokens[handle].put(token)

    def metrics(self) -> dict:
        m = self.split.window()
        m["pipeline_batches"] = self.batches
        m["pipeline_depth"] = self._ready.qsize()
        if self.coded_parts or self.decode_errors:
            # Inbound codec ledger (lifetime): what the coded parts
            # cost on the wire vs what they expanded to in the arena.
            m["pipeline_coded_parts"] = self.coded_parts
            m["pipeline_decode_errors"] = self.decode_errors
            m["pipeline_decode_rejects"] = self.decode_rejects
            m["traj_coded_mb"] = round(self.traj_coded_bytes / 1e6, 6)
            m["traj_decoded_mb"] = round(self.traj_decoded_bytes / 1e6, 6)
            if self.traj_coded_bytes:
                m["traj_codec_ratio"] = round(
                    self.traj_decoded_bytes / self.traj_coded_bytes, 2
                )
        return m

    def close(self) -> None:
        """Ordered shutdown: stop the prefetch thread, then drop any
        staged batch so device buffers free promptly."""
        self._closed.set()
        self._thread.join(timeout=10.0)
        while True:
            try:
                self._ready.get_nowait()
            except queue_lib.Empty:
                break
        # Unblock nothing-in-particular: tokens queue is bounded per
        # slot and the thread is gone; clear for idempotent close().
        for tq in self._tokens:
            try:
                tq.get_nowait()
            except queue_lib.Empty:
                pass

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()


class DeviceRolloutSource:
    """Device-resident self-play as a batch source (the mixed-mode leg
    of the Podracer/Anakin fast path).

    ``get()`` dispatches the jitted ``collect`` program — env.step +
    act + segment assembly entirely on the learner's mesh — and hands
    back a device-resident ``(batch, eps, None)``; the batch never
    crosses the host. The env fleet's state threads through the source
    (reset lazily on first use, so construction costs nothing);
    ``set_params`` swaps the acting weights in process — the publish
    path calls it alongside the wire broadcast, so device self-play
    acts on new weights with zero staleness.

    ``exec_lock`` is the CPU-mesh serialize rule (see
    ``algos.impala.ImpalaActor``): when set, every dispatch runs to
    completion under it; on real accelerators it is None and collect
    dispatches overlap the learner's compute.
    """

    def __init__(
        self,
        *,
        collect: Callable[..., Any],
        reset: Callable[..., Any],
        params: Any,
        seed: int,
        exec_lock: Optional[threading.Lock] = None,
    ):
        self._collect = collect
        self._reset = reset
        self._params = params
        self._key = jax.random.PRNGKey(seed)
        self._exec_lock = exec_lock
        self._env: Optional[Tuple[Any, Any]] = None
        self.split = TimeSplit(prefix=metric_names.DEVICE)
        self.batches = 0

    def set_params(self, params: Any) -> None:
        # Reference swap is atomic under the GIL; params pytrees are
        # immutable device arrays (the ParamStore argument).
        self._params = params

    def _dispatch(self, fn, *args):
        if self._exec_lock is None:
            return fn(*args)
        with self._exec_lock:
            out = fn(*args)
            jax.block_until_ready(out)
            return out

    def get(
        self,
        timeout: float = 0.5,
        stop: Optional[threading.Event] = None,
        max_wait_s: Optional[float] = None,
    ):
        if stop is not None and stop.is_set():
            return None
        with self.split.span("collect_s"):
            if self._env is None:
                self._key, k = jax.random.split(self._key)
                self._env = tuple(self._dispatch(self._reset, k))
            self._key, k = jax.random.split(self._key)
            env_state, obs, batch, ep = self._dispatch(
                self._collect, self._params, self._env[0], self._env[1], k
            )
            self._env = (env_state, obs)
        self.batches += 1
        return batch, [ep], None

    def mark_consumed(self, handle, token) -> None:
        pass  # device batches are fresh program outputs; no slot reuse

    def metrics(self) -> dict:
        m = self.split.window()
        m["device_batches"] = self.batches
        return m

    def close(self) -> None:
        self._env = None  # release the env fleet's device buffers


class InterleavedSource:
    """Deterministic round-robin over a wire batch source and a device
    self-play source: ``device_per_wire`` device batches are served for
    every ONE wire batch. The wire turn blocks on its pipeline exactly
    like host mode's queue drain does (a configured wire fleet is
    expected to feed), so both sources provably contribute — the
    mixed-mode e2e pin counts on it."""

    def __init__(self, wire, device, device_per_wire: int = 1):
        self._wire = wire
        self._device = device
        self._period = max(1, device_per_wire) + 1
        self._n_device = self._period - 1
        self._i = 0
        self.wire_batches = 0
        self.device_batches = 0

    def get(
        self,
        timeout: float = 0.5,
        stop: Optional[threading.Event] = None,
        max_wait_s: Optional[float] = None,
    ):
        use_device = (self._i % self._period) < self._n_device
        self._i += 1
        if use_device:
            got = self._device.get(stop=stop)
            if got is not None:
                self.device_batches += 1
            return got
        got = self._wire.get(timeout=timeout, stop=stop,
                             max_wait_s=max_wait_s)
        if got is not None:
            self.wire_batches += 1
        return got

    def mark_consumed(self, handle, token) -> None:
        # Device handles are None (a no-op for the pipeline too), so
        # one forward covers both sources.
        self._wire.mark_consumed(handle, token)

    def metrics(self) -> dict:
        m = dict(self._wire.metrics())
        m.update(self._device.metrics())
        m["mixed_wire_batches"] = self.wire_batches
        m["mixed_device_batches"] = self.device_batches
        return m

    def close(self) -> None:
        self._wire.close()
        self._device.close()


class _PipelineClosed(Exception):
    """Internal: prefetch observed close() mid-assembly."""


class AsyncParamPublisher:
    """Parameter broadcast off the learner's critical path.

    ``submit(params)`` stores the newest weights reference and returns
    immediately; a side thread performs ``publish_fn(params)`` (the
    blocking device->host fetch + broadcast). Intermediate versions
    are dropped (newest wins) — actors only ever want the latest.

    With buffer donation active the caller must submit a COPY of the
    params (the learner's own buffers are recycled next step); without
    donation the live reference is safe — params are immutable.
    """

    def __init__(self, publish_fn: Callable[[Any], None]):
        self._publish = publish_fn
        self._cond = threading.Condition()
        self._pending: Any = None
        self._has_pending = False
        self._closed = False
        self._error: Optional[BaseException] = None
        self.published = 0
        self.publish_s = 0.0
        self._thread = threading.Thread(
            target=self._run, name="param-publisher", daemon=True
        )
        self._thread.start()

    def submit(self, params: Any) -> None:
        if self._error is not None:
            raise self._error
        with self._cond:
            self._pending = params
            self._has_pending = True
            self._cond.notify()

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._has_pending and not self._closed:
                    self._cond.wait(timeout=0.5)
                if self._closed and not self._has_pending:
                    return
                params, self._pending = self._pending, None
                self._has_pending = False
            try:
                t0 = time.perf_counter()
                self._publish(params)
                self.publish_s += time.perf_counter() - t0
                self.published += 1
            except BaseException as e:
                self._error = e
                return

    def metrics(self) -> dict:
        return {
            "publish_async": self.published,
            "publish_s": round(self.publish_s, 4),
        }

    def close(self) -> None:
        """Flush the pending publication (if any), then stop."""
        with self._cond:
            self._closed = True
            self._cond.notify()
        self._thread.join(timeout=10.0)
        if self._error is not None:
            raise self._error
