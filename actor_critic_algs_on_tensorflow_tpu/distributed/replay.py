"""Ape-X-style sharded prioritized replay tier on the existing wire
planes (Horgan et al. 2018).

PRs 1-12 built transport, codecs, resilience, sharding and quorum for
exactly one workload: on-policy IMPALA. This module is the first
non-IMPALA consumer of those planes — a replay-server tier that
decouples the off-policy family (DDPG/TD3/SAC) the same way Ape-X
decouples acting from learning:

  env-stepper actors --(KIND_TRAJ/KIND_TRAJ_CODED transitions)-->
      replay servers (host ring + sum-tree priority index)
          --(KIND_SAMPLE_REQ/KIND_SAMPLE_BATCH prioritized batches)-->
      learner --(KIND_PRIO_UPDATE absolute TD errors)--> replay servers
      learner --(param plane: KIND_GET_PARAMS/PARAMS_NOTIFY)--> actors

Everything below the replay logic is REUSED, not rebuilt: transitions
ride the PR-6 coded trajectory path (byte-plane codec, per-leaf CRC,
hello/capability negotiation, validator quarantine), the sample RPC is
seq-tagged like the serving tier's lanes (a desynced reply fails the
connection, the resilient client reconnects and re-draws), and the
actor->shard assignment reuses ``ShardPlan``'s contiguous slices.

The tier is sharded N ways: each replay server owns an independent
ring + sum tree fed by its slice of the actor fleet; the learner
round-robins draws across shards and routes each batch's priority
update back to the shard that served it. A shard restart costs refill
time, not a crash — the learner's per-shard clients fail fast and the
draw rotation simply skips a dead shard until it returns.

Priority discipline (bit-auditable; pinned by unit test):

  - new rows enter at the maximum priority seen so far (1.0 initially),
  - the learner sends ABSOLUTE TD errors; the server owns the exponent:
    ``p = (|td| + eps) ** alpha`` becomes the sum-tree leaf,
  - sampling is stratified over the total mass (one uniform draw per
    segment), and importance weights are
    ``w_i = (N * p_i / total) ** -beta / max_j w_j``,
  - every row carries a monotonically-increasing id; a priority update
    for a row the ring has since overwritten is dropped as stale
    instead of re-prioritizing an unrelated transition.
"""

from __future__ import annotations

import dataclasses
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from actor_critic_algs_on_tensorflow_tpu.distributed import codec
from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
    EPOCH_SHIFT,
)
from actor_critic_algs_on_tensorflow_tpu.utils.metric_names import REPLAY

__all__ = [
    "SumTree",
    "PrioritizedReplayShard",
    "ReplayShardService",
    "ReplayClientGroup",
    "ReplaySnapshotter",
    "SampledBatch",
    "replay_server_main",
]


class SumTree:
    """Flat-array sum tree over ``capacity`` leaves (pow2-padded).

    ``tree[1]`` is the root (total mass); leaves live at
    ``[leaf_base, leaf_base + capacity)``. All operations are
    vectorized numpy — ``find`` descends all queries level-by-level in
    lockstep, ``update`` recomputes each touched parent from BOTH its
    children (duplicate-index safe). float64 throughout so prefix sums
    stay exact enough for the bit-audit tests.
    """

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        n = 1
        while n < capacity:
            n <<= 1
        self.leaf_base = n
        self._tree = np.zeros(2 * n, np.float64)

    def update(self, indices: np.ndarray, priorities: np.ndarray) -> None:
        """Set leaf priorities and re-sum the touched ancestor paths."""
        idx = np.asarray(indices, np.int64).reshape(-1)
        pri = np.asarray(priorities, np.float64).reshape(-1)
        if idx.size != pri.size:
            raise ValueError(
                f"{idx.size} indices vs {pri.size} priorities"
            )
        if idx.size == 0:
            return
        if idx.min(initial=0) < 0 or idx.max(initial=0) >= self.capacity:
            raise ValueError(
                f"leaf index outside [0, {self.capacity})"
            )
        if not np.isfinite(pri).all() or pri.min(initial=0.0) < 0.0:
            raise ValueError("priorities must be finite and >= 0")
        t = self._tree
        t[self.leaf_base + idx] = pri
        # Recompute parents bottom-up FROM THEIR CHILDREN: with
        # duplicate leaf indices in one call, a delta-propagation would
        # double-apply — child sums cannot.
        parents = np.unique((self.leaf_base + idx) >> 1)
        while parents.size and parents[0] >= 1:
            t[parents] = t[2 * parents] + t[2 * parents + 1]
            if parents[0] == 1:
                break
            parents = np.unique(parents >> 1)

    def get(self, indices: np.ndarray) -> np.ndarray:
        idx = np.asarray(indices, np.int64).reshape(-1)
        return self._tree[self.leaf_base + idx].copy()

    def total(self) -> float:
        return float(self._tree[1])

    def find(self, values: np.ndarray) -> np.ndarray:
        """Prefix-sum descent: for each ``v`` return the leaf index
        ``i`` with ``sum(p[:i]) <= v < sum(p[:i+1])`` (ties resolve
        left; values clipped into ``[0, total)``)."""
        v = np.asarray(values, np.float64).reshape(-1).copy()
        total = self._tree[1]
        # Clip away fp edge cases (v == total would walk off the end).
        np.clip(v, 0.0, np.nextafter(total, 0.0), out=v)
        idx = np.ones(v.size, np.int64)
        t = self._tree
        while idx[0] < self.leaf_base:
            left = 2 * idx
            left_sum = t[left]
            go_right = v >= left_sum
            v -= np.where(go_right, left_sum, 0.0)
            idx = np.where(go_right, left + 1, left)
        out = idx - self.leaf_base
        # The pow2 padding leaves have zero mass, but fp clipping can
        # still land a query on the last nonzero leaf's right sibling;
        # clamp into the real capacity.
        np.clip(out, 0, self.capacity - 1, out=out)
        return out


class LayoutError(ValueError):
    """A transition frame disagrees with the shard's pinned layout."""


@dataclasses.dataclass
class _EpStats:
    """Episode-return accounting riding the ingest path (actors append
    finished-episode returns to their pushes; the learner drains the
    aggregate through sample-reply metas)."""

    return_sum: float = 0.0
    count: int = 0


class PrioritizedReplayShard:
    """Host-side transition ring + sum-tree priority index (one shard).

    Storage is a list of preallocated ``[capacity, ...]`` numpy arrays
    whose layout is pinned by the FIRST ingested batch (same discipline
    as the host arena: a stale-config actor's mismatched frame is
    rejected, never enthroned). Thread-safe — ingest runs on server
    connection threads while sampling runs on the replay handler's.
    """

    def __init__(
        self,
        capacity: int,
        *,
        alpha: float = 0.6,
        eps: float = 1e-6,
        seed: int = 0,
    ):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self.alpha = float(alpha)
        self.eps = float(eps)
        self._lock = threading.Lock()
        self._rng = np.random.RandomState(seed)
        self._tree = SumTree(self.capacity)
        self._storage: Optional[List[np.ndarray]] = None
        self._leaf_specs: Optional[List[Tuple[tuple, np.dtype]]] = None
        # Monotonic per-row transition ids: a priority update names
        # (index, id) and applies only while the id still matches —
        # wraparound overwrites invalidate stale updates exactly.
        self._row_ids = np.full(self.capacity, -1, np.int64)
        self._next_id = 0
        self._insert_pos = 0
        self.size = 0
        # Exponentiated max priority (the sum-tree leaf value new rows
        # enter at): Ape-X's "insert at max priority" rule.
        self._max_pri = 1.0
        self.ep = _EpStats()
        # Counters (read under the lock via metrics()).
        self.inserted = 0
        self.overwritten = 0
        self.samples_served = 0
        self.sample_rows = 0
        self.prio_applied = 0
        self.prio_stale = 0
        self.rejected_layout = 0
        # -- durability / failover state --------------------------------
        # While ``restoring`` (a respawned server loading its ring
        # snapshot), ingest is dropped-and-counted and sampling answers
        # "refilling" — a half-applied ring must never serve or accept.
        # ``restore_frac`` is the load progress the sample-reply meta
        # exports so the learner's stall guard can tell "restoring
        # (ring N% loaded)" from "dead". ``ring_restored`` marks a
        # shard whose ``inserted`` meter CONTINUED from a snapshot
        # (the client group's meter reconciliation keys on it).
        self.restoring = False
        self.restore_frac = 1.0
        self.ring_restored = False
        self.restored_rows = 0
        self.dropped_restoring = 0
        self.snapshots_taken = 0
        self.last_snapshot_t: Optional[float] = None
        # Fencing epoch (quorum control plane): the highest reign any
        # sample/priority peer ever announced. Priority updates tagged
        # with an OLDER reign are a deposed learner's late frames —
        # dropped and counted, never applied (see
        # ``ReplayShardService.handle``). Snapshot-persisted so a
        # restored shard keeps fencing its old deposed learner.
        self.fence_epoch = 0
        self.prio_fenced = 0

    # -- ingest --------------------------------------------------------

    def _pin_layout(self, leaves: Sequence[np.ndarray]) -> None:
        self._leaf_specs = [
            (tuple(a.shape[1:]), a.dtype) for a in leaves
        ]
        self._storage = [
            np.empty((self.capacity,) + spec, dtype)
            for spec, dtype in self._leaf_specs
        ]

    def _check_layout(self, leaves: Sequence[np.ndarray]) -> Optional[str]:
        if len(leaves) != len(self._leaf_specs):
            return (
                f"{len(leaves)} leaves vs pinned {len(self._leaf_specs)}"
            )
        rows = {int(a.shape[0]) for a in leaves if a.ndim >= 1}
        if len(rows) != 1:
            return f"inconsistent row counts {sorted(rows)}"
        for i, (a, (shape, dtype)) in enumerate(
            zip(leaves, self._leaf_specs)
        ):
            if a.ndim < 1 or tuple(a.shape[1:]) != shape or a.dtype != dtype:
                return (
                    f"leaf {i} is {a.dtype.str}{tuple(a.shape)}, pinned "
                    f"[n]{shape} {dtype.str}"
                )
        return None

    def add(self, leaves: Sequence[np.ndarray]) -> int:
        """Insert a ``[n, ...]``-rows transition batch at the cursor
        (ring semantics; ``n`` > capacity keeps the last ``capacity``
        rows). New rows enter the priority index at the max priority
        seen. Returns rows inserted; raises ``LayoutError`` on a frame
        that disagrees with the pinned layout."""
        leaves = [np.asarray(a) for a in leaves]
        if not leaves or leaves[0].ndim < 1:
            raise LayoutError("transition frame carries no row axis")
        with self._lock:
            if self.restoring:
                # A half-applied ring must not interleave fresh rows
                # with the snapshot being loaded; the frame is dropped
                # (the server still ACKs) and counted. The window is
                # the snapshot load time — seconds, bounded.
                self.dropped_restoring += 1
                return 0
            if self._storage is None:
                self._pin_layout(leaves)
            reason = self._check_layout(leaves)
            if reason is not None:
                self.rejected_layout += 1
                raise LayoutError(reason)
            n = int(leaves[0].shape[0])
            keep = min(n, self.capacity)
            if keep < n:
                leaves = [a[n - keep:] for a in leaves]
            rows = (
                self._insert_pos + np.arange(keep, dtype=np.int64)
            ) % self.capacity
            for buf, a in zip(self._storage, leaves):
                buf[rows] = a
            self.overwritten += max(0, self.size + keep - self.capacity)
            # Ids track the ORIGINAL stream position: when a batch
            # exceeds capacity only its last ``keep`` rows survive,
            # and they keep their stream ids.
            self._row_ids[rows] = (
                self._next_id + (n - keep) + np.arange(keep, dtype=np.int64)
            )
            self._next_id += n
            self._tree.update(
                rows, np.full(keep, self._max_pri, np.float64)
            )
            self._insert_pos = (self._insert_pos + keep) % self.capacity
            self.size = min(self.size + keep, self.capacity)
            self.inserted += n
            return keep

    def add_episode_returns(self, returns: np.ndarray) -> None:
        r = np.asarray(returns, np.float64).reshape(-1)
        if r.size == 0:
            return
        with self._lock:
            self.ep.return_sum += float(r.sum())
            self.ep.count += int(r.size)

    def drain_episode_stats(self) -> Tuple[float, int]:
        with self._lock:
            out = (self.ep.return_sum, self.ep.count)
            self.ep = _EpStats()
            return out

    # -- sampling ------------------------------------------------------

    def sample(self, batch_size: int, beta: float):
        """Stratified prioritized draw. Returns ``(indices, ids,
        priorities, weights, batch_leaves)`` or ``None`` while the
        shard cannot fill a batch (refilling)."""
        batch_size = int(batch_size)
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, {batch_size}")
        with self._lock:
            if self.restoring:
                return None  # loading the ring snapshot: refill-like
            if self._storage is None or self.size < batch_size:
                return None
            total = self._tree.total()
            if total <= 0.0:
                return None
            # Stratified: one uniform draw inside each of batch_size
            # equal-mass segments — lower variance than iid draws and
            # deterministic under the shard's seeded rng.
            seg = total / batch_size
            targets = (
                np.arange(batch_size, dtype=np.float64)
                + self._rng.uniform(size=batch_size)
            ) * seg
            idx = self._tree.find(targets)
            # fp descent can land on a padded/unwritten leaf when the
            # mass boundary falls exactly on it; fold back into the
            # written region.
            np.clip(idx, 0, self.size - 1, out=idx)
            pri = self._tree.get(idx)
            probs = pri / total
            weights = np.power(
                np.maximum(self.size * probs, 1e-12), -float(beta)
            )
            weights /= max(float(weights.max()), 1e-12)
            batch = [buf[idx].copy() for buf in self._storage]
            ids = self._row_ids[idx].copy()
            self.samples_served += 1
            self.sample_rows += batch_size
            return (
                idx.astype(np.int64),
                ids,
                pri,
                weights.astype(np.float32),
                batch,
            )

    def update_priorities(
        self,
        indices: np.ndarray,
        ids: np.ndarray,
        td_abs: np.ndarray,
    ) -> Tuple[int, int]:
        """Apply absolute-TD priorities: ``p = (|td| + eps) ** alpha``
        for rows whose id still matches (overwritten rows are dropped
        as stale). Returns (applied, stale)."""
        idx = np.asarray(indices, np.int64).reshape(-1)
        ids = np.asarray(ids, np.int64).reshape(-1)
        td = np.abs(np.asarray(td_abs, np.float64).reshape(-1))
        if not (idx.size == ids.size == td.size):
            raise ValueError("indices/ids/td size mismatch")
        if idx.size == 0:
            return 0, 0
        if idx.min() < 0 or idx.max() >= self.capacity:
            raise ValueError(f"row index outside [0, {self.capacity})")
        # A hostile/corrupt TD vector must not poison the tree.
        td = np.where(np.isfinite(td), td, 0.0)
        pri = np.power(td + self.eps, self.alpha)
        with self._lock:
            fresh = self._row_ids[idx] == ids
            applied = int(fresh.sum())
            if applied:
                self._tree.update(idx[fresh], pri[fresh])
                self._max_pri = max(
                    self._max_pri, float(pri[fresh].max())
                )
            self.prio_applied += applied
            self.prio_stale += idx.size - applied
            return applied, idx.size - applied

    def priority_of(self, indices: np.ndarray) -> np.ndarray:
        """Current sum-tree leaf values (the bit-audit probe)."""
        with self._lock:
            return self._tree.get(indices)

    # -- durability (snapshot / restore / fencing) ---------------------

    def raise_fence(self, epoch: int) -> int:
        """Adopt a (monotonically larger) fencing epoch; returns the
        epoch in force. Epochs never regress — a deposed learner
        re-announcing its old reign cannot lower the fence."""
        with self._lock:
            if int(epoch) > self.fence_epoch:
                self.fence_epoch = int(epoch)
            return self.fence_epoch

    def note_fenced(self, n: int = 1) -> None:
        with self._lock:
            self.prio_fenced += int(n)

    def begin_restore(self) -> None:
        with self._lock:
            self.restoring = True
            self.restore_frac = 0.0

    def set_restore_progress(self, frac: float) -> None:
        with self._lock:
            self.restore_frac = min(1.0, max(0.0, float(frac)))

    def end_restore(self) -> None:
        with self._lock:
            self.restoring = False
            self.restore_frac = 1.0

    def durability_meta(self) -> Tuple[float, float, float]:
        """(restore_frac, snapshot_age_s, ring_restored) for the
        sample-reply meta — the learner's view of this shard's
        durability state (age −1.0 = never snapshotted)."""
        with self._lock:
            age = (
                time.monotonic() - self.last_snapshot_t
                if self.last_snapshot_t is not None
                else -1.0
            )
            return (
                float(self.restore_frac),
                float(age),
                1.0 if self.ring_restored else 0.0,
            )

    def snapshot_cut(
        self, since_id: Optional[int] = None
    ) -> Optional[Dict[str, np.ndarray]]:
        """One CONSISTENT copy of the shard's durable state, taken
        under the lock (the caller writes it to disk off the serve
        threads). ``since_id=None`` cuts the FULL ring; otherwise only
        rows whose stream ids are >= ``since_id`` (the incremental
        delta since the previous cut's ``next_id`` watermark) ride,
        while the small per-row vectors (ids, priorities) and the
        scalar meters always ship whole — so applying full + deltas in
        order reproduces the ring, tree, rng and meters bit-exactly.
        ``None`` when nothing was ever ingested."""
        with self._lock:
            if self._storage is None:
                return None
            _, rng_keys, rng_pos, rng_has_g, rng_gauss = (
                self._rng.get_state()
            )
            state: Dict[str, np.ndarray] = {
                "meta_i": np.asarray(
                    [
                        self.capacity,
                        len(self._storage),
                        self._insert_pos,
                        self.size,
                        self._next_id,
                        self.inserted,
                        self.overwritten,
                        self.fence_epoch,
                        self.ep.count,
                        -1 if since_id is None else int(since_id),
                    ],
                    np.int64,
                ),
                "meta_f": np.asarray(
                    [self._max_pri, self.ep.return_sum], np.float64
                ),
                "row_ids": self._row_ids.copy(),
                "pri": self._tree.get(np.arange(self.capacity)),
                "rng_keys": np.asarray(rng_keys, np.uint32),
                "rng_meta": np.asarray([rng_pos, rng_has_g], np.int64),
                "rng_gauss": np.asarray([rng_gauss], np.float64),
            }
            if since_id is None:
                rows = None
            else:
                rows = np.nonzero(self._row_ids >= int(since_id))[0]
                state["positions"] = rows.astype(np.int64)
            for i, buf in enumerate(self._storage):
                state[f"leaf{i:02d}"] = (
                    buf.copy() if rows is None else buf[rows].copy()
                )
            return state

    def apply_snapshot(self, states: Sequence[Dict[str, np.ndarray]]) -> int:
        """Install a snapshot chain (one FULL cut, then its deltas in
        order) wholesale: storage, ids, priorities, rng and meters all
        come from the chain, so a restored shard samples bit-
        identically to the pre-kill shard at the snapshot point.
        Returns resident rows. Whatever the ring held before (e.g. a
        few frames that raced in pre-restore) is overwritten — those
        transitions were counted by the meters when first ingested."""
        if not states:
            raise ValueError("empty snapshot chain")
        full, incs = states[0], states[1:]
        meta_i = np.asarray(full["meta_i"], np.int64).reshape(-1)
        if int(meta_i[0]) != self.capacity:
            raise ValueError(
                f"snapshot capacity {int(meta_i[0])} != shard capacity "
                f"{self.capacity} (restore into a same-shape shard)"
            )
        if int(meta_i[9]) != -1:
            raise ValueError("snapshot chain does not start with a full cut")
        n_leaves = int(meta_i[1])
        storage = [
            np.asarray(full[f"leaf{i:02d}"]).copy() for i in range(n_leaves)
        ]
        for inc in incs:
            if int(np.asarray(inc["meta_i"], np.int64)[1]) != n_leaves:
                raise ValueError("incremental cut leaf count mismatch")
            pos = np.asarray(inc["positions"], np.int64).reshape(-1)
            for i in range(n_leaves):
                storage[i][pos] = np.asarray(inc[f"leaf{i:02d}"])
        last = states[-1]
        meta_i = np.asarray(last["meta_i"], np.int64).reshape(-1)
        meta_f = np.asarray(last["meta_f"], np.float64).reshape(-1)
        with self._lock:
            self._storage = storage
            self._leaf_specs = [
                (tuple(a.shape[1:]), a.dtype) for a in storage
            ]
            self._row_ids = np.asarray(last["row_ids"], np.int64).copy()
            self._tree = SumTree(self.capacity)
            self._tree.update(
                np.arange(self.capacity),
                np.asarray(last["pri"], np.float64),
            )
            self._insert_pos = int(meta_i[2])
            self.size = int(meta_i[3])
            self._next_id = int(meta_i[4])
            self.inserted = int(meta_i[5])
            self.overwritten = int(meta_i[6])
            self.fence_epoch = max(self.fence_epoch, int(meta_i[7]))
            self.ep = _EpStats(
                return_sum=float(meta_f[1]), count=int(meta_i[8])
            )
            self._max_pri = float(meta_f[0])
            rng_meta = np.asarray(last["rng_meta"], np.int64).reshape(-1)
            self._rng.set_state((
                "MT19937",
                np.asarray(last["rng_keys"], np.uint32),
                int(rng_meta[0]),
                int(rng_meta[1]),
                float(np.asarray(last["rng_gauss"], np.float64)[0]),
            ))
            self.ring_restored = True
            self.restored_rows = self.size
            return self.size

    def metrics(self) -> Dict[str, float]:
        with self._lock:
            age = (
                time.monotonic() - self.last_snapshot_t
                if self.last_snapshot_t is not None
                else -1.0
            )
            return {
                REPLAY + "size": self.size,
                REPLAY + "inserted": self.inserted,
                REPLAY + "samples_served": self.samples_served,
                REPLAY + "sample_rows": self.sample_rows,
                REPLAY + "prio_applied": self.prio_applied,
                REPLAY + "prio_stale": self.prio_stale,
                REPLAY + "layout_rejects": self.rejected_layout,
                REPLAY + "snapshots": self.snapshots_taken,
                REPLAY + "snapshot_age_s": round(age, 3),
                REPLAY + "restore_frac": self.restore_frac,
                REPLAY + "restored_rows": self.restored_rows,
                REPLAY + "drop_restoring": self.dropped_restoring,
                REPLAY + "prio_fenced": self.prio_fenced,
            }


_SNAP_RE = re.compile(r"^snap-(\d{8})-(full|inc)\.npz$")


class ReplaySnapshotter:
    """Atomic on-disk ring snapshots for one ``PrioritizedReplayShard``.

    The replay ring is the only training state that lives nowhere but
    a server process's memory; this spills it with the same
    atomic-write discipline as ``utils.checkpoint.Checkpointer``
    (write to a temp name, ``os.replace`` to finalize — a kill
    mid-write leaves a ``.tmp-`` dropping, never a corrupt snapshot).

    Layout under ``directory``: ``snap-<seq>-full.npz`` (the whole
    ring) and ``snap-<seq>-inc.npz`` (rows newer than the previous
    snapshot's stream-id watermark, plus the full small vectors —
    ids, priorities, rng, meters). Every ``full_every``-th save is
    full; the chain ``full + incs`` replays to the exact pre-kill
    state (``PrioritizedReplayShard.apply_snapshot``). Retention: a
    new full snapshot prunes everything OLDER than the previous full,
    so the previous chain stays as the crash-safe fallback when the
    newest full itself is the partial write.

    Restore walks fulls newest-first; a corrupt incremental truncates
    its chain there (the prefix is still a consistent, just older,
    state), a corrupt full falls back to the previous chain — the
    ``Checkpointer.restore`` fallback discipline, file-local."""

    def __init__(
        self,
        directory: str,
        *,
        full_every: int = 8,
        log: Callable[[str], None] | None = None,
    ):
        self.directory = os.path.abspath(os.fspath(directory))
        os.makedirs(self.directory, exist_ok=True)
        self._full_every = max(1, int(full_every))
        self._log = log if log is not None else (
            lambda msg: print(f"[replay-snapshot] {msg}", flush=True)
        )
        files = self._files()
        self._seq = files[-1][0] if files else 0
        # Stream-id watermark of the last save/restore: None forces the
        # next save to be FULL (a respawned snapshotter cannot know
        # what the on-disk chain covers relative to a live ring).
        self._watermark: Optional[int] = None
        self._saves_since_full = 0

    def _files(self) -> List[Tuple[int, str, str]]:
        """Sorted ``(seq, kind, path)`` of finalized snapshots."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        for name in names:
            m = _SNAP_RE.match(name)
            if m:
                out.append((
                    int(m.group(1)), m.group(2),
                    os.path.join(self.directory, name),
                ))
        return sorted(out)

    def available(self) -> bool:
        return any(kind == "full" for _, kind, _ in self._files())

    def save(self, shard: "PrioritizedReplayShard") -> int:
        """Write one snapshot (full or incremental per the cadence);
        returns the sequence id, or -1 when the ring is still empty.
        The cut is taken under the shard lock; the (slow) disk write
        happens after release, off the serve threads."""
        full = (
            self._watermark is None
            or self._saves_since_full >= self._full_every - 1
        )
        cut = shard.snapshot_cut(None if full else self._watermark)
        if cut is None:
            return -1
        self._seq += 1
        seq = self._seq
        kind = "full" if full else "inc"
        path = os.path.join(self.directory, f"snap-{seq:08d}-{kind}.npz")
        tmp = os.path.join(self.directory, f".tmp-snap-{seq:08d}.npz")
        with open(tmp, "wb") as f:
            np.savez(f, **cut)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._watermark = int(np.asarray(cut["meta_i"], np.int64)[4])
        self._saves_since_full = 0 if full else self._saves_since_full + 1
        with shard._lock:
            shard.snapshots_taken += 1
            shard.last_snapshot_t = time.monotonic()
        if full:
            self._prune(seq)
        return seq

    def _prune(self, new_full_seq: int) -> None:
        """Keep the new full's chain plus the previous full's chain;
        drop everything older (and any stale temp droppings)."""
        fulls = [
            s for s, kind, _ in self._files()
            if kind == "full" and s < new_full_seq
        ]
        keep_from = fulls[-1] if fulls else new_full_seq
        for s, _, path in self._files():
            if s < keep_from:
                try:
                    os.remove(path)
                except OSError:
                    pass
        try:
            for name in os.listdir(self.directory):
                if name.startswith(".tmp-"):
                    os.remove(os.path.join(self.directory, name))
        except OSError:
            pass

    def restore(self, shard: "PrioritizedReplayShard") -> int:
        """Load the newest restorable chain into ``shard``; returns
        rows restored (0 = nothing usable on disk). Progress is
        surfaced through ``shard.set_restore_progress`` so the
        sample-reply meta can report "ring N% loaded" while files
        stream in."""
        files = self._files()
        fulls = [f for f in files if f[1] == "full"]
        for base_seq, _, base_path in reversed(fulls):
            chain_paths = [(base_seq, base_path)]
            for s, kind, path in files:
                if s > base_seq and kind == "inc":
                    chain_paths.append((s, path))
                elif s > base_seq and kind == "full":
                    break  # a newer full owns the incs after it
            total = sum(
                max(1, os.path.getsize(p)) for _, p in chain_paths
            )
            states, done = [], 0
            for i, (s, path) in enumerate(chain_paths):
                size = max(1, os.path.getsize(path))
                try:
                    with np.load(path, allow_pickle=False) as z:
                        # Per-member progress: one full cut usually
                        # dominates the chain, and a multi-GB load
                        # that reported nothing until the whole file
                        # landed would sit at 0.0 across the
                        # learner's stall windows — read as "dead",
                        # not "loading". npz members decompress on
                        # access, so each storage leaf advances the
                        # fraction.
                        keys = list(z.files)
                        state = {}
                        for j, key in enumerate(keys):
                            state[key] = z[key]
                            shard.set_restore_progress(
                                (done + size * (j + 1) / len(keys))
                                / total
                            )
                        states.append(state)
                except Exception as e:
                    if i == 0:
                        self._log(
                            f"full snapshot seq {s} unreadable "
                            f"({type(e).__name__}: {e}); trying the "
                            f"previous chain"
                        )
                        states = None
                        break
                    self._log(
                        f"incremental snapshot seq {s} unreadable "
                        f"({type(e).__name__}: {e}); truncating the "
                        f"chain there (restoring the older prefix)"
                    )
                    break
                done += size
                shard.set_restore_progress(done / total)
            if not states:
                continue
            try:
                rows = shard.apply_snapshot(states)
            except (KeyError, ValueError, IndexError) as e:
                self._log(
                    f"snapshot chain at full seq {base_seq} failed to "
                    f"apply ({type(e).__name__}: {e}); trying the "
                    f"previous chain"
                )
                continue
            self._watermark = shard._next_id
            self._seq = max(self._seq, chain_paths[-1][0])
            return rows
        return 0


class _TransitionView:
    """Adapter mapping a flattened ``offpolicy.Transition`` frame onto
    the field names ``TrajectoryValidator`` checks (obs/rewards/dones/
    last_obs/actions), so the PR-3 quarantine machinery applies to
    transition frames unchanged. Frames with a different leaf count
    still get whole-frame finite checks via ``obs``."""

    def __init__(self, leaves: Sequence[np.ndarray]):
        if len(leaves) == 5:
            self.obs, self.actions, self.rewards, self.last_obs, \
                self.dones = leaves
        else:
            self.obs = list(leaves)
            self.actions = None
            self.rewards = None
            self.last_obs = None
            self.dones = None


class ReplayShardService:
    """Glue between one ``LearnerServer`` and one
    ``PrioritizedReplayShard``: the trajectory sink (transition ingest
    with validator quarantine, plain or coded frames) and the replay
    handler (sample RPC + priority updates).

    Sample-reply wire contract (``KIND_SAMPLE_BATCH``, tag = request
    seq): ``arrays[0]`` is a float64 meta vector
    ``[rows_available, inserted_total, ep_return_sum, ep_count]``;
    a served batch appends ``[indices (i64), ids (i64), priorities
    (f64), weights (f32), *batch leaves]`` — meta alone means the
    shard cannot fill the batch yet (refilling). Episode stats drain
    through the meta so the learner's log stream keeps avg_return
    without a separate reporting plane.
    """

    def __init__(
        self,
        shard: PrioritizedReplayShard,
        *,
        validator=None,
        admission=None,
        log: Callable[[str], None] | None = None,
    ):
        self.shard = shard
        self.validator = validator
        # Tenant metering (distributed.tenancy.TenantAdmission): the
        # quarantine adapter's question extends from "is this frame
        # poisoned" to "is this tenant over budget" — over-budget
        # frames are shed (still ACKed) before they cost a ring slot.
        self.admission = admission
        self._log = log if log is not None else (
            lambda msg: print(f"[replay-shard] {msg}", flush=True)
        )

    # -- ingest (LearnerServer on_trajectory, 3-arg form) --------------

    def ingest(self, traj, ep_leaves, peer) -> bool:
        actor_id = getattr(peer, "actor_id", -1)
        if self.shard.restoring:
            # Loading the ring snapshot: fresh rows must not interleave
            # with the wholesale apply. Dropped (still ACKed) and
            # counted; the window is the snapshot load, seconds.
            with self.shard._lock:
                self.shard.dropped_restoring += 1
            return False
        if isinstance(traj, codec.CodedTrajectory):
            if self.validator is not None and (
                self.validator.drop_quarantined(actor_id)
            ):
                return False
            try:
                leaves = traj.decode()
            except codec.CodecError as e:
                self._log(f"undecodable transition frame: {e}")
                return False
        else:
            leaves = [np.asarray(x) for x in traj]
        if self.admission is not None and not self.admission.admit_frame(
            peer, sum(int(a.nbytes) for a in leaves)
        ):
            return False
        if self.validator is not None:
            ok = self.validator.admit(
                _TransitionView(leaves), {}, source_actor_id=actor_id
            )
            if not ok:
                return False
        try:
            self.shard.add(leaves)
        except LayoutError as e:
            self._log(f"rejected transition frame: {e}")
            return False
        # Episode-info convention on this plane: one float leaf of
        # finished-episode returns (possibly empty) per push.
        if ep_leaves:
            returns = np.asarray(ep_leaves[0], np.float64).reshape(-1)
            if np.isfinite(returns).all():
                self.shard.add_episode_returns(returns)
        return True

    # -- sample / priority plane (LearnerServer replay handler) --------

    def handle(self, peer, kind, tag, arrays, reply) -> None:
        from actor_critic_algs_on_tensorflow_tpu.distributed import (
            transport,
        )

        # Fencing (quorum control plane): every sample/priority frame's
        # tag carries its sender's reign in the high bits
        # (transport.EPOCH_SHIFT), and the sender's hello announced one
        # too. The highest reign ever seen is the fence; a PRIORITY
        # update tagged with an older reign is a deposed learner's
        # late frame — dropped and counted, never applied. Sample
        # draws are not fenced (a stale draw wastes only bandwidth;
        # its priorities will be fenced anyway). Legacy peers tag and
        # announce 0, so a fleet that never elects never fences.
        peer_epoch = getattr(peer, "epoch", 0)
        if kind == transport.KIND_SAMPLE_REQ:
            self.shard.raise_fence(
                max(peer_epoch, transport.epoch_of(tag))
            )
            malformed = False
            try:
                batch_size = int(np.asarray(arrays[0]).reshape(-1)[0])
                beta = float(np.asarray(arrays[1]).reshape(-1)[0])
            except (IndexError, TypeError, ValueError):
                # Answer meta-only rather than dropping the request:
                # the client's sample_request is a BLOCKING
                # request/reply, so silence here would hang every
                # draw for the client's full idle deadline instead of
                # surfacing as a visible refill + log line.
                self._log(f"malformed sample request from {peer}")
                malformed = True
                batch_size = 0
            # batch_size <= 0 is the STATUS PROBE: the learner
            # refreshes its budget/episode meters without paying for
            # (and without the shard serving) a discarded batch.
            out = (
                self.shard.sample(batch_size, beta)
                if batch_size > 0 and not malformed
                else None
            )
            ret_sum, ep_count = self.shard.drain_episode_stats()
            restore_frac, snap_age, restored = (
                self.shard.durability_meta()
            )
            meta = np.asarray(
                [
                    float(self.shard.size),
                    float(self.shard.inserted),
                    ret_sum,
                    float(ep_count),
                    # Durability view (meta[4:7], absent on legacy
                    # shards): load progress while a respawn restores
                    # its ring, snapshot age (-1 = never), and whether
                    # this process's meter CONTINUED from a snapshot.
                    restore_frac,
                    snap_age,
                    restored,
                ],
                np.float64,
            )
            if out is None:
                reply([meta])
                return
            idx, ids, pri, weights, batch = out
            reply([meta, idx, ids, pri, weights, *batch])
        elif kind == transport.KIND_PRIO_UPDATE:
            # One frame carries >= 1 (ids, indices, td) triples: the
            # pipelined learner coalesces a tick's write-backs into
            # one multi-entry frame per shard (the serial learner's
            # single triple is the degenerate case).
            if not arrays or len(arrays) % 3 != 0:
                self._log(
                    f"malformed priority update ({len(arrays)} arrays)"
                )
                return
            sender_epoch = transport.epoch_of(tag)
            fence = self.shard.raise_fence(
                max(peer_epoch, sender_epoch)
            )
            if sender_epoch < fence:
                # One tag fences the WHOLE coalesced frame: every
                # entry is from the same deposed reign.
                self.shard.note_fenced()
                return
            for i in range(0, len(arrays), 3):
                try:
                    self.shard.update_priorities(
                        np.asarray(arrays[i + 1], np.int64),
                        np.asarray(arrays[i], np.int64),
                        np.asarray(arrays[i + 2], np.float64),
                    )
                except ValueError as e:
                    self._log(f"rejected priority update: {e}")

    def metrics(self) -> Dict[str, float]:
        out = dict(self.shard.metrics())
        if self.admission is not None:
            out.update(self.admission.metrics())
        return out


def replay_server_main(
    shard_id: int,
    port_conn,
    *,
    host: str = "127.0.0.1",
    port: int = 0,
    capacity: int = 100_000,
    alpha: float = 0.6,
    eps: float = 1e-6,
    seed: int = 0,
    validate: bool = True,
    quarantine_threshold: int = 3,
    idle_timeout_s: float | None = None,
    max_frame_bytes: int = 1 << 30,
    report_interval_s: float = 30.0,
    snapshot_dir: str | None = None,
    snapshot_interval_s: float = 30.0,
    snapshot_full_every: int = 8,
    tenancy_budget_mb_s: float = 0.0,
    tenancy_budgets: str = "",
    tenancy_burst_s: float = 2.0,
    server_io_mode: str = "reactor",
) -> None:
    """Entry point of one spawned replay-server PROCESS.

    Binds a ``LearnerServer`` whose trajectory sink feeds the shard's
    ring (the full PR-6 ingest path: CRC at the wire, hello
    provenance, coded-frame decode, validator quarantine) and whose
    replay handler serves the sample/priority plane. Reports the bound
    port back through ``port_conn`` (a multiprocessing pipe end) so
    the parent can wire endpoints race-free, then serves until
    drained or terminated.

    Durability (``snapshot_dir`` set): the ring is restored from the
    newest on-disk snapshot chain at boot — a respawned shard resumes
    its rows, priorities, rng and ``inserted`` meter instead of
    refilling from zero (draws during the load answer meta-only with
    the load fraction, so the learner reports "restoring", not
    "dead") — and re-snapshotted every ``snapshot_interval_s`` off
    the serve threads. Clean drain: SIGTERM, or an orderly
    ``KIND_CLOSE`` goodbye from a ``ROLE_LEARNER`` peer (the
    coordinated ``--preempt-save`` teardown), flushes one final
    snapshot before exit so the shutdown is resumable end-to-end —
    only a SIGKILL costs the since-last-snapshot tail."""
    import signal as signal_lib

    from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import (
        pin_process_to_cpu,
    )

    pin_process_to_cpu(f"replay-server {shard_id}")
    from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
        ROLE_LEARNER,
        LearnerServer,
    )

    log = lambda msg: print(f"[replay-server {shard_id}] {msg}", flush=True)
    drain = threading.Event()
    try:
        signal_lib.signal(
            signal_lib.SIGTERM, lambda signum, frame: drain.set()
        )
    except (ValueError, OSError):
        pass  # not this process's main thread (in-process test drive)
    validator = None
    if validate:
        from actor_critic_algs_on_tensorflow_tpu.utils.health import (
            TrajectoryValidator,
        )

        validator = TrajectoryValidator(
            quarantine_threshold=quarantine_threshold, log=log
        )
    shard = PrioritizedReplayShard(
        capacity, alpha=alpha, eps=eps, seed=seed
    )
    snapshotter = None
    if snapshot_dir:
        snapshotter = ReplaySnapshotter(
            snapshot_dir, full_every=snapshot_full_every, log=log
        )
        if snapshotter.available():
            # Gate ingest/sampling BEFORE the listener binds: frames
            # that race the load are dropped-and-counted, and draws
            # answer meta-only with the load fraction.
            shard.begin_restore()
    admission = None
    if tenancy_budget_mb_s > 0 or tenancy_budgets:
        from actor_critic_algs_on_tensorflow_tpu.distributed.tenancy import (
            TenantAdmission,
            parse_budgets,
        )

        admission = TenantAdmission(
            default_mb_s=tenancy_budget_mb_s,
            budgets=parse_budgets(tenancy_budgets),
            burst_s=tenancy_burst_s,
            log=log,
        )
    service = ReplayShardService(
        shard, validator=validator, admission=admission, log=log
    )
    server = LearnerServer(
        service.ingest,
        host=host,
        port=port,
        idle_timeout_s=idle_timeout_s,
        max_frame_bytes=max_frame_bytes,
        # The replay tier publishes no params; the delta ring would
        # only hold memory.
        param_delta=False,
        server_io_mode=server_io_mode,
        log=log,
    )
    server.set_replay_handler(service.handle)

    def _on_goodbye(peer):
        # Drain only on the CURRENT reign's learner goodbye: a
        # deposed-but-alive learner (it stalled past the takeover
        # deadline, a standby took over, and it tears down later)
        # announces its OLD epoch — its KIND_CLOSE must not shut the
        # tier down under the new primary, whose first draw raised
        # the fence past it. Residual window: a goodbye landing
        # before the new reign ever touched this shard still drains,
        # and the flushed final snapshot makes even that recoverable.
        if peer.role == ROLE_LEARNER and peer.epoch >= shard.fence_epoch:
            drain.set()
        elif peer.role == ROLE_LEARNER:
            log(
                f"ignored goodbye from deposed learner (epoch "
                f"{peer.epoch} < fence {shard.fence_epoch})"
            )

    server.set_goodbye_handler(_on_goodbye)
    if port_conn is not None:
        port_conn.send(server.port)
        port_conn.close()
    print(
        f"[replay-server {shard_id}] serving on {host}:{server.port} "
        f"(capacity {capacity}, alpha {alpha}"
        + (f", snapshots -> {snapshot_dir}" if snapshot_dir else "")
        + ")",
        flush=True,
    )
    if shard.restoring:
        try:
            rows = snapshotter.restore(shard)
            if rows:
                log(
                    f"ring restored: {rows} rows, meter continues at "
                    f"{shard.inserted} (fence epoch "
                    f"{shard.fence_epoch})"
                )
            else:
                log("no restorable snapshot chain; starting empty")
        except Exception as e:
            log(
                f"ring restore failed ({type(e).__name__}: {e}); "
                f"starting empty"
            )
        finally:
            shard.end_restore()
    try:
        last_report = last_snap = time.monotonic()
        while not drain.is_set():
            drain.wait(0.5)
            now = time.monotonic()
            if (
                snapshotter is not None
                and snapshot_interval_s
                and now - last_snap >= snapshot_interval_s
            ):
                last_snap = now
                try:
                    snapshotter.save(shard)
                except OSError as e:
                    log(
                        f"snapshot failed ({type(e).__name__}: {e}); "
                        f"will retry next interval"
                    )
            if (
                report_interval_s
                and now - last_report >= report_interval_s
            ):
                last_report = now
                log(f"{service.metrics()}")
    except KeyboardInterrupt:
        pass
    finally:
        if snapshotter is not None:
            # The clean-drain contract: SIGTERM / learner goodbye /
            # Ctrl-C all flush a final cut so the shutdown is
            # resumable; only SIGKILL loses the tail.
            try:
                seq = snapshotter.save(shard)
                if seq >= 0:
                    log(
                        f"final snapshot seq {seq} "
                        f"({shard.size} rows, meter {shard.inserted})"
                    )
            except OSError as e:
                log(f"final snapshot failed ({type(e).__name__}: {e})")
        server.close()
        if drain.is_set():
            log("drained (clean shutdown)")


class SampledBatch:
    """One prioritized draw as the learner consumes it."""

    __slots__ = (
        "shard_idx", "indices", "ids", "priorities", "weights", "leaves",
    )

    def __init__(self, shard_idx, indices, ids, priorities, weights, leaves):
        self.shard_idx = shard_idx
        self.indices = indices
        self.ids = ids
        self.priorities = priorities
        self.weights = weights
        self.leaves = leaves


class ReplayClientGroup:
    """Learner-side client over N replay shards: round-robin draws,
    fail-fast failover, and priority routing.

    Each shard gets its own ``ResilientActorClient`` with a SHORT
    retry deadline: a draw against a dead shard costs ~``retry_s`` of
    backoff, then the rotation moves on (``sample_failovers``
    counted) — one replay-server restart degrades sampling sharpness,
    never the learner. Priority updates route back to the shard that
    served the batch and are best-effort by design."""

    def __init__(
        self,
        endpoints: Sequence[Tuple[str, int]],
        *,
        client_id: int = 0,
        epoch: int = 0,
        retry_s: float = 2.0,
        heartbeat_interval_s: float | None = 10.0,
        idle_timeout_s: float | None = 60.0,
        max_frame_bytes: int = 1 << 30,
        connect_timeout: float = 5.0,
        make_client=None,
    ):
        from actor_critic_algs_on_tensorflow_tpu.distributed.resilience import (  # noqa: E501
            ResilientActorClient,
            RetryPolicy,
        )
        from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (  # noqa: E501
            CAP_REPLAY,
            ROLE_LEARNER,
        )

        if not endpoints:
            raise ValueError("replay client group needs >= 1 endpoint")
        # The learner's fencing reign: announced in the hello and
        # stamped into every sample/priority tag's high bits, so a
        # shard can drop a DEPOSED learner's late priority updates
        # after a standby takeover bumps the epoch.
        self.epoch = int(epoch)
        if make_client is None:
            def make_client(host, port):
                return ResilientActorClient(
                    host,
                    port,
                    retry=RetryPolicy(deadline_s=retry_s),
                    heartbeat_interval_s=heartbeat_interval_s,
                    idle_timeout_s=idle_timeout_s,
                    connect_timeout=connect_timeout,
                    max_frame_bytes=max_frame_bytes,
                    # ROLE_LEARNER: a replay server treats THIS peer's
                    # orderly goodbye as "the run is over — flush a
                    # final ring snapshot and drain" (actors' goodbyes
                    # mean nothing tier-wide).
                    hello=(
                        client_id, 0, ROLE_LEARNER, CAP_REPLAY,
                        self.epoch,
                    ),
                )

        # Clients are constructed LAZILY, per shard, on first use: a
        # shard that is down when the group comes up (or restarting
        # mid-run) must cost a failover, never the learner — eager
        # construction would crash on the first dead endpoint.
        self._endpoints = [(h, int(p)) for h, p in endpoints]
        self._make_client = make_client
        self._clients: List[Any] = [None] * len(self._endpoints)
        self._rr = 0
        self._seq = 0
        # Pipelined prefetch runs one drawing thread PER SHARD
        # concurrently with the runner's meter polls: seq allocation
        # and the meter/counter state each get a lock. Per-shard draw
        # seqs (instead of the shared rotation seq) keep a shard's
        # in-flight draw tags monotonic per connection, so a reissued
        # draw after an interrupt can never match a stale echo.
        self._seq_lock = threading.Lock()
        self._meter_lock = threading.Lock()
        self._shard_seqs = [0] * len(self._endpoints)
        self.draws = 0
        self.refills = 0
        self.sample_failovers = 0
        self.prio_failures = 0
        # Per-shard view from the last seen sample-reply meta. The
        # budget meter is CUMULATIVE with reset detection: a respawned
        # shard's counter restarts at 0, but the transitions its dead
        # predecessor ingested were real env steps — summing raw
        # meters would regress the global meter below an
        # already-reached budget and wedge the runner's stop
        # condition (found by the kill-drill test).
        self.shard_rows = [0.0] * len(self._clients)
        self.shard_inserted_last = [0.0] * len(self._clients)
        self._shard_inserted_cum = [0.0] * len(self._clients)
        # Per-shard durability view from the extended sample-reply
        # meta: snapshot-restore progress (1.0 = fully serving),
        # snapshot age (-1 = never), and whether the shard's meter
        # continued from a restored ring (reconciliation keys on it).
        self.shard_restore_frac = [1.0] * len(self._clients)
        self.shard_snapshot_age = [-1.0] * len(self._clients)
        self._shard_ring_restored = [False] * len(self._clients)
        self._ep_return_sum = 0.0
        self._ep_count = 0

    def __len__(self) -> int:
        return len(self._clients)

    def _client(self, k: int):
        if self._clients[k] is None:
            self._clients[k] = self._make_client(*self._endpoints[k])
        return self._clients[k]

    def _parse(self, shard_idx: int, arrays) -> Optional[SampledBatch]:
        if not arrays:
            raise ConnectionError("empty sample reply")
        meta = np.asarray(arrays[0], np.float64).reshape(-1)
        with self._meter_lock:
            self._apply_meta(shard_idx, meta)
        if len(arrays) == 1:
            return None  # shard refilling
        if len(arrays) < 6:
            raise ConnectionError(
                f"sample reply carries {len(arrays)} arrays"
            )
        return SampledBatch(
            shard_idx,
            np.asarray(arrays[1], np.int64),
            np.asarray(arrays[2], np.int64),
            np.asarray(arrays[3], np.float64),
            np.asarray(arrays[4], np.float32),
            [np.asarray(a) for a in arrays[5:]],
        )

    def _apply_meta(self, shard_idx: int, meta: np.ndarray) -> None:
        """Fold one sample-reply meta into the per-shard meter view.
        Caller holds ``_meter_lock``: concurrent prefetch workers fold
        replies from different shards, and the reconciliation below is
        read-modify-write on the cumulative meters."""
        if meta.size >= 4:
            self.shard_rows[shard_idx] = float(meta[0])
            restored = self._shard_ring_restored[shard_idx]
            if meta.size >= 7:
                self.shard_restore_frac[shard_idx] = float(meta[4])
                self.shard_snapshot_age[shard_idx] = float(meta[5])
                restored = meta[6] > 0.5
                self._shard_ring_restored[shard_idx] = restored
            v = float(meta[1])
            last = self.shard_inserted_last[shard_idx]
            if meta.size >= 7 and meta[4] < 1.0:
                # MID-RESTORE reply: the meter is the half-applied
                # ring's (zero until the chain lands). Reconciliation
                # must not see it — zeroing ``last`` here would make
                # the first post-restore reply re-add the restored
                # meter on top of the predecessor's contribution,
                # double-counting the whole pre-kill ingest.
                pass
            else:
                if v >= last:
                    self._shard_inserted_cum[shard_idx] += v - last
                elif not restored:
                    # Cold respawn (no ring snapshot): the meter
                    # restarted at zero. Keep the dead predecessor's
                    # contribution and count the new meter from
                    # scratch.
                    self._shard_inserted_cum[shard_idx] += v
                # else: the respawn RESTORED its ring, so the meter
                # CONTINUED from the snapshot — v is the pre-kill
                # meter minus the unsnapshotted tail, which was
                # already counted when first seen. Adding anything
                # here would double-count; regrowth past ``last``
                # resumes counting new steps above.
                self.shard_inserted_last[shard_idx] = v
                self._ep_return_sum += float(meta[2])
                self._ep_count += int(meta[3])

    def sample(
        self, batch_size: int, beta: float
    ) -> Optional[SampledBatch]:
        """One prioritized draw, rotating across shards. Walks every
        shard at most once: a dead shard costs its client's (short)
        retry budget and is skipped; a refilling shard is skipped for
        free. None when no shard can serve yet."""
        req = [
            np.asarray([int(batch_size)], np.int64),
            np.asarray([float(beta)], np.float64),
        ]
        n = len(self._clients)
        for k in range(n):
            shard_idx = (self._rr + k) % n
            with self._seq_lock:
                self._seq = (self._seq + 1) & ((1 << EPOCH_SHIFT) - 1)
                seq = self._seq
            # The tag's high bits carry this learner's fencing reign
            # (the server echoes the tag verbatim, so the seq match
            # still holds); the low 48 bits stay the per-draw seq.
            wire_seq = (self.epoch << EPOCH_SHIFT) | seq
            try:
                reply = self._client(shard_idx).sample_request(
                    wire_seq, req
                )
            except (ConnectionError, OSError):
                with self._meter_lock:
                    self.sample_failovers += 1
                continue
            batch = self._parse(shard_idx, reply)
            if batch is None:
                with self._meter_lock:
                    self.refills += 1
                continue
            with self._meter_lock:
                self.draws += 1
            # NEXT draw starts one past the shard that just served, so
            # the rotation spreads draws evenly across live shards.
            self._rr = (shard_idx + 1) % n
            return batch
        self._rr = (self._rr + 1) % n
        return None

    def sample_shard(
        self, shard_idx: int, batch_size: int, beta: float
    ) -> Optional[SampledBatch]:
        """One prioritized draw against ONE shard — the pipelined
        prefetcher's primitive (one worker thread per shard, each
        calling this concurrently; ``sample`` above is the serial
        rotation). No failover walk: a dead shard RAISES
        (``ConnectionError``/``OSError``, including the deliberate
        ``OperationInterrupted``) and the worker decides whether to
        reissue. ``None`` means the shard is refilling."""
        req = [
            np.asarray([int(batch_size)], np.int64),
            np.asarray([float(beta)], np.float64),
        ]
        with self._seq_lock:
            self._shard_seqs[shard_idx] = (
                self._shard_seqs[shard_idx] + 1
            ) & ((1 << EPOCH_SHIFT) - 1)
            seq = self._shard_seqs[shard_idx]
        wire_seq = (self.epoch << EPOCH_SHIFT) | seq
        try:
            reply = self._client(shard_idx).sample_request(
                wire_seq, req
            )
        except (ConnectionError, OSError):
            with self._meter_lock:
                self.sample_failovers += 1
            raise
        batch = self._parse(shard_idx, reply)
        with self._meter_lock:
            if batch is None:
                self.refills += 1
            else:
                self.draws += 1
        return batch

    def poll_meters(self) -> None:
        """Meter-refresh probe: a zero-row sample request, answered
        meta-only (budget/episode accounting without a served batch).
        The paced-out learner polls THIS instead of drawing-and-
        discarding full batches — a real draw costs the shard a
        sum-tree descent plus a batch copy over the wire, and would
        inflate the draw/served counters with work no update consumed.
        Advances the rotation one shard per call; failures are silent
        (the next real draw pays the failover accounting)."""
        k = self._rr
        self._rr = (self._rr + 1) % len(self._clients)
        with self._seq_lock:
            self._seq = (self._seq + 1) & ((1 << EPOCH_SHIFT) - 1)
            seq = self._seq
        try:
            reply = self._client(k).sample_request(
                (self.epoch << EPOCH_SHIFT) | seq,
                [np.asarray([0], np.int64), np.asarray([0.0])],
            )
        except (ConnectionError, OSError):
            return
        self._parse(k, reply)

    def update_priorities(
        self,
        shard_idx: int,
        ids: np.ndarray,
        indices: np.ndarray,
        td_abs: np.ndarray,
    ) -> None:
        try:
            self._client(shard_idx).prio_update(
                [
                    np.asarray(ids, np.int64),
                    np.asarray(indices, np.int64),
                    np.asarray(td_abs, np.float64),
                ],
                epoch=self.epoch,
            )
        except (ConnectionError, OSError):
            with self._meter_lock:
                self.prio_failures += 1

    def update_priorities_multi(
        self,
        shard_idx: int,
        entries: Sequence[
            Tuple[np.ndarray, np.ndarray, np.ndarray]
        ],
    ) -> None:
        """Coalesced write-back: one ``KIND_PRIO_UPDATE`` frame
        carrying every ``(ids, indices, td_abs)`` triple a tick
        produced for this shard. One frame == one epoch tag == one
        fence decision shard-side (all entries are from the same
        reign by construction). Best-effort like the single-entry
        path: a dead shard costs ``prio_failures`` and the stale
        priorities age out."""
        if not entries:
            return
        arrays: List[np.ndarray] = []
        for ids, indices, td_abs in entries:
            arrays.append(np.asarray(ids, np.int64))
            arrays.append(np.asarray(indices, np.int64))
            arrays.append(np.asarray(td_abs, np.float64))
        try:
            self._client(shard_idx).prio_update(
                arrays, epoch=self.epoch
            )
        except (ConnectionError, OSError):
            with self._meter_lock:
                self.prio_failures += 1

    def interrupt(self, shard_idx: Optional[int] = None) -> int:
        """Abort in-flight operations on one shard's client (or all
        of them) WITHOUT taking client locks: sets each client's
        interrupt flag and hard-closes its socket so a prefetch
        worker blocked in ``recv`` faults promptly with
        ``OperationInterrupted`` instead of riding out the retry
        deadline against a process that is gone (failover) or must
        not be drawn from any more (takeover drain). The aborted
        draw produced no reply, so the meter reconciliation never
        saw it — nothing to un-count. Returns how many clients had
        a live link to abort."""
        idxs = (
            range(len(self._clients))
            if shard_idx is None else [int(shard_idx)]
        )
        n = 0
        for k in idxs:
            c = self._clients[k]
            if c is None:
                continue
            intr = getattr(c, "interrupt", None)
            if intr is not None and intr():
                n += 1
        return n

    def rehome(self, shard_idx: Optional[int] = None) -> int:
        """Reset the (stale) link state of a shard the runner just
        respawned in place — or of every shard with ``None``. The old
        connection is half-open against a process that no longer
        exists: left alone, the first post-restore draw pays a fault
        on it and burns part (or all) of the SHORT per-draw retry
        deadline — spuriously counted as a failover against a shard
        that is actually back and serving. Dropping the link NOW (no
        goodbye frame — the new process must not mistake this for the
        learner's orderly drain) makes the next draw reconnect fresh.
        Returns how many links were reset."""
        idxs = (
            range(len(self._clients))
            if shard_idx is None else [int(shard_idx)]
        )
        n = 0
        for k in idxs:
            c = self._clients[k]
            if c is not None and c.reset():
                n += 1
        return n

    def meter_state(self) -> Tuple[List[float], List[float]]:
        """(cumulative, last-seen) per-shard ingest watermarks — the
        learner checkpoint's slice of this group, so a resumed run
        continues the global transition meter instead of re-deriving
        a misleading budget from respawned shards."""
        return (
            list(self._shard_inserted_cum),
            list(self.shard_inserted_last),
        )

    def restore_meter_state(
        self, cum: Sequence[float], last: Sequence[float]
    ) -> None:
        if len(cum) != len(self._clients) or (
            len(last) != len(self._clients)
        ):
            raise ValueError(
                f"meter state for {len(cum)} shards, group has "
                f"{len(self._clients)} (resume with the same "
                f"n_replay_shards)"
            )
        self._shard_inserted_cum = [float(x) for x in cum]
        self.shard_inserted_last = [float(x) for x in last]

    def inserted_total(self) -> int:
        """Aggregate transitions ever ingested across shards — the
        runner's env-step budget meter. Monotonic across shard
        restarts (see the reset detection in ``_parse``)."""
        return int(sum(self._shard_inserted_cum))

    def drain_episode_stats(self) -> Tuple[float, int]:
        out = (self._ep_return_sum, self._ep_count)
        self._ep_return_sum, self._ep_count = 0.0, 0
        return out

    def stats(self) -> Dict[str, float]:
        return {
            REPLAY + "draws": self.draws,
            REPLAY + "refills": self.refills,
            REPLAY + "sample_failovers": self.sample_failovers,
            REPLAY + "prio_failures": self.prio_failures,
            REPLAY + "inserted": self.inserted_total(),
        }

    def close(self) -> None:
        for c in self._clients:
            if c is None:
                continue
            try:
                c.close()
            except Exception:
                pass
