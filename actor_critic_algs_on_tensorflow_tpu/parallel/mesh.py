"""Device-mesh construction and axis conventions.

Capability parity: the reference scales with synchronous data-parallel
gradient averaging over NCCL via ``tf.distribute.MirroredStrategy``
(BASELINE.json:5). The TPU-native analog is a 1-D ``jax.sharding.Mesh``
over the ICI-connected chips with ``lax.pmean`` gradient averaging
inside ``shard_map`` — XLA emits the all-reduce on ICI; no hand-written
collectives (SURVEY.md §2.2).

Axis names:
  - ``data``: data-parallel axis (actors/envs sharded, params replicated).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with this repo's default (``check_vma`` off:
    the local programs pmean/psum what crosses devices themselves)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def device_line() -> str:
    """``device: platform=… device_kind=… n=…`` for the backend this
    process runs on (initialises the backend)."""
    devices = jax.devices()
    return (
        f"device: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind} n={len(devices)}"
    )


def pin_process_to_cpu(who: str) -> None:
    """First statement of every process that must never claim the chip
    its parent holds (actors, env shims, replay servers): one process
    owns a chip, and a second one that reaches for it fails or hangs.
    The platform is selected through ``jax.config`` before first
    backend use, so an inherited ``JAX_PLATFORMS`` cannot override it,
    and the process says which device it came up on."""
    jax.config.update("jax_platforms", "cpu")
    print(f"[{who}] {device_line()}", flush=True)


def make_mesh(num_devices: int | None = None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D data-parallel mesh over the first ``num_devices`` devices."""
    devices = jax.devices()
    if num_devices is None:
        num_devices = len(devices)
    if num_devices > len(devices):
        raise ValueError(
            f"requested {num_devices} devices, have {len(devices)}"
        )
    return Mesh(np.asarray(devices[:num_devices]), (axis_name,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def spans_processes(mesh: Mesh) -> bool:
    """Whether ``mesh`` includes devices of more than one process —
    the multi-host (per-host-sharded learner) regime, where host
    values become global arrays via the process-local constructors
    instead of a plain ``device_put``."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def put_replicated_tree(tree, mesh: Mesh):
    """Place a host pytree fully replicated on ``mesh``, multi-host
    aware.

    Single-process meshes (and abstract tracing, e.g. ``eval_shape``
    of an init program) take the ordinary ``device_put``. A mesh that
    spans processes instead wraps each (identical-on-every-host —
    same seed, same config) concrete leaf with
    ``jax.make_array_from_process_local_data``: every process
    contributes its own replica and no cross-process transfer happens,
    which is both the portable path on this jax line and the only one
    that never asks ``device_put`` to address a non-addressable
    device."""
    sharding = NamedSharding(mesh, P())
    leaves = jax.tree_util.tree_leaves(tree)
    concrete = all(
        isinstance(x, (np.ndarray, np.generic, jax.Array, int, float, bool))
        and not isinstance(x, jax.core.Tracer)
        for x in leaves
    )
    if not spans_processes(mesh) or not concrete:
        return jax.device_put(tree, sharding)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x), np.shape(x)
        ),
        tree,
    )


def batch_sharded(mesh: Mesh, axis_name: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch/env) axis across the mesh."""
    return NamedSharding(mesh, P(axis_name))


def shard_batch_specs(tree, axis_name: str = DATA_AXIS):
    """PartitionSpec pytree: every leaf sharded on its leading axis.

    Scalar leaves (e.g. a host-env ordering token) cannot shard on a
    leading axis — they are replicated instead.
    """
    return jax.tree_util.tree_map(
        lambda x: P(axis_name) if len(getattr(x, "shape", ())) else P(), tree
    )


def replicated_specs(tree):
    return jax.tree_util.tree_map(lambda _: P(), tree)


def put_by_specs(tree, specs, mesh: Mesh):
    """``device_put`` a pytree onto the mesh per a PartitionSpec pytree.

    Host-built states can hold the SAME array object in two leaves
    (e.g. ``FrameStack.reset`` returns its frame buffer as both
    ``env_state.frames`` and ``obs``). ``device_put`` preserves that
    aliasing when no resharding copy is needed (1-device mesh), and a
    donated jit then fails with "donate the same buffer twice" — so
    repeated leaves are copied before placement.
    """
    seen: set[int] = set()

    def _unalias(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            if id(x) in seen:
                return (
                    x.copy() if isinstance(x, np.ndarray)
                    else jax.numpy.array(x, copy=True)
                )
            seen.add(id(x))
        return x

    tree = jax.tree_util.tree_map(_unalias, tree)
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(tree, shardings)


def device_count(mesh: Mesh | None) -> int:
    return int(np.prod(mesh.devices.shape)) if mesh is not None else 1
