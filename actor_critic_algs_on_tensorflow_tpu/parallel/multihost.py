"""Multi-host (pod / multi-slice) initialization helpers.

Capability parity: the reference scales across workers with
``tf.distribute`` over NCCL (BASELINE.json:5); multi-HOST TPU training
in JAX needs one extra step — ``jax.distributed.initialize`` — after
which the SAME single-controller programs in this package (shard_map
over a global mesh, psum on ICI/DCN) run unchanged: ``jax.devices()``
returns the global device set and XLA routes collectives over ICI
within a slice and DCN across slices (SURVEY.md §5 "Distributed
communication backend").

On a Cloud TPU pod slice, coordinator address/process metadata come
from the environment, so ``initialize()`` with no arguments suffices;
explicit arguments are for manual clusters (the IMPALA actor-host
deployment, SURVEY.md §3.3).
"""

from __future__ import annotations

import jax


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join this process to the multi-host runtime (idempotent)."""
    if is_initialized():
        return
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    jax.distributed.initialize(**kwargs)


def is_initialized() -> bool:
    """Whether this process already joined (or hosts) the multi-host
    runtime — another ``jax.distributed.initialize`` would raise."""
    return bool(jax.distributed.is_initialized())


def process_count() -> int:
    """Processes in the runtime (1 when not distributed-initialized)."""
    return jax.process_count()


def process_info() -> dict:
    """Host topology snapshot for logs/metrics — folded into the
    sharded learner's periodic log line (``extra_metrics``) so a
    multi-host run is attributable to its host from the log stream
    alone."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }
