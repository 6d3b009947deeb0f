"""Profiling and timing harnesses.

Capability parity: the reference era's TensorBoard profiling and the
env-steps/sec counters that define its headline metric (SURVEY.md §5
"Tracing / profiling"; BASELINE.json:2). TPU-native mechanisms:
``jax.profiler`` traces (viewable in Perfetto/XProf) around training
iterations, and a wall-clock harness that separates compile time from
steady-state throughput. Every timing window ends in
``jax.block_until_ready``: dispatch is asynchronous, so a window
without it times the enqueue.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace: ``with trace("/tmp/tb"): run_iterations()``.

    View with XProf/TensorBoard or load the .trace.json.gz in Perfetto.
    """
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def time_iteration(
    step_fn: Callable,
    state,
    *,
    warmup: int = 1,
    iters: int = 10,
) -> Dict[str, float]:
    """Wall-clock a ``state -> (state, metrics)`` iteration function.

    Returns compile time (first call), steady-state seconds/iteration,
    and iterations/sec. The final state is NOT returned — use for
    measurement only, on a disposable state.
    """
    t0 = time.perf_counter()
    state, metrics = step_fn(state)
    jax.block_until_ready(metrics)
    compile_s = time.perf_counter() - t0

    for _ in range(max(0, warmup - 1)):
        state, metrics = step_fn(state)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step_fn(state)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    return {
        "compile_s": compile_s,
        "sec_per_iter": dt / iters,
        "iters_per_sec": iters / dt,
    }


def steps_per_sec(
    step_fn: Callable,
    state,
    steps_per_iteration: int,
    **kw,
) -> float:
    """Steady-state env-steps/sec of a fused training iteration —
    the headline metric's harness (BASELINE.json:2)."""
    t = time_iteration(step_fn, state, **kw)
    return steps_per_iteration * t["iters_per_sec"]
