"""On-device scalar metrics.

Design rule (SURVEY.md §5): metrics are computed on-device inside the
jitted step and fetched once per logging interval, so logging never
forces an early device sync. A ``Metrics`` dict maps name -> scalar
array; host-side consumption converts to floats in one transfer.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Mapping

import jax
import numpy as np

from actor_critic_algs_on_tensorflow_tpu.utils import metric_names

Metrics = Dict[str, jax.Array]


class TimeSplit:
    """Thread-safe named wall-clock accounting with window deltas.

    The learner's ingest pipeline attributes each second of an
    iteration to a named bucket (queue-wait / assemble / transfer /
    compute); ``add(name, s)`` accumulates, ``window()`` returns the
    per-name seconds since the previous ``window()`` call (one window
    per log interval), ``cumulative()`` returns lifetime totals;
    ``span(name)`` brackets a block (``add`` stays for a duration
    computed some other way). Keys
    are emitted with ``prefix`` so they sort next to each other in the
    log stream and TensorBoard.
    """

    def __init__(self, prefix: str = metric_names.PIPELINE):
        self._prefix = prefix
        self._lock = threading.Lock()
        self._acc: Dict[str, float] = {}
        self._last: Dict[str, float] = {}

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._acc[name] = self._acc.get(name, 0.0) + seconds

    @contextlib.contextmanager
    def span(self, name: str):
        """Time the block into ``name`` as ``add`` would, and show it
        in the profiler's trace under the log-row key (``prefix`` +
        ``name``): the column of the log and the span of the trace are
        one name. Outside a profiling session the annotation is a flag
        test."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(self._prefix + name):
                yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def cumulative(self) -> Dict[str, float]:
        with self._lock:
            return {
                f"{self._prefix}{k}": round(v, 4)
                for k, v in self._acc.items()
            }

    def window(self) -> Dict[str, float]:
        with self._lock:
            out = {}
            for k, v in self._acc.items():
                out[f"{self._prefix}{k}"] = round(
                    v - self._last.get(k, 0.0), 4
                )
                self._last[k] = v
            return out


class Ewma:
    """Bias-corrected exponential moving average (host-side scalar).

    The training-health sentinel's divergence detectors track the loss
    and gradient-norm trend with this: ``update(x)`` folds in a sample
    and returns the corrected mean, ``value`` reads it without
    updating (``None`` until the first sample).
    """

    def __init__(self, beta: float = 0.98):
        if not 0.0 < beta < 1.0:
            raise ValueError(f"beta must be in (0, 1), got {beta}")
        self.beta = beta
        self._acc = 0.0
        self.n = 0

    def update(self, x: float) -> float:
        self._acc = self.beta * self._acc + (1.0 - self.beta) * float(x)
        self.n += 1
        return self.value

    @property
    def value(self) -> float | None:
        if self.n == 0:
            return None
        return self._acc / (1.0 - self.beta**self.n)


def percentile(sorted_xs, q: float) -> float:
    """Nearest-rank percentile of an ALREADY-SORTED sequence
    (``q`` in [0, 100]); 0.0 for an empty one. Tiny and dependency-free
    so hot paths (the serving tick) can afford it per call."""
    n = len(sorted_xs)
    if n == 0:
        return 0.0
    idx = min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))
    return float(sorted_xs[idx])


class LatencyStats:
    """Bounded-reservoir latency recorder with p50/p99 summaries.

    The shared helper behind every latency-shaped report in the repo
    (serving-tier act latency, publish->visible notify latency, bench
    legs): ``add_ms(x)`` records one sample, ``summary(prefix)``
    returns ``{count, mean, p50, p99, max}`` in milliseconds. Keeps at
    most ``capacity`` samples — once full, new samples overwrite
    uniformly-random slots (reservoir sampling), so percentiles stay
    representative of the whole run at O(1) memory. Thread-safe."""

    def __init__(self, capacity: int = 4096, seed: int = 0):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self._capacity = capacity
        self._samples: list = []
        self._lock = threading.Lock()
        self._rng = np.random.RandomState(seed)
        self.count = 0
        self._sum = 0.0
        self._max = 0.0

    def reset(self) -> None:
        """Drop all samples (e.g. a bench excluding its warmup)."""
        with self._lock:
            self._samples = []
            self.count = 0
            self._sum = 0.0
            self._max = 0.0

    def add_ms(self, ms: float) -> None:
        ms = float(ms)
        with self._lock:
            self.count += 1
            self._sum += ms
            self._max = max(self._max, ms)
            if len(self._samples) < self._capacity:
                self._samples.append(ms)
            else:
                # Reservoir: keep each of the `count` samples with
                # equal probability capacity/count.
                j = int(self._rng.randint(0, self.count))
                if j < self._capacity:
                    self._samples[j] = ms

    def add_s(self, seconds: float) -> None:
        self.add_ms(seconds * 1e3)

    def summary(self, prefix: str = "") -> Dict[str, float]:
        with self._lock:
            xs = sorted(self._samples)
            count, total, mx = self.count, self._sum, self._max
        return {
            f"{prefix}count": count,
            f"{prefix}mean_ms": round(total / count, 4) if count else 0.0,
            f"{prefix}p50_ms": round(percentile(xs, 50), 4),
            f"{prefix}p99_ms": round(percentile(xs, 99), 4),
            f"{prefix}max_ms": round(mx, 4),
        }


def device_get_metrics(metrics: Mapping[str, jax.Array]) -> Dict[str, float]:
    """One host transfer for the whole metric dict."""
    flat = jax.device_get(dict(metrics))
    return {k: float(np.asarray(v)) for k, v in flat.items()}


def format_metrics(step: int, metrics: Mapping[str, float]) -> str:
    parts = [f"step={step}"]
    for k in sorted(metrics):
        v = metrics[k]
        parts.append(f"{k}={v:.4g}")
    return " ".join(parts)


class Stopwatch:
    """Wall-clock rate meter for env-steps/sec (the headline metric,
    BASELINE.json:2)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = time.perf_counter()
        self._steps0 = 0
        self._steps = 0

    def update(self, steps: int):
        self._steps = steps

    def rate(self) -> float:
        dt = time.perf_counter() - self._t0
        if dt <= 0:
            return 0.0
        return (self._steps - self._steps0) / dt

    def lap(self) -> float:
        r = self.rate()
        self._t0 = time.perf_counter()
        self._steps0 = self._steps
        return r
