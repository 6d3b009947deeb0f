"""Pallas TPU kernel for the backward linear recurrence shared by
GAE(lambda), V-trace, and discounted returns.

Capability parity: the reference's temporal-credit ops are Python/TF
loops; TPU-first they are one fused on-chip recurrence. XLA's
``lax.scan`` already fuses well, but it materialises its carry through
HBM-visible loop state per step; this kernel keeps the whole ``[T, B]``
problem resident in VMEM and walks the time axis in-register, one
128-lane batch block per grid step (see pallas_guide.md: grid/BlockSpec,
fori_loop, min f32 tile (8, 128)).

The recurrence (identical shape for all three consumers):

    acc_t = delta_t + decay_t * acc_{t+1},    acc_T = init

  * GAE:       delta = TD-error,            decay = gamma * lam * (1 - done)
  * V-trace:   delta = rho * TD-error,      decay = gamma * (1-done) * c
  * n-step:    delta = reward,              decay = gamma * (1 - done),
               init  = bootstrap value

The kernel compiles through Mosaic, which exists on TPU only. Tests on
the CPU mesh exercise the same kernel body by passing
``interpret=True``; nothing here picks the interpreter by itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128      # last-dim tile width
_SUBLANES = 8     # f32 second-to-last tile width


def _kernel(deltas_ref, decay_ref, out_ref):
    t_rows = deltas_ref.shape[0]

    def body(i, acc):
        t = t_rows - 1 - i
        acc = deltas_ref[t, :] + decay_ref[t, :] * acc
        out_ref[t, :] = acc
        return acc

    jax.lax.fori_loop(
        0,
        t_rows,
        body,
        jnp.zeros((deltas_ref.shape[1],), deltas_ref.dtype),
    )


def linear_backward_scan(
    deltas: jax.Array,
    decay: jax.Array,
    init: jax.Array | None = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """``acc_t = deltas_t + decay_t * acc_{t+1}`` over axis 0, fused.

    ``deltas``/``decay``: ``[T, ...]`` (any trailing shape, f32).
    ``init``: optional ``[...]`` starting accumulator (``acc_T``).
    ``interpret``: run the kernel body in the Pallas interpreter (any
    backend; for tests). Unset, the kernel is compiled for the TPU, and
    on another backend that is an error.
    Returns ``[T, ...]`` accumulators.
    """
    out_dtype = jnp.asarray(deltas).dtype
    # Accumulate in f32 regardless of input dtype (bf16 recurrences lose
    # precision fast); cast back so the flag is a pure perf switch.
    deltas = jnp.asarray(deltas, jnp.float32)
    decay = jnp.asarray(decay, jnp.float32)
    t_len = deltas.shape[0]
    batch_shape = deltas.shape[1:]
    n = 1
    for d in batch_shape:
        n *= d
    d2 = deltas.reshape(t_len, n)
    g2 = decay.reshape(t_len, n)

    # Fold `init` in as an extra first-processed row: acc after that row
    # is exactly init (delta=init, decay=0).
    init_row = (
        jnp.zeros((1, n), jnp.float32)
        if init is None
        else jnp.asarray(init, jnp.float32).reshape(1, n)
    )
    d2 = jnp.concatenate([d2, init_row], axis=0)
    g2 = jnp.concatenate([g2, jnp.zeros((1, n), jnp.float32)], axis=0)

    # Pad to TPU f32 tile multiples: rows to 8, lanes to 128. Padded
    # rows sit AFTER the init row in time, i.e. processed before it
    # with decay 0 — they cannot leak into real rows.
    t_pad = (-d2.shape[0]) % _SUBLANES
    n_pad = (-n) % _LANES
    d2 = jnp.pad(d2, ((0, t_pad), (0, n_pad)))
    g2 = jnp.pad(g2, ((0, t_pad), (0, n_pad)))
    t_rows, n_cols = d2.shape

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((t_rows, n_cols), jnp.float32),
        grid=(n_cols // _LANES,),
        in_specs=[
            pl.BlockSpec((t_rows, _LANES), lambda i: (0, i)),
            pl.BlockSpec((t_rows, _LANES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((t_rows, _LANES), lambda i: (0, i)),
        interpret=interpret,
    )(d2, g2)
    return out[:t_len, :n].reshape((t_len,) + batch_shape).astype(out_dtype)
