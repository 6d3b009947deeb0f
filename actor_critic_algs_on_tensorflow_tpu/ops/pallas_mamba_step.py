"""Pallas TPU kernel for one decode step of the Mamba-2 recurrence: one
pass over the state, in place in the array that holds every layer's.

``models/granite_hybrid.py::mamba_step`` is the recurrence in plain
array operations, and XLA runs it as two passes over a layer's state ``S
[B, h, p, n]``: a multiply-add that reads it and writes it through a
dynamic-update-slice into the carry's one state array, then the
reduction against ``C``, which reads what was just written. No fusion
can merge the two (the first writes through the slice, the second
reduces over what it wrote), so every token moves the state three
times. Here an env's heads of the named layer are resident in VMEM
while both happen, and each element leaves HBM once and returns once,
to the same buffer (``input_output_aliases``):

    S <- a S + (dt x) (x) B;   y = S C

The carry keeps the states of all Mamba layers in ONE array ``[B,
layers, h, p, n]`` (a layer's array of its own was staged whole through
VMEM every step, PERF.md section 6, PR 32). The kernel takes that whole
array; the layer is a scalar-prefetch operand that the index map reads,
so the other layers' bytes are never touched, and every layer of a
model shares one trace and one lowering (the call is a ``jax.jit`` of
its own).

``a`` is the per-(env, head) decay ``exp(dt A)``, times 0 where the
episode was reset: the reset is folded in here, so zeroing the state
costs no pass either. A decay of exactly 0 gives an empty state
whatever the state held, ``inf`` and ``nan`` included (the plain form's
``0 * inf`` is ``nan``). Float32 on the vector unit throughout, as the
plain form; only the order of the float32 sums differs from it.

As stored, a head's tile is ``[p, n]``: the read-out against ``C``
reduces along the lanes and ``dt x`` is wanted down the sublanes. The
kernel walks tiles of 128 rows (``128 / p`` heads): a tile takes its
row of ``dt x``, spreads it down the sublanes and transposes it, and
transposes its products with ``C`` so that their sum runs down the
sublanes and ``y`` comes out as a row (the transpose unit is otherwise
idle; ``ops/pallas_delta_step.py`` pays the same two transposes a 64
KiB tile). The tiles are walked by a loop, not unrolled (PERF.md
section 6, PR 28). On the chip the kernel runs at the rate of a kernel
that only scales the state: the DMA, not the arithmetic, is what takes
the time (PERF.md section 6, PR 38).

The step form is never differentiated (``make_ppo``'s update runs the
sequence form, ``chunk_state_space_scan``), so there is no VJP and
asking for one raises. The kernel compiles through Mosaic, which exists
on TPU only; tests on the CPU mesh run the same body with
``interpret=True``, and nothing here picks the interpreter by itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128     # last-dim tile width
_SUBLANES = 8    # f32 second-to-last tile width
_ROWS = 128      # rows of the state a tile: with the lanes, a square transpose


def fits(state) -> bool:
    """Whether a state ``[..., h, p, n]`` tiles the vector unit as the
    kernel walks it: ``n`` one lane tile, ``p`` whole sublane tiles that
    divide 128, and the heads whole tiles of 128 rows. The published
    widths do (64 heads of 64 x 128); a test preset's narrow state does
    not."""
    h, p, n = state.shape[-3:]
    return (n == _LANES and p % _SUBLANES == 0 and _ROWS % p == 0
            and (h * p) % _ROWS == 0)


def _kernel(layer_ref, a_ref, S_ref, dx_ref, B_ref, C_ref, S_out_ref,
            y_ref, *, heads):
    """``heads``: of a tile's rows."""
    del layer_ref  # read by the index maps
    tiles, rows, _ = S_ref.shape
    p = rows // heads
    first = pl.program_id(0) * tiles
    B_row, C_row = B_ref[...], C_ref[...]                   # [1, n]
    # Two tiles a pass of the loop: one's transposes overlap the
    # other's sums (one a pass ran at 0.333 ms a layer where two run at
    # 0.213 and four at 0.212, PERF.md section 6, PR 38).
    unroll = 2 if tiles % 2 == 0 else 1

    def tile(j):
        # [1, rows] -> [rows, rows], constant along the lanes
        dx_cols = jnp.broadcast_to(dx_ref[pl.ds(j, 1), :], (rows, rows)).T
        read = []
        for u in range(heads):
            a = a_ref[(first + j) * heads + u]
            of_head = slice(u * p, (u + 1) * p)
            S = jnp.where(a == 0.0, 0.0, S_ref[j, of_head, :] * a) + (
                dx_cols[of_head] * B_row
            )
            S_out_ref[j, of_head, :] = S
            read.append(S * C_row)
        # [rows, n] -> [n, rows]: the sum over n runs down the sublanes
        y_ref[pl.ds(j, 1), :] = jnp.sum(
            jnp.concatenate(read, 0).T, axis=0, keepdims=True
        )

    def tiles_of_a_pass(i, carry):
        for u in range(unroll):
            tile(i * unroll + u)
        return carry

    jax.lax.fori_loop(0, tiles // unroll, tiles_of_a_pass, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(layer, a, state, dx, B, C, *, interpret):
    """``layer [1]`` int32, ``a [b * h]``, ``state [b, layers, tiles,
    128, n]``, ``dx [b, tiles, 128]``, ``B, C [b, 1, n]``."""
    b, _, tiles, rows, n = state.shape
    of_a_layer = b * tiles * rows * n  # elements

    # this layer's heads of one env: 2 MiB at the published widths, 8
    # MiB in and out double-buffered, inside Mosaic's 16 MiB scoped
    # default
    of_layer = pl.BlockSpec(
        (None, None, tiles, rows, n),
        lambda i, layer, a: (i, layer[0], 0, 0, 0),
    )

    def of_env(*block):
        return pl.BlockSpec((None, *block), lambda i, *_: (i, 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, heads=a.size // (b * tiles)),
        out_shape=(
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
            jax.ShapeDtypeStruct(dx.shape, jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                of_layer, of_env(tiles, rows), of_env(1, n), of_env(1, n),
            ],
            out_specs=(of_layer, of_env(tiles, rows)),
        ),
        # operands 0 and 1 are the prefetched scalars; the state is 2
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state's block in and out, each double-buffered, and
            # room for the small operands and the body's temporaries
            vmem_limit_bytes=max(
                16 << 20, 16 * tiles * rows * n + (4 << 20)
            ),
        ),
        # what the compiler's scheduler may overlap with the kernel (its
        # prefetches of the next products' weights) rests on this
        cost_estimate=pl.CostEstimate(
            flops=5 * of_a_layer, transcendentals=0,
            bytes_accessed=4 * (
                2 * of_a_layer + 2 * dx.size + B.size + C.size + a.size
            ),
        ),
        interpret=interpret,
        name="mamba_state_step",
    )(layer, a, state, dx, B, C)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 7))
def _step(state, layer, x, dt, a, B, C, interpret):
    b, layers, h, p, n = state.shape
    tiles = h * p // _ROWS
    new, y = _call(
        jnp.full((1,), layer, jnp.int32), a.reshape(b * h),
        state.reshape(b, layers, tiles, _ROWS, n),
        (dt[..., None] * x).reshape(b, tiles, _ROWS),
        B[:, None], C[:, None], interpret=interpret,
    )
    return new.reshape(state.shape), y.reshape(b, h, p)


def _no_vjp(*_):
    raise NotImplementedError(
        "ops.pallas_mamba_step has no VJP: the step form of the "
        "state-space recurrence is never differentiated by a trainer. "
        "Differentiate models.granite_hybrid.mamba_step, or the sequence "
        "form chunk_state_space_scan."
    )


_step.defvjp(_no_vjp, _no_vjp)


def mamba_step(state, layer, x, dt, a, B, C, *, interpret=False):
    """One step of the recurrence on layer ``layer`` (a Python int) of
    ``state [b, layers, h, p, n]``, float32: ``x [b, h, p]``, ``dt`` and
    the decay ``a [b, h]`` (the caller folds a reset into it: 0 where
    the env starts over), ``B, C [b, n]``. What
    ``models.granite_hybrid.mamba_step(state[:, layer], x, dt, a, B,
    C)`` returns, the new state written where the old one stood:
    ``(state, y [b, h, p])``, ``state`` in the argument's buffer where
    the caller donates it and no other layer's bytes read or written.

    ``interpret``: run the body in the Pallas interpreter (any backend;
    for tests). Unset, the kernel is compiled for the TPU, and on
    another backend that is an error. Not differentiable: see the
    module's docstring."""
    state, x, dt, a, B, C = (
        jnp.asarray(v, jnp.float32) for v in (state, x, dt, a, B, C)
    )
    return _step(state, layer, x, dt, a, B, C, interpret)
