"""Pallas TPU kernel for the inverse of many small unit-lower-triangular
matrices: ``T = (I + a)^{-1}``, ``a [..., c, c]`` strictly lower.

``models/qwen3_next.py::chunk_gated_delta_rule`` solves one such system a
chunk, an env and a head: 4,096 matrices of 64 x 64 a minibatch at the
timed shape. XLA's ``triangular_solve`` runs it on a TPU as the custom
call ``InvertDiagBlocksLowerTriangular``, 11-13 ms a call of row
substitution, one matrix after another, on a TPU v5e; this kernel takes
0.8 ms there with its relayouts (PERF.md section 6). Here the
substitution runs on 128 matrices at once: the matrices lie along the
lanes (``[c, c, N]``, an XLA copy before the kernel and one after it),
so that row ``i`` of a block of them is ``c / 8`` vector registers and

    T_i = e_i - sum_{j < i} a_ij T_j

is a multiply-add of whole registers for every ``j``, ``a_ij`` one row
of lanes spread down the sublanes. Row ``i`` of a unit-lower inverse is
zero beyond column ``i``, so a row of the ``t``-th tile of 8 rows
touches only its first ``8 (t + 1)`` columns: the rows of a tile are
walked by a loop with that many columns, the ``j`` before them by a loop
over tiles of 8 rows. The block's output starts at zero, so that a row
not yet written adds nothing: the loop over ``j`` runs to the end of the
row's tile and needs no bound of its own.

Float32 on the vector unit throughout: the same substitution as the
plain ``solve_triangular`` and no product on the MXU, so nothing is
rounded to bfloat16 and only the order of the float32 sums differs from
it. The kernel compiles through Mosaic, which exists on TPU only; tests
on the CPU run the same body with ``interpret=True``, and nothing here
picks the interpreter by itself. It has no derivative: the caller's
custom VJP differentiates through the inverse it returns.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_LANES = 128     # matrices a vector register holds side by side
_SUBLANES = 8    # float32 rows of a vector register
_MAX_WIDTH = 128
# Matrices a grid step: one lane tile (256 ran 2 % faster in isolation on
# a TPU v5e, PERF.md section 6; the cell was measured at 128).
_BLOCK = _LANES


def fits(a) -> bool:
    """Whether ``a [..., c, c]`` tiles the vector unit as the kernel
    walks it: ``c`` whole sublane tiles, at most 128 (a block in and out,
    double-buffered, then holds 32 MiB), and the matrices whole lane
    tiles. The published widths do (64 x 64, 4,096 matrices a minibatch);
    a test preset's 8 x 8 chunks of a few envs and heads do not."""
    if a.ndim < 3 or a.shape[-1] != a.shape[-2]:
        return False
    c = a.shape[-1]
    return (c % _SUBLANES == 0 and c <= _MAX_WIDTH
            and math.prod(a.shape[:-2]) % _LANES == 0)


def _kernel(a_ref, t_ref):
    """``a [c, c, m]`` -> ``t [c, c, m]``: row, column, matrix."""
    c, _, m = a_ref.shape
    t_ref[...] = jnp.zeros(t_ref.shape, _F32)
    for tile in range(c // _SUBLANES):
        width = _SUBLANES * (tile + 1)

        def row(r, carry, tile=tile, width=width):
            i = _SUBLANES * tile + r

            def earlier(jt, acc):
                first = pl.multiple_of(jt * _SUBLANES, _SUBLANES)
                a_rows = a_ref[i, pl.ds(first, _SUBLANES), :]      # [8, m]
                for s in range(_SUBLANES):
                    acc = acc + a_rows[s:s + 1] * t_ref[first + s, :width, :]
                return acc

            acc = jax.lax.fori_loop(0, tile + 1, earlier,
                                    jnp.zeros((width, m), _F32))
            column = jax.lax.broadcasted_iota(jnp.int32, (width, m), 0)
            t_ref[i, :width, :] = jnp.where(column == i, 1.0, 0.0) - acc
            return carry

        jax.lax.fori_loop(0, _SUBLANES, row, 0)


def _multiply_adds(c: int) -> int:
    """Multiply-adds a matrix, as the kernel walks it: a row of the
    ``t``-th tile takes ``8 (t + 1)`` rows of ``8 (t + 1)`` columns."""
    tiles = c // _SUBLANES
    return sum(_SUBLANES * (_SUBLANES * (t + 1)) ** 2 for t in range(tiles))


def inverse(a, *, interpret=False):
    """``(I + a)^{-1}`` of every ``a [..., c, c]``, float32; ``a``
    strictly lower (what lies on and above its diagonal is not read, as
    long as it is finite). ``interpret``: run the body in the
    Pallas interpreter (any backend; for tests). Unset, the kernel is
    compiled for the TPU, and on another backend that is an error."""
    a = jnp.asarray(a, _F32)
    c = a.shape[-1]
    n = math.prod(a.shape[:-2])
    lanes = jnp.transpose(a.reshape(n, c, c), (1, 2, 0))
    spec = pl.BlockSpec((c, c, _BLOCK), lambda k: (0, 0, k))
    t = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((c, c, n), _F32),
        grid=(n // _BLOCK,),
        in_specs=[spec],
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a block in and out, each double-buffered, and the loops'
            # registers
            vmem_limit_bytes=max(16 << 20, 16 * c * c * _BLOCK + (4 << 20)),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * _multiply_adds(c) * n, transcendentals=0,
            bytes_accessed=2 * 4 * a.size,
        ),
        interpret=interpret,
        name="unit_lower_inverse",
    )(lanes)
    return jnp.transpose(t, (2, 0, 1)).reshape(a.shape)
