"""Pallas TPU kernel for one decode step of the gated delta rule: one
pass over the state.

``models/qwen3_next.py::gated_delta_step`` is the recurrence in plain
array operations, and XLA runs it as two passes over the state ``S [B,
h, d_k, d_v]``: a reduce pass that reads ``S`` for ``S^T k`` and ``S^T
q``, then an update pass that reads it again and writes it. No fusion
can merge the two (the update needs the finished reduction over the
same elements), so every token moves the state three times. Here a
block of (env, head) tiles of ``S`` is resident in VMEM while both
happen, and each element leaves HBM once and returns once, to the same
buffer (``input_output_aliases``):

    S <- S * decay;  s_k = S^T k;  s_q = S^T q
    delta = (v - s_k) * beta;  o = s_q + (q . k) delta
    S <- S + k delta^T

``decay`` is the per-(env, head) scalar ``exp(g)``, times 0 where the
episode was reset (``keep``): the reset is folded in here, so zeroing
the state costs no pass either. Float32 on the vector unit throughout, as the
plain form; only the order of the float32 sums differs from it.

A tile's contractions run over ``d_k``, the tile's sublane axis, so
``q`` and ``k`` are wanted as columns spread along the lanes: a tile
takes its row of each, spreads it down the sublanes and transposes it
(the transpose unit is otherwise idle). The tiles of a block are walked
by a loop; unrolled whole, the body's thousand operations a kernel were
seconds of tracing in a program that holds six of them (PERF.md
section 6, PR 28). On the chip the kernel runs at the
rate of a kernel that only scales the state by its decay: the DMA, not
the arithmetic, is what takes the time.

The step form is never differentiated (``make_ppo``'s update runs the
sequence form, ``chunk_gated_delta_rule``), so there is no VJP and
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
# Envs a grid step: one env's 32 heads are 2 MiB in and 2 MiB out, 8 MiB
# double-buffered, inside Mosaic's 16 MiB scoped default.
BLOCK_ENVS = 1


def fits(S) -> bool:
    """Whether a state of this shape tiles the vector unit: the kernel
    is for the published widths, not for a test preset's 8 x 8 heads."""
    return S.shape[-1] % _LANES == 0 and S.shape[-2] % _SUBLANES == 0


def _kernel(scalars_ref, S_ref, k_ref, q_ref, v_ref, S_out_ref, o_ref):
    tiles, dk, dv = S_ref.shape
    first = pl.program_id(0) * tiles
    # Two tiles a pass of the loop: one's transposes and sums overlap
    # the other's (one a pass ran 10 % slower, four no faster).
    unroll = 2 if tiles % 2 == 0 else 1

    def tile(j):
        decay = scalars_ref[0, first + j]
        beta = scalars_ref[1, first + j]
        q_dot_k = scalars_ref[2, first + j]
        # [1, d_k] -> [d_k, d_v], constant along the lanes
        k_cols = jnp.broadcast_to(k_ref[pl.ds(j, 1), :], (dv, dk)).T
        q_cols = jnp.broadcast_to(q_ref[pl.ds(j, 1), :], (dv, dk)).T
        S = S_ref[j] * decay
        s_k = jnp.sum(S * k_cols, axis=0, keepdims=True)
        s_q = jnp.sum(S * q_cols, axis=0, keepdims=True)
        delta = (v_ref[pl.ds(j, 1), :] - s_k) * beta
        o_ref[pl.ds(j, 1), :] = s_q + q_dot_k * delta
        S_out_ref[j] = S + k_cols * delta

    def tiles_of_a_pass(i, carry):
        for u in range(unroll):
            tile(i * unroll + u)
        return carry

    jax.lax.fori_loop(0, tiles // unroll, tiles_of_a_pass, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _step(S, q, k, v, decay, beta, block_envs, interpret):
    B, h, dk, dv = S.shape
    envs = max(d for d in range(1, min(block_envs, B) + 1) if B % d == 0)
    tiles, steps = envs * h, B // envs
    scalars = jnp.stack([decay, beta, jnp.sum(q * k, -1)]).reshape(3, B * h)
    S_new, o = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B * h, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((B * h, dv), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((tiles, dk, dv), lambda i, _: (i, 0, 0)),
                pl.BlockSpec((tiles, dk), lambda i, _: (i, 0)),
                pl.BlockSpec((tiles, dk), lambda i, _: (i, 0)),
                pl.BlockSpec((tiles, dv), lambda i, _: (i, 0)),
            ],
            out_specs=(
                pl.BlockSpec((tiles, dk, dv), lambda i, _: (i, 0, 0)),
                pl.BlockSpec((tiles, dv), lambda i, _: (i, 0)),
            ),
        ),
        # operand 0 is the prefetched scalars; the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state's block in and out, each double-buffered, and
            # room for the small operands and the body's temporaries
            vmem_limit_bytes=max(16 << 20, 16 * tiles * dk * dv + (4 << 20)),
        ),
        # what the compiler's scheduler may overlap with the kernel (its
        # prefetches of the next products' weights) rests on this
        cost_estimate=pl.CostEstimate(
            flops=7 * S.size, transcendentals=0,
            bytes_accessed=4 * (2 * S.size + 2 * k.size + 2 * v.size),
        ),
        interpret=interpret,
        name="gdn_state_step",
    )(scalars, S.reshape(B * h, dk, dv), k.reshape(B * h, dk),
      q.reshape(B * h, dk), v.reshape(B * h, dv))
    return S_new.reshape(S.shape), o.reshape(B, h, dv)


def _no_vjp(*_):
    raise NotImplementedError(
        "ops.pallas_delta_step has no VJP: the step form of the gated "
        "delta rule is never differentiated by a trainer. Differentiate "
        "models.qwen3_next.gated_delta_step, or the sequence form "
        "chunk_gated_delta_rule."
    )


_step.defvjp(_no_vjp, _no_vjp)


def gated_delta_step(S, q, k, v, g, beta, keep, *, block_envs=BLOCK_ENVS,
                     interpret=False):
    """One step of the recurrence on ``S [B, h, d_k, d_v]``, float32:
    ``q, k [B, h, d_k]``, ``v [B, h, d_v]``, ``g, beta [B, h]``, ``keep
    [B]`` (0 where the env starts over, else 1). What
    ``models.qwen3_next.gated_delta_step(S * keep, q, k, v, g, beta)``
    returns: ``(S_new, o [B, h, d_v])``, ``S_new`` in ``S``'s buffer
    where the caller donates it.

    ``block_envs``: envs a grid step, at most (the largest divisor of
    ``B`` that is no larger). ``interpret``: run the body in the Pallas
    interpreter (any backend; for tests). Unset, the kernel is compiled
    for the TPU, and on another backend that is an error. Not
    differentiable: see the module's docstring."""
    S, q, k, v, g, beta, keep = (
        jnp.asarray(x, jnp.float32) for x in (S, q, k, v, g, beta, keep)
    )
    decay = jnp.exp(g) * keep[:, None]
    return _step(S, q, k, v, decay, beta, block_envs, interpret)
