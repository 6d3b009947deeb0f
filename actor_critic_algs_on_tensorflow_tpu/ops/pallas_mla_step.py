"""Pallas TPU kernel for one decode step of latent attention in its
absorbed form: one pass over a layer's cache of latents, and only over
the rows that exist.

``models/kimi_vl.py::latent_attention`` is the same thing in plain
array operations: ``scores = [q^; q^r] . cache`` over all ``L`` rows
with the rows beyond ``pos`` masked, a float32 softmax, then ``probs .
cache`` over all ``L`` rows again. XLA cannot fuse two products that
share an operand with a softmax between them, so every token streams
each layer's cache twice, whole. Here a block of envs walks its rows in
chunks; a chunk is resident in VMEM while its scores are taken AND
while it is weighed into the sum (the running maximum, normaliser and
sum of the chunked softmax in float32), so each row leaves HBM once:

    s = [q^; q^r] . chunk^T * scale       (rows beyond pos: -inf)
    m' = max(m, max s);  a = exp(m - m');  p = exp(s - m')
    l <- a l + sum p;  o <- a o + p . chunk[:, :rank];  m <- m'

and ``o / l`` after the last chunk. Per block of envs, the last chunk
any of them needs (from ``pos``) goes in as a scalar-prefetch operand: a
grid step beyond it computes nothing and its index map names the last
needed chunk again, which the pipeline holds already, so no block of
rows that lies wholly beyond ``pos`` is fetched. Within a chunk the
scores of the rows beyond an env's own ``pos`` are replaced by ``-inf``
and those rows enter the weighted sum as zeros: what an unwritten row
holds never reaches the result, not even times zero, for any ``pos
[B]``.

Precision is the plain form's: the cache's dtype (bfloat16 on the
chip) into both products, float32 sums, float32 scores, maximum,
exponent and normaliser. One rounding sits elsewhere: the plain form
rounds the NORMALISED probabilities for the second product, the
running form has to round ``exp(s - m')`` before the normaliser is
known; both are one rounding of a number's 8 bits.

The envs of a block are walked by a loop, ``GROUP_ENVS`` a pass as one
batched product, and the chunks by the grid: nothing is unrolled in the
traced body (PERF.md section 6, PR 28: a body unrolled over a block's
tiles cost seconds of tracing in a program that holds a dozen kernels).
The layers' caches come as one array ``[B, layers, L, width]`` (the
env axis leads, as the trainer shards a carry) and the kernel's index
map names the layer: a layer's cache as an array of its own (75 MB) the
compiler staged whole through VMEM and back every step (PERF.md
section 6, PR 32). The step form is never differentiated (``make_ppo``'s update
runs ``mla_seq``), so there is no VJP and asking for one raises. The
kernel compiles through Mosaic, which exists on TPU only; tests on the
CPU mesh run the same body with ``interpret=True``, and nothing here
picks the interpreter by itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Rows a grid step: one (16, 128) bfloat16 tile of scores a head block.
CHUNK = 128
# Envs a grid step: 16 x 128 rows of 576 (640 in VMEM) bfloat16 are
# 2.5 MiB, 5 MiB double-buffered, inside Mosaic's 16 MiB scoped default.
BLOCK_ENVS = 16
# Envs a pass of the body's loop, as one batched product each: the
# compiler overlaps their short chains (16 query rows an env leave the
# matrix unit waiting on latency; one env a pass ran at 0.187 ms a layer
# where eight run at 0.10, PERF.md section 6, PR 32).
GROUP_ENVS = 8


def fits(caches, rank: int, chunk: int = CHUNK) -> bool:
    """Whether caches ``[B, layers, L, rank + d_rope]`` take the kernel:
    the latent part whole lane tiles (the rope key splits off on a tile
    boundary), the rows whole chunks. The published widths do; a test
    preset's narrow cache does not."""
    return (rank % _LANES == 0 and caches.shape[-1] > rank
            and caches.shape[-2] % chunk == 0)


def _largest_divisor(n: int, at_most: int) -> int:
    return max(d for d in range(1, min(at_most, n) + 1) if n % d == 0)


def _last_chunks(pos, cache_len: int, envs: int, chunk: int):
    """Per block of ``envs`` envs, the last chunk any of them needs (a
    position past the cache needs its last row)."""
    pos = jnp.minimum(pos.astype(jnp.int32), cache_len - 1)
    return jnp.max(pos.reshape(-1, envs), 1) // chunk


def rows_read_share(pos, cache_len: int, *, block_envs=BLOCK_ENVS,
                    chunk=CHUNK):
    """The share of a cache's rows that the kernel's index map fetches
    at ``pos [B]``: per block of envs the chunks up to the one that
    holds the block's largest position, over ``cache_len``."""
    envs = _largest_divisor(pos.shape[0], block_envs)
    chunks = _last_chunks(pos, cache_len, envs, chunk) + 1
    return jnp.mean(chunks.astype(jnp.float32)) * (chunk / cache_len)


def _kernel(last_ref, pos_ref, q_ref, c_ref, o_ref, m_ref, l_ref, *,
            scale, rank, group):
    envs, chunk, _ = c_ref.shape
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(j <= last_ref[i])
    def _():
        first = j * chunk
        down = first + jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1)
        along = first + jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
        # per env: [h, c] . [rows, c]^T and [h, rows] . [rows, c]
        q_rows = (((2,), (2,)), ((0,), (0,)))
        p_rows = (((2,), (1,)), ((0,), (0,)))

        def envs_of_a_pass(g, carry):
            sl = pl.ds(pl.multiple_of(g * group, group), group)
            pos = pos_ref[sl]  # [group, 1, 1]
            q, c = q_ref[sl], c_ref[sl]
            s = jax.lax.dot_general(
                q[:, :, :rank], c[:, :, :rank], q_rows,
                preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                q[:, :, rank:], c[:, :, rank:], q_rows,
                preferred_element_type=jnp.float32,
            )
            # A row beyond pos may hold anything: its score is replaced,
            # and it enters the weighted sum as zeros, not times zero.
            s = jnp.where(along <= pos, s * scale, -jnp.inf)
            latent = c[:, :, :rank]
            latent = jnp.where(down <= pos, latent, jnp.zeros_like(latent))
            # Row 0 of chunk 0 is visible to every env, so from the
            # first chunk on the running maximum is finite.
            m = m_ref[sl]
            m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
            a = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_ref[sl] = a * l_ref[sl] + jnp.sum(p, -1, keepdims=True)
            o_ref[sl] = a * o_ref[sl] + jax.lax.dot_general(
                p.astype(c.dtype), latent, p_rows,
                preferred_element_type=jnp.float32,
            )
            m_ref[sl] = m_new
            return carry

        jax.lax.fori_loop(0, envs // group, envs_of_a_pass, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = o_ref[...] / l_ref[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attend(q, caches, pos, layer, scale, rank, block_envs, chunk,
            interpret):
    B, h, width = q.shape
    L = caches.shape[2]
    envs = _largest_divisor(B, block_envs)
    pos = jnp.minimum(pos.astype(jnp.int32), L - 1)
    last = _last_chunks(pos, L, envs, chunk)
    mean_rows = (L + chunk) // 2

    def by_env(i, j, last):
        return i, 0, 0

    in_vmem = -(-width // _LANES) * _LANES * caches.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank,
                          group=_largest_divisor(envs, GROUP_ENVS)),
        out_shape=jax.ShapeDtypeStruct((B, h, rank), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // envs, L // chunk),
            in_specs=[
                pl.BlockSpec((envs, 1, 1), by_env),
                pl.BlockSpec((envs, h, width), by_env),
                # this layer's cache; beyond the block's last needed
                # chunk that chunk again, which is not fetched again
                pl.BlockSpec(
                    (envs, None, chunk, width),
                    lambda i, j, last: (
                        i, layer, jnp.minimum(j, last[i]), 0
                    ),
                ),
            ],
            out_specs=pl.BlockSpec((envs, h, rank), by_env),
            scratch_shapes=[
                pltpu.VMEM((envs, h, 1), jnp.float32),
                pltpu.VMEM((envs, h, 1), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the chunk double-buffered at its width in VMEM (whole lane
            # tiles), and room for the query, the sum and the body
            vmem_limit_bytes=max(
                16 << 20, 2 * envs * chunk * in_vmem + (8 << 20)
            ),
        ),
        # what the compiler's scheduler may overlap with the kernel
        # rests on this (PERF.md section 6, PR 28); the rows fetched are
        # not known here, so: half the cache and half a chunk
        cost_estimate=pl.CostEstimate(
            flops=2 * B * h * mean_rows * (width + rank),
            transcendentals=B * h * mean_rows,
            bytes_accessed=(
                B * mean_rows * width * caches.dtype.itemsize
                + q.size * q.dtype.itemsize + 4 * B * h * rank
            ),
        ),
        interpret=interpret,
        name="mla_absorbed_step",
    )(last, pos.reshape(B, 1, 1), q, caches)


def _no_vjp(*_):
    raise NotImplementedError(
        "ops.pallas_mla_step has no VJP: the step form of latent "
        "attention is never differentiated by a trainer. Differentiate "
        "models.kimi_vl.latent_attention, or the sequence form mla_seq."
    )


_attend.defvjp(_no_vjp, _no_vjp)


def latent_attention(q, caches, layer, pos, *, scale, rank,
                     block_envs=BLOCK_ENVS, chunk=CHUNK, interpret=False):
    """``q [B, h, rank + d_rope]`` (the query carried into the latent
    space, and its rope part) over the rows ``0..pos [B]`` of layer
    ``layer``'s (a Python int) cache in ``caches [B, layers, L, rank +
    d_rope]``: ``softmax(q . cache^T * scale) . cache[..., :rank]`` as
    ``[B, h, rank]`` float32, what ``models.kimi_vl.latent_attention``
    returns on ``caches[:, layer]``. ``q`` is taken in the caches' dtype.

    ``block_envs``: envs a grid step, at most (the largest divisor of
    ``B`` that is no larger); ``chunk``: rows a grid step, a divisor of
    ``L``. ``interpret``: run the body in the Pallas interpreter (any
    backend; for tests). Unset, the kernel is compiled for the TPU, and
    on another backend that is an error. Not differentiable: see the
    module's docstring."""
    if caches.shape[2] % chunk:
        raise ValueError(
            f"cache of {caches.shape[2]} rows is not whole chunks of {chunk}"
        )
    return _attend(q.astype(caches.dtype), caches, pos, layer, float(scale),
                   rank, block_envs, chunk, interpret)
