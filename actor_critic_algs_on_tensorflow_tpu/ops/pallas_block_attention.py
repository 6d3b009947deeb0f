"""Pallas TPU kernels for grouped-query attention over a sampling
trajectory under the block-diffusion mask, forward and backward, with
the scores in VMEM from the first product to the last.

``models/decoder.py::attend`` is the same thing in plain array
operations: float32 scores ``[b, heads, n, n]``, the mask, a float32
softmax, the probabilities rounded to the products' dtype, the weighted
sum. XLA writes the scores to HBM and every one of those steps, the
layer's recomputation under ``jax.checkpoint`` and the backward of all
of it reads or writes them there again (680 MB a layer and minibatch at
``ppo-sdar-turns``' 16 x 576 positions: PERF.md section 6, PR 34). Here
a grid step holds ONE sequence's keys and values for ONE key/value head
whole (``n`` rows of ``head_dim``: 147 KB each at 576 x 128 bfloat16)
beside the queries of the group's heads at every position (1.2 MB: one
large block a grid step, because 192-row blocks left the kernel waiting
on its DMAs two thirds of the time) and walks the queries in tiles of
``TILE_Q`` positions with the group's heads folded into rows; scores,
mask, softmax and the second product happen there, and only ``[b, n,
heads * head_dim]`` leaves, in the products' dtype (the output
projection rounds it there anyway).

The mask is never an operand. ``step [n]`` (a position's pass,
non-decreasing) and ``key_commit [b, n]`` (whether the key's pass was a
commit) say it all::

    visible(q, k) = step[k] == step[q] or (step[k] < step[q] and commit[k])

and a tile's mask is rebuilt from them in VMEM. A causal mask is the
case ``step = arange(n)``, ``key_commit`` all true. Two things follow
from ``step`` alone, per tile of queries, and go in as scalar-prefetch
operands: the LAST chunk of ``CHUNK`` keys the tile can see (a query
sees no key of a later pass, so the chunks beyond are not computed:
``score_tiles_computed_share``), and the first chunk that is not wholly
of earlier passes (before it ``visible`` is ``commit[k]`` alone, one
added row in place of two compares and a select a score).

Precision is the plain form's, rounding for rounding: operands in
``dtype``, float32 sums; scores times ``head_dim ** -0.5``, maximum,
exponent and normaliser in float32; the NORMALISED probabilities rounded
to ``dtype`` for the second product. The whole key range is resident,
so the softmax is the exact two-pass one, not a running one, and no
rounding moves (as it had to in ``ops/pallas_mla_step.py``). One
departure at the width of a float32 rounding: the probabilities are
multiplied by the reciprocal of their sum, not divided by it.

The backward kernel recomputes a tile's probabilities from ``q`` and
``k`` with the forward's own code, so the forward saves no row
statistic: a column of ``n`` log-normalisers a head is 128 lanes wide in
HBM (as large as the output, written and read again), and the maximum
and sum it would spare are two vector operations a score. Residuals are
``q``, ``k``, ``v`` and the output; nothing of size ``n x n``. Then::

    dv += p^T . do;  dp = do . v^T;  ds = p * (dp - rowsum(do * o))
    dq = ds . k * scale;  dk += ds^T . q * scale

with ``dk`` and ``dv`` of a key/value head accumulated in VMEM over the
tiles of queries (the group's heads are rows of one tile, so they sum
into their key/value head in the product itself) and held transposed,
``[head_dim, keys]``: the transposed operands are then the tile's ``q``
and ``do``, not its scores.

The tiles and the chunks are walked by loops: nothing is unrolled in
the traced body (PERF.md section 6, PR 28). The kernels
compile through Mosaic, which exists on TPU only; tests on the CPU mesh
run the same bodies with ``interpret=True``, and nothing here picks the
interpreter by itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# Query positions a pass of the body's outer loop, at most: the largest
# divisor of ``n`` that is whole sublane tiles and no larger, so that no
# query is padded (192 at ``n = 576``). Times the heads of a group, the
# rows of every product (1,536 there: a chunk of keys loaded into the
# matrix unit serves them all).
TILE_Q = 256
# Keys a pass of the body's loops: one lane tile of scores a row.
CHUNK = 128
# The pass of a key that is before no query's: an uncommitted key, or
# one the op padded the range with.
_NEVER = 2 ** 30
_F32 = jnp.float32
# x [m, d] . y [c, d]^T, and x [m, d]^T . y [m, c]
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def fits(q, k, v) -> bool:
    """Whether ``q [b, n, nh, hd]``, ``k [b, n, nkv, hd]`` and ``v [b,
    n, nkv, hdv]`` take the kernels: the head widths whole lane tiles
    (a head's columns are then a block of the projections' own layout,
    and nothing is transposed on the way in or out), ``n`` whole sublane
    tiles. The published widths do; a test preset's narrow heads do
    not."""
    return (q.shape[-1] % _LANES == 0 and v.shape[-1] % _LANES == 0
            and q.shape[1] % _SUBLANES == 0
            and q.shape[2] % k.shape[2] == 0)


def _tiles(n: int, tile_q: int, chunk: int):
    """Tiles of queries and chunks of keys over ``n`` positions, and
    the positions a tile of queries holds: the largest divisor of ``n``
    up to ``tile_q`` that is whole sublane tiles."""
    if n % _SUBLANES:
        raise ValueError(
            f"{n} positions are not whole sublane tiles of {_SUBLANES}"
        )
    tile_q = max(t for t in range(_SUBLANES, max(tile_q, _SUBLANES) + 1,
                                  _SUBLANES) if n % t == 0)
    return n // tile_q, -(-n // chunk), tile_q


def _visited(step, tile_q: int, chunk: int):
    """Per tile of queries, from ``step [n]`` alone: the last chunk of
    keys it can see, and the first that is not wholly of earlier
    passes."""
    n = step.shape[0]
    nq, nk, tile_q = _tiles(n, tile_q, chunk)
    step = step.astype(jnp.int32)
    q_lo, q_hi = step[::tile_q], step[tile_q - 1::tile_q]
    k_lo = step[::chunk]
    k_hi = step[jnp.minimum(jnp.arange(1, nk + 1) * chunk, n) - 1]
    last = jnp.sum(k_lo[None, :] <= q_hi[:, None], 1) - 1
    mixed = jnp.sum(k_hi[None, :] < q_lo[:, None], 1)
    return last.astype(jnp.int32), mixed.astype(jnp.int32)


def score_tiles_computed_share(step, *, tile_q=TILE_Q, chunk=CHUNK):
    """The share of the (tile of queries, chunk of keys) pairs over
    ``step [n]`` whose scores the kernels compute: a tile's chunks up to
    the last that holds a key of its passes."""
    nq, nk, _ = _tiles(step.shape[0], tile_q, chunk)
    last, _ = _visited(step, tile_q, chunk)
    return jnp.sum(last + 1).astype(_F32) / (nq * nk)


# ---- the bodies ----------------------------------------------------------


def _fold(ref, rows, g: int):
    """The rows ``rows`` of a block ``[n, g * d]`` (a position's heads
    side by side, as the projection lays them out) -> ``[g * tile_q,
    d]``, head-major."""
    d = ref.shape[-1] // g
    return jnp.concatenate(
        [ref[rows, j * d:(j + 1) * d] for j in range(g)], axis=0
    )


def _unfold(ref, rows, x, g: int):
    """``x [g * tile_q, d]`` back into the rows ``rows`` of the block
    ``[n, g * d]``."""
    t, d = x.shape[0] // g, x.shape[1]
    for j in range(g):
        ref[rows, j * d:(j + 1) * d] = x[j * t:(j + 1) * t].astype(ref.dtype)


def _rows(i, size: int):
    return pl.ds(pl.multiple_of(i * size, size), size)


def _softmax_rows(q, sq, k_ref, sk_ref, kk_ref, s_ref, t_ref, mixed, last,
                  scale):
    """``exp(s - max s)`` of the chunks ``0 .. last`` into ``s_ref[c]``
    for the rows ``q [m, hd]`` at passes ``sq [m, 1]``; returns the
    reciprocal of the rows' sums ``[m, 1]``. ``t_ref [m, chunk]`` is
    scratch: the maximum and the sum are taken elementwise over the
    chunks and across the lanes once."""
    chunk = t_ref.shape[1]
    sq = jnp.broadcast_to(sq, t_ref.shape)
    t_ref[...] = jnp.full(t_ref.shape, -jnp.inf, _F32)

    def scores(whole_mask, c, carry):
        s = jax.lax.dot_general(
            q, k_ref[_rows(c, chunk), :], _NT, preferred_element_type=_F32
        ) * scale
        kk = kk_ref[c]  # [1, chunk]: the key's pass if a commit's
        if whole_mask:
            s = jnp.where((sk_ref[c] == sq) | (kk < sq), s, -jnp.inf)
        else:  # every key of an earlier pass: a commit's or not
            s = s + jnp.where(kk < _NEVER, 0.0, -jnp.inf)
        s_ref[c] = s
        t_ref[...] = jnp.maximum(t_ref[...], s)
        return carry

    jax.lax.fori_loop(0, mixed, functools.partial(scores, False), 0)
    jax.lax.fori_loop(mixed, last + 1, functools.partial(scores, True), 0)
    # A query's own block is visible and lies in a visited chunk: the
    # maximum is finite.
    m = jnp.broadcast_to(
        jnp.max(t_ref[...], axis=1, keepdims=True), t_ref.shape
    )
    t_ref[...] = jnp.zeros(t_ref.shape, _F32)

    def exponents(c, carry):
        p = jnp.exp(s_ref[c] - m)
        s_ref[c] = p
        t_ref[...] += p
        return carry

    jax.lax.fori_loop(0, last + 1, exponents, 0)
    return 1.0 / jnp.sum(t_ref[...], axis=1, keepdims=True)


def _forward_kernel(last_ref, mixed_ref, sq_ref, sk_ref, kk_ref, q_ref,
                    k_ref, v_ref, o_ref, s_ref, t_ref, acc_ref, *, g, scale,
                    tile_q):
    chunk = t_ref.shape[1]

    def tile(i, carry):
        rows, last = _rows(i, tile_q), last_ref[i]
        inv = _softmax_rows(
            _fold(q_ref, rows, g),
            jnp.concatenate([sq_ref[rows, :]] * g, axis=0),
            k_ref, sk_ref, kk_ref, s_ref, t_ref, mixed_ref[i], last, scale,
        )
        inv = jnp.broadcast_to(inv, t_ref.shape)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

        def values(c, carry):
            p = (s_ref[c] * inv).astype(v_ref.dtype)
            acc_ref[...] += jnp.dot(
                p, v_ref[_rows(c, chunk), :], preferred_element_type=_F32
            )
            return carry

        jax.lax.fori_loop(0, last + 1, values, 0)
        _unfold(o_ref, rows, acc_ref[...], g)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // tile_q, tile, 0)


def _backward_kernel(last_ref, mixed_ref, sq_ref, sk_ref, kk_ref, q_ref,
                     k_ref, v_ref, o_ref, do_ref, dq_ref, dkt_ref, dvt_ref,
                     s_ref, t_ref, acc_ref, *, g, scale, tile_q):
    chunk = t_ref.shape[1]
    dkt_ref[...] = jnp.zeros(dkt_ref.shape, _F32)
    dvt_ref[...] = jnp.zeros(dvt_ref.shape, _F32)

    def tile(i, carry):
        rows, last = _rows(i, tile_q), last_ref[i]
        q, do = _fold(q_ref, rows, g), _fold(do_ref, rows, g)
        delta = jnp.sum(
            do.astype(_F32) * _fold(o_ref, rows, g).astype(_F32),
            axis=1, keepdims=True,
        )
        inv = _softmax_rows(
            q, jnp.concatenate([sq_ref[rows, :]] * g, axis=0), k_ref,
            sk_ref, kk_ref, s_ref, t_ref, mixed_ref[i], last, scale,
        )
        inv = jnp.broadcast_to(inv, t_ref.shape)
        delta = jnp.broadcast_to(delta, t_ref.shape)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

        def gradients(c, carry):
            keys = _rows(c, chunk)
            p = s_ref[c] * inv
            dvt_ref[c] += jax.lax.dot_general(
                do, p.astype(q.dtype), _TN, preferred_element_type=_F32
            )
            dp = jax.lax.dot_general(
                do, v_ref[keys, :], _NT, preferred_element_type=_F32
            )
            ds = (p * (dp - delta)).astype(q.dtype)
            acc_ref[...] += jnp.dot(
                ds, k_ref[keys, :], preferred_element_type=_F32
            )
            dkt_ref[c] += jax.lax.dot_general(
                q, ds, _TN, preferred_element_type=_F32
            )
            return carry

        jax.lax.fori_loop(0, last + 1, gradients, 0)
        _unfold(dq_ref, rows, acc_ref[...] * scale, g)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // tile_q, tile, 0)


# ---- the calls -----------------------------------------------------------


def _operands(q, k, v, step, key_commit, nkv, tile_q, chunk):
    """The padded operands of both kernels and their block specs.
    ``q [b, n, nh * hd]``, ``k [b, n, nkv * hd]``, ``v [b, n, nkv *
    hdv]``: the keys' range is padded to whole chunks with keys of no
    pass, which no query sees."""
    b, n, _ = q.shape
    nq, nk, tile_q = _tiles(n, tile_q, chunk)
    hd, hdv = k.shape[2] // nkv, v.shape[2] // nkv
    g = q.shape[2] // (nkv * hd)
    pad_k = nk * chunk - n
    step = step.astype(jnp.int32)
    last, mixed = _visited(step, tile_q, chunk)
    sq = step.reshape(-1, 1)
    sk = jnp.pad(step, (0, pad_k), constant_values=-1).reshape(nk, 1, chunk)
    kk = jnp.pad(
        jnp.where(key_commit, step, _NEVER), ((0, 0), (0, pad_k)),
        constant_values=_NEVER,
    ).reshape(b, nk, 1, chunk)
    k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    def by_group(width):  # the group's query heads, every position
        return pl.BlockSpec(
            (None, n, g * width), lambda bi, hi, *_: (bi, 0, hi)
        )

    def by_head(width):  # a key/value head's whole range
        return pl.BlockSpec(
            (None, nk * chunk, width), lambda bi, hi, *_: (bi, 0, hi)
        )

    specs = [
        pl.BlockSpec((n, 1), lambda bi, hi, *_: (0, 0)),
        pl.BlockSpec((nk, 1, chunk), lambda bi, hi, *_: (0, 0, 0)),
        pl.BlockSpec((None, nk, 1, chunk), lambda bi, hi, *_: (bi, 0, 0, 0)),
        by_group(hd), by_head(hd), by_head(hdv),
    ]
    dims = dict(b=b, n=n, nq=nq, nk=nk, tile_q=tile_q, g=g, hd=hd, hdv=hdv)
    return (last, mixed, sq, sk, kk, q, k, v), specs, by_group, dims


def _scratch(d, chunk, width):
    m = d["g"] * d["tile_q"]
    return [
        pltpu.VMEM((d["nk"], m, chunk), _F32),  # a tile's scores
        pltpu.VMEM((m, chunk), _F32),
        pltpu.VMEM((m, width), _F32),
    ]


def _cost(d, nkv, chunk, products, row_bytes):
    """What the compiler's scheduler may overlap with a kernel rests on
    this (PERF.md section 6, PR 28). The chunks visited are not known
    here: the causal share of them."""
    pairs = (d["b"] * nkv * d["g"] * d["n"] * d["nk"] * chunk
             * (d["nq"] + 1) // (2 * d["nq"]))
    return pl.CostEstimate(
        flops=2 * pairs * products,
        transcendentals=pairs,
        bytes_accessed=d["b"] * nkv * d["g"] * d["n"] * row_bytes,
    )


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        # a tile's scores (3.75 MiB at 1,536 rows x 640 keys), a
        # sequence's queries and outputs double-buffered (1.2 MiB each
        # in bfloat16) and the body's temporaries: above Mosaic's 16 MiB
        # scoped default, far inside the chip's 128 MiB
        vmem_limit_bytes=64 << 20,
    )


# Both calls are jitted: a model's layers then share ONE trace of each
# kernel's body and one lowering of it (six layers, each with the pass,
# its recomputation and the backward, traced and lowered one by one cost
# ~3 s of a program's set-up).
_STATIC = dict(static_argnames=("nkv", "scale", "tile_q", "chunk",
                                "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _attend(q, k, v, step, key_commit, nkv, scale, tile_q, chunk,
            interpret):
    return _forward(q, k, v, step, key_commit, nkv, scale, tile_q, chunk,
                    interpret)[0]


def _forward(q, k, v, step, key_commit, nkv, scale, tile_q, chunk,
             interpret):
    out = _forward_call(q, k, v, step, key_commit, nkv=nkv, scale=scale,
                        tile_q=tile_q, chunk=chunk, interpret=interpret)
    return out, (q, k, v, step, key_commit, out)


@functools.partial(jax.jit, **_STATIC)
def _forward_call(q, k, v, step, key_commit, *, nkv, scale, tile_q, chunk,
                  interpret):
    operands, specs, by_group, d = _operands(
        q, k, v, step, key_commit, nkv, tile_q, chunk
    )
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, g=d["g"], scale=scale, tile_q=d["tile_q"]
        ),
        out_shape=jax.ShapeDtypeStruct(
            (d["b"], d["n"], nkv * d["g"] * d["hdv"]), q.dtype
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(d["b"], nkv),
            in_specs=specs,
            out_specs=by_group(d["hdv"]),
            scratch_shapes=_scratch(d, chunk, d["hdv"]),
        ),
        compiler_params=_params(),
        cost_estimate=_cost(
            d, nkv, chunk, d["hd"] + d["hdv"],
            (d["hd"] + d["hdv"]) * q.dtype.itemsize,
        ),
        interpret=interpret,
        name="block_attention",
    )(*operands)


def _backward(nkv, scale, tile_q, chunk, interpret, residuals, do):
    grads = _backward_call(*residuals, do, nkv=nkv, scale=scale,
                           tile_q=tile_q, chunk=chunk, interpret=interpret)
    return (*grads, None, None)


@functools.partial(jax.jit, **_STATIC)
def _backward_call(q, k, v, step, key_commit, out, do, *, nkv, scale,
                   tile_q, chunk, interpret):
    operands, specs, by_group, d = _operands(
        q, k, v, step, key_commit, nkv, tile_q, chunk
    )

    def transposed(width):  # a key/value head's, over all its chunks
        return pl.BlockSpec(
            (None, None, d["nk"], width, chunk),
            lambda bi, hi, *_: (bi, hi, 0, 0, 0),
        )

    def of_keys(width):
        return jax.ShapeDtypeStruct(
            (d["b"], nkv, d["nk"], width, chunk), _F32
        )

    dq, dkt, dvt = pl.pallas_call(
        functools.partial(
            _backward_kernel, g=d["g"], scale=scale, tile_q=d["tile_q"]
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            of_keys(d["hd"]), of_keys(d["hdv"]),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(d["b"], nkv),
            in_specs=specs + [by_group(d["hdv"]), by_group(d["hdv"])],
            out_specs=(
                by_group(d["hd"]), transposed(d["hd"]), transposed(d["hdv"])
            ),
            scratch_shapes=_scratch(d, chunk, d["hd"]),
        ),
        compiler_params=_params(),
        cost_estimate=_cost(
            d, nkv, chunk, 3 * d["hd"] + 2 * d["hdv"],
            2 * (d["hd"] + d["hdv"]) * q.dtype.itemsize,
        ),
        interpret=interpret,
        name="block_attention_backward",
    )(*operands, out, do)

    def of_positions(xt, like):  # [b, nkv, nk, w, chunk] -> [b, n, nkv * w]
        x = jnp.transpose(xt, (0, 2, 4, 1, 3)).reshape(d["b"], -1, like.shape[2])
        return x[:, :d["n"]]

    return (
        dq,
        (of_positions(dkt, k) * scale).astype(k.dtype),
        of_positions(dvt, v).astype(v.dtype),
    )


_attend.defvjp(_forward, _backward)


def block_attention(q, k, v, step, key_commit, dtype, *, tile_q=TILE_Q,
                    chunk=CHUNK, interpret=False):
    """``q [b, n, nh, hd]``, ``k [b, n, nkv, hd]``, ``v [b, n, nkv,
    hdv]`` (query head ``j`` reads key/value head ``j // (nh / nkv)``),
    ``step [n]`` (a position's pass, non-decreasing) and ``key_commit
    [b, n]`` -> ``[b, n, nh * hdv]`` in ``dtype``: what
    ``models.decoder.attend`` gives, heads merged, under the mask ``step[k] ==
    step[q] | (step[k] < step[q]) & key_commit[:, k]``, the products in
    ``dtype``, rounded to it; differentiable in ``q``, ``k`` and ``v``.

    ``tile_q``: query positions a pass of the body's outer loop, at
    most; ``chunk``: keys a pass of its inner loops. ``interpret``: run the bodies in the
    Pallas interpreter (any backend; for tests). Unset, the kernels are
    compiled for the TPU, and on another backend that is an error."""
    b, n, nh, hd = q.shape
    nkv = k.shape[2]
    dtype = jnp.dtype(dtype)
    return _attend(
        q.astype(dtype).reshape(b, n, nh * hd),
        k.astype(dtype).reshape(b, n, nkv * hd),
        v.astype(dtype).reshape(b, n, -1),
        step, key_commit, nkv, float(hd) ** -0.5, tile_q, chunk, interpret,
    )
