"""Pallas TPU kernels for a sublayer's two passes over the float32
streams of manifold-constrained hyper-connections, each with a
hand-written backward that is one kernel too.

``models/hyper_connections.py`` writes a sublayer on the streams ``X
[N, n, C]`` in plain array operations (``Phi [n C, M]``, ``M = 2n +
n^2``):

    r = (mean(vec(X)^2) + eps)^(-1/2);  pqr = r (vec(X) Phi)
    h = sum_i sigmoid(a p + b)_i X_i;   y = F(h)
    X'_i = sum_j H_ij X_j + h_post_i y

and XLA runs it, with its derivative and the per-layer recomputation, as
many float32 fusions that each read the streams again: the norm, ``u
Phi``, the pre-mix, the residual mix's broadcast product and its
transpose, the sum of the two cotangents of ``X``. Here the streams lie
as ``[N, n C]`` (a free reshape): tokens down the sublanes, stream ``i``
in lanes ``[i C, (i + 1) C)``, so no axis of ``n`` is padded to a tile,
and a tile of tokens holds whole tokens, so every sum over ``n C``
finishes inside it. Four kernels, each one pass:

* ``mhc_pre`` reads ``X`` once and writes ``h`` and ``pqr``;
* ``mhc_post`` reads ``X`` and ``y`` once and writes ``X'``;
* ``mhc_post_backward`` reads ``dX'``, ``X`` and ``y`` once and writes
  ``dX = H^T dX'``, ``dy = sum_i h_post_i dX'_i`` and the per-token
  ``dH_ij = <dX'_i, X_j>``, ``dh_post_i = <dX'_i, y>``;
* ``mhc_pre_backward`` reads ``X``, ``dh`` and that ``dX`` once and
  writes the whole ``dX``: ``<X_i, dh>`` through the read weights'
  sigmoid, ``pqr``'s cotangent through ``Phi^T`` and the norm, ``h_pre_i
  dh`` and the residual path's ``dX``; ``dPhi`` is summed over the
  tiles in VMEM.

What lies between the two passes (``h_post``, the clamp, the ``exp``
and the Sinkhorn on ``[n, n, N]``) is small and stays plain JAX, which
differentiates it. The residual path's cotangent reaches the pre-pass's
backward without an add of its own: ``pre`` hands ``X`` on as an output
and ``post`` reads that one, so JAX gives ``pre``'s backward the
cotangent of what ``post`` read.

The same float32 work as the plain form: the streams, both mixes, the
norm and the product (``Precision.HIGHEST``, on the MXU); the norm is a
per-token scale and is applied after the product, so only the order of
float32 roundings differs from it. The elementwise work walks a tile by
a loop of one sublane tile of tokens a pass (PERF.md section 6, PR 28:
a body unrolled over its tiles cost seconds of set-up). The kernels
compile through Mosaic, which exists on TPU only; tests on the CPU run
the same bodies with ``interpret=True``, and nothing here picks the
interpreter by itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_ROWS = 8      # tokens a pass of a body's loop: one float32 sublane tile
_TILE = 64     # tokens a grid step at most (32 and 128 ran as fast)
# the pre-pass's backward holds three blocks of a tile's streams (X and
# the residual path's dX in, dX out: 3.7 MB each at 64 tokens of 4 x
# 3,584), double-buffered, with Phi^T, its gradient and the body's
# temporaries: above Mosaic's 16 MiB scoped default, inside the chip's
# 128 MiB
_VMEM = 96 << 20


def tile_rows(tokens: int) -> int:
    """Tokens a grid step: the largest power of two up to ``_TILE`` that
    divides ``tokens``."""
    tm = _TILE
    while tokens % tm:
        tm //= 2
    return tm


def fits(x, n: int) -> bool:
    """Whether streams ``x [N, n C]`` tile the vector unit as the
    kernels walk them: ``C`` whole lane tiles, the tokens whole sublane
    tiles, and a token's ``n^2 + n`` mixing coefficients one lane tile.
    The published widths do (4 x 3,584 at 4,096 and 128 tokens); a test
    preset's narrow streams do not."""
    if x.ndim != 2 or x.shape[1] % n:
        return False
    N, C = x.shape[0], x.shape[1] // n
    return C % _LANES == 0 and N % _ROWS == 0 and n * n + n <= _LANES


def _rows(g):
    return pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)


def _stream(i, C):
    return pl.ds(i * C, C)


def _column(a, k):
    """Column ``k`` of ``a [rows, m]`` as ``[rows, 1]``."""
    return a[:, k:k + 1]


def _loop(tm, body):
    def step(g, carry):
        body(_rows(g))
        return carry

    jax.lax.fori_loop(0, tm // _ROWS, step, 0)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), precision=_HIGHEST,
        preferred_element_type=_F32,
    )


# ---- the kernels ----------------------------------------------------------


def _pre_kernel(ab_ref, x_ref, phi_ref, h_ref, pqr_ref, *, n, eps):
    """``ab``: ``[a_pre, b_pre_0, ...]`` in SMEM; ``x [tm, n C]``, ``phi
    [M, n C]`` (``Phi^T``) -> ``h [tm, C]``, ``pqr [tm, M]``."""
    tm, K = x_ref.shape
    C = K // n
    # x Phi, the tile's tokens at once (the per-token scale comes after),
    # as (Phi^T x^T)^T: with the tile as the product's right-hand side
    # the pass runs at the rate of the same pass without the product
    # (0.473 ms at 4,096 tokens, 0.452 without it; with the tile on the
    # left, 0.665: PERF.md section 6, PR 40)
    pqr_ref[...] = _dot(phi_ref[...], x_ref[...], ((1,), (1,))).T

    def body(rows):
        ss = sum(jnp.sum(jnp.square(x_ref[rows, _stream(i, C)]), 1,
                         keepdims=True) for i in range(n))
        pqr = jax.lax.rsqrt(ss / K + eps) * pqr_ref[rows, :]
        pqr_ref[rows, :] = pqr
        h = None
        for i in range(n):
            w = jax.nn.sigmoid(ab_ref[0] * _column(pqr, i) + ab_ref[1 + i])
            term = w * x_ref[rows, _stream(i, C)]
            h = term if h is None else h + term
        h_ref[rows, :] = h

    _loop(tm, body)


def _post_kernel(cf_ref, x_ref, y_ref, out_ref, *, n):
    """``cf [tm, n^2 + n]`` (``H`` by rows, then ``h_post``), ``x [tm, n
    C]``, ``y [tm, C]`` -> ``X' [tm, n C]``."""
    tm, K = x_ref.shape
    C = K // n

    def body(rows):
        cf = cf_ref[rows, :]
        y = y_ref[rows, :].astype(_F32)
        xs = [x_ref[rows, _stream(j, C)] for j in range(n)]
        for i in range(n):
            mixed = _column(cf, i * n) * xs[0]
            for j in range(1, n):
                mixed = mixed + _column(cf, i * n + j) * xs[j]
            out_ref[rows, _stream(i, C)] = mixed + _column(cf, n * n + i) * y

    _loop(tm, body)


def _post_backward_kernel(cf_ref, g_ref, x_ref, y_ref, dx_ref, dy_ref,
                          dcf_ref, *, n):
    """The cotangent ``g = dX' [tm, n C]`` -> ``dX [tm, n C]``, ``dy [tm,
    C]``, ``dcf [tm, n^2 + n]`` (``dH`` by rows, then ``dh_post``)."""
    tm, K = x_ref.shape
    C = K // n
    width = n * n + n

    def body(rows):
        cf = cf_ref[rows, :]
        y = y_ref[rows, :].astype(_F32)
        gs = [g_ref[rows, _stream(i, C)] for i in range(n)]
        dy = _column(cf, n * n) * gs[0]
        for i in range(1, n):
            dy = dy + _column(cf, n * n + i) * gs[i]
        dy_ref[rows, :] = dy.astype(dy_ref.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, width), 1)
        dcf = jnp.zeros((_ROWS, width), _F32)
        for j in range(n):
            xj = x_ref[rows, _stream(j, C)]
            dxj = _column(cf, j) * gs[0]
            for i in range(1, n):
                dxj = dxj + _column(cf, i * n + j) * gs[i]
            dx_ref[rows, _stream(j, C)] = dxj
            for i in range(n):
                dcf = jnp.where(lane == i * n + j,
                                jnp.sum(gs[i] * xj, 1, keepdims=True), dcf)
        for i in range(n):
            dcf = jnp.where(lane == n * n + i,
                            jnp.sum(gs[i] * y, 1, keepdims=True), dcf)
        dcf_ref[rows, :] = dcf

    _loop(tm, body)


def _pre_backward_kernel(ab_ref, x_ref, phi_ref, pqr_ref, dpqr_ref, dh_ref,
                         dxr_ref, dx_ref, dphi_ref, dz_ref, g_scr, hs_scr, *,
                         n, eps):
    """``x``, ``phi``, ``pqr`` as the pass had them; the cotangents
    ``dpqr [tm, M]`` (of what the plain part read of ``pqr``), ``dh [tm,
    C]`` and ``dxr [tm, n C]`` (of the ``X`` handed on) -> ``dX [tm, n
    C]``, ``dPhi^T [M, n C]`` (summed over the grid) and ``dz [tm, n]``,
    the cotangent of the read weights' sigmoid's argument."""
    tm, K = x_ref.shape
    M = phi_ref.shape[0]
    C = K // n

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, _F32)

    def first(rows):
        ss = sum(jnp.sum(jnp.square(x_ref[rows, _stream(i, C)]), 1,
                         keepdims=True) for i in range(n))
        r = jax.lax.rsqrt(ss / K + eps)
        pqr = pqr_ref[rows, :]
        dh = dh_ref[rows, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, M), 1)
        dz = ws = jnp.zeros((_ROWS, M), _F32)
        for i in range(n):
            w = jax.nn.sigmoid(ab_ref[0] * _column(pqr, i) + ab_ref[1 + i])
            dw = jnp.sum(x_ref[rows, _stream(i, C)] * dh, 1, keepdims=True)
            dz = jnp.where(lane == i, dw * w * (1.0 - w), dz)
            ws = jnp.where(lane == i, w, ws)
        d = dpqr_ref[rows, :] + ab_ref[0] * dz
        g_scr[rows, :] = r * d
        # the norm's part of dX, as a multiple of x: its coefficient
        # rides in the lane after the weights
        s = (r * r / K) * jnp.sum(d * pqr, 1, keepdims=True)
        hs_scr[rows, :] = jnp.where(lane == n, s, ws)
        dz_ref[rows, :] = dz[:, :n]

    _loop(tm, first)
    g = g_scr[...]
    dx_ref[...] = _dot(g, phi_ref[...], ((1,), (0,)))
    dphi_ref[...] += _dot(g, x_ref[...], ((0,), (0,)))

    def second(rows):
        hs = hs_scr[rows, :]
        s = _column(hs, n)
        dh = dh_ref[rows, :]
        for i in range(n):
            sl = _stream(i, C)
            dx_ref[rows, sl] = (dxr_ref[rows, sl] + dx_ref[rows, sl]
                                - s * x_ref[rows, sl] + _column(hs, i) * dh)

    _loop(tm, second)


# ---- the calls ------------------------------------------------------------


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _by_tile(tm, width):
    return pl.BlockSpec((tm, width), lambda t: (t, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda t: (0, 0))


# Each call is jitted: a model's sublayers then share ONE trace of each
# kernel's body and one lowering of it (PERF.md section 6, PR 34).
_STATIC = dict(static_argnames=("n", "eps", "interpret"))


@functools.partial(jax.jit, **_STATIC)
def _pre_call(ab, x, phi, *, n, eps, interpret):
    N, K = x.shape
    M, C, tm = phi.shape[0], K // n, tile_rows(N)
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((N, C), _F32),
                   jax.ShapeDtypeStruct((N, M), _F32)),
        grid=(N // tm,),
        in_specs=[_smem(), _by_tile(tm, K), _whole((M, K))],
        out_specs=(_by_tile(tm, C), _by_tile(tm, M)),
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * M + 4 * N * K, transcendentals=N * (n + 1),
            bytes_accessed=4 * (N * K + M * K + N * C + N * M),
        ),
        interpret=interpret,
        name="mhc_pre",
    )(ab, x, phi)


@functools.partial(jax.jit, **_STATIC)
def _pre_backward_call(ab, x, phi, pqr, dpqr, dh, dxr, *, n, eps, interpret):
    N, K = x.shape
    M, C, tm = phi.shape[0], K // n, tile_rows(N)
    return pl.pallas_call(
        functools.partial(_pre_backward_kernel, n=n, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((N, K), _F32),
                   jax.ShapeDtypeStruct((M, K), _F32),
                   jax.ShapeDtypeStruct((N, n), _F32)),
        grid=(N // tm,),
        in_specs=[_smem(), _by_tile(tm, K), _whole((M, K)), _by_tile(tm, M),
                  _by_tile(tm, M), _by_tile(tm, C), _by_tile(tm, K)],
        out_specs=(_by_tile(tm, K), _whole((M, K)), _by_tile(tm, n)),
        scratch_shapes=[pltpu.VMEM((tm, M), _F32)] * 2,
        # dPhi is summed over the tiles in one resident block
        compiler_params=_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=4 * N * K * M + 8 * N * K, transcendentals=N * (n + 1),
            bytes_accessed=4 * (3 * N * K + 2 * M * K + N * C + 2 * N * M),
        ),
        interpret=interpret,
        name="mhc_pre_backward",
    )(ab, x, phi, pqr, dpqr, dh, dxr)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _post_call(cf, x, y, *, n, interpret):
    N, K = x.shape
    C, tm, width = K // n, tile_rows(N), cf.shape[1]
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        out_shape=jax.ShapeDtypeStruct((N, K), _F32),
        grid=(N // tm,),
        in_specs=[_by_tile(tm, width), _by_tile(tm, K), _by_tile(tm, C)],
        out_specs=_by_tile(tm, K),
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * (n + 1), transcendentals=0,
            bytes_accessed=4 * (2 * N * K + N * width) + N * C
            * y.dtype.itemsize,
        ),
        interpret=interpret,
        name="mhc_post",
    )(cf, x, y)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _post_backward_call(cf, g, x, y, *, n, interpret):
    N, K = x.shape
    C, tm, width = K // n, tile_rows(N), cf.shape[1]
    return pl.pallas_call(
        functools.partial(_post_backward_kernel, n=n),
        out_shape=(jax.ShapeDtypeStruct((N, K), _F32),
                   jax.ShapeDtypeStruct((N, C), y.dtype),
                   jax.ShapeDtypeStruct((N, width), _F32)),
        grid=(N // tm,),
        in_specs=[_by_tile(tm, width), _by_tile(tm, K), _by_tile(tm, K),
                  _by_tile(tm, C)],
        out_specs=(_by_tile(tm, K), _by_tile(tm, C), _by_tile(tm, width)),
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * (2 * n + 1), transcendentals=0,
            bytes_accessed=4 * (3 * N * K + 2 * N * width)
            + 2 * N * C * y.dtype.itemsize,
        ),
        interpret=interpret,
        name="mhc_post_backward",
    )(cf, g, x, y)


# ---- the differentiable passes --------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pre(x, phi, a, b, eps, interpret):
    return _pre_forward(x, phi, a, b, eps, interpret)[0]


def _pre_forward(x, phi, a, b, eps, interpret):
    h, pqr = _pre_call(jnp.concatenate([a[None], b]), x, phi, n=b.shape[0],
                       eps=eps, interpret=interpret)
    return (h, pqr, x), (x, phi, a, b, pqr)


def _pre_backward(eps, interpret, residuals, cotangents):
    x, phi, a, b, pqr = residuals
    dh, dpqr, dxr = cotangents
    n = b.shape[0]
    dx, dphi, dz = _pre_backward_call(
        jnp.concatenate([a[None], b]), x, phi, pqr, dpqr, dh, dxr, n=n,
        eps=eps, interpret=interpret,
    )
    # the read weights' gain and bias, from the cotangent of their
    # sigmoid's argument a p + b
    return dx, dphi, jnp.sum(dz * pqr[:, :n]), jnp.sum(dz, 0)


_pre.defvjp(_pre_forward, _pre_backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _post(x, y, cf, n, interpret):
    return _post_call(cf, x, y, n=n, interpret=interpret)


def _post_forward(x, y, cf, n, interpret):
    return _post_call(cf, x, y, n=n, interpret=interpret), (x, y, cf)


def _post_backward(n, interpret, residuals, g):
    x, y, cf = residuals
    return _post_backward_call(cf, g, x, y, n=n, interpret=interpret)


_post.defvjp(_post_forward, _post_backward)


def pre(x, phi, alpha_pre, b_pre, *, eps, interpret=False):
    """The pass before the sublayer: streams ``x [N, n C]`` float32,
    ``phi [n C, M]``, the read gain ``alpha_pre`` (a scalar) and bias
    ``b_pre [n]`` -> ``(h [N, C], pqr [N, M], x)``: what
    ``models.hyper_connections`` computes as ``u Phi`` and ``sum_i
    h_pre_i X_i``, and ``x`` handed on for ``post`` to read (its
    cotangent reaches this pass's backward, which adds it in).

    ``interpret``: run the bodies in the Pallas interpreter (any
    backend; for tests). Unset, the kernels are compiled for the TPU,
    and on another backend that is an error."""
    return _pre(x.astype(_F32), phi.T.astype(_F32),
                jnp.asarray(alpha_pre, _F32), b_pre.astype(_F32), eps,
                interpret)


def post(x, y, h_post, H, *, interpret=False):
    """The pass after it: ``X'_i = sum_j H_ij X_j + h_post_i y`` for
    streams ``x [N, n C]``, ``y [N, C]``, ``h_post [N, n]`` and ``H [n,
    n, N]`` (rows, columns, tokens), float32 -> ``[N, n C]``."""
    N, n = h_post.shape
    cf = jnp.concatenate(
        [jnp.moveaxis(H, -1, 0).reshape(N, n * n), h_post], 1
    ).astype(_F32)
    return _post(x, y, cf, n, interpret)
