"""Manifold-constrained hyper-connections (mHC): the residual stream of
a decoder layer widened into ``n`` streams, read into a sublayer and
written back through per-token coefficients, the streams mixed by a
matrix projected onto the doubly stochastic ones by Sinkhorn's
iterations. Written once, so that any core can wrap its sublayers in
it (``models/xing.py`` is the first).

Sources: Xie et al. (DeepSeek-AI), "mHC: Manifold-Constrained
Hyper-Connections", arXiv:2512.24880, built on Zhu et al.,
"Hyper-Connections", arXiv:2409.19606; the numbers are a config's keys
``hc_mult`` (``n``), ``hc_sinkhorn_iters``, ``hc_eps`` and
``mhc_h_res_clamp_min`` / ``_max``.

One sublayer ``F`` on a token's streams ``X [n, C]``:

* ``u = vec(X) / sqrt(mean(vec(X)^2) + eps)`` over all ``n C`` numbers
  (no gain: one would fold into ``Phi``);
* ``[p | q | r] = u Phi``, ``Phi [n C, 2n + n^2]``, widths ``n | n |
  n^2``;
* ``h_pre = sigmoid(a_pre p + b_pre)``, ``h_post = 2 sigmoid(a_post q +
  b_post)``;
* ``H_res = Sinkhorn(exp(clamp(a_res mat(r) + B_res, lo, hi)))``: each
  of ``hc_sinkhorn_iters`` iterations divides every column by its sum +
  ``hc_eps``, then every row by its sum + ``hc_eps``;
* ``y = F(sum_i h_pre_i X_i)``; ``X'_i = sum_j H_res_ij X_j + h_post_i
  y``.

Everything here is float32 whatever the compute dtype of ``F``: the
streams, the coefficient product (``Precision.HIGHEST``), the
iterations and both mixes. Scope ``mhc`` is around all of it but ``F``
(the coefficients, both mixes, the streams' expansion and reduction),
``mhc_sinkhorn`` inside it around the iterations. The iterations run on
``[n, n, tokens]``, the tokens the minor axis: on ``[tokens, n, n]`` a
TPU would pad the two axes of 4 to a tile of 8 x 128.

A core carries the streams as ``[..., n C]``, stream ``i`` in ``[i C,
(i + 1) C)``: a TPU tiles the last two axes of an array, and an axis of
``n`` = 4 among them would be padded to 8 and relaid between every
sublayer's passes (PERF.md section 5, PR 40). On a TPU at widths that
tile (``ops/pallas_hyper_connections.py``), the two passes over the
streams are that module's kernels, forward and backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.ops import kernel_choice
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# Counters of a call, over its tokens: the mean of 1 - tr(H_res) / n,
# and the largest |row or column sum - 1| of H_res.
OFFDIAG_MASS = "mhc_res_offdiag_mass"
SINKHORN_RESIDUAL = "mhc_sinkhorn_residual"
# The share of a call's sublayers whose two passes ran as the kernels of
# ops/pallas_hyper_connections.py: 1 or 0.
FUSED_SHARE = "mhc_fused_share"
_KERNELS = "pallas_hyper_connections"
# The gains of the coefficients' dynamic part start small, so that at
# the start a sublayer's coefficients are its biases' give or take a
# little: at 1, ``u Phi`` (unit ``u`` over ``n C`` = 14,336 numbers,
# ``Phi`` normal(0.02)) has a spread of 2.4 a token, and 20 Sinkhorn
# iterations leave 1 in 10^2 of such matrices 1e-2 from doubly
# stochastic (PERF.md section 6, PR 39).
ALPHA_INIT = 0.01


def param_spec(cfg, prefix: str):
    """``{name: (shape, init)}`` of one sublayer's coefficients, names
    under ``prefix`` (assumed: no published checkpoint is read):
    ``Phi`` normal(0.02), ``b_pre`` and ``b_post`` 0, ``B_res`` normal(0,
    1), so that ``H_res`` starts far from the identity, and the three
    gains ``alpha`` (pre, post, residual) ``ALPHA_INIT``."""
    n, C = cfg.hc_mult, cfg.hidden_size
    zeros = nn.initializers.zeros_init()
    return {
        f"{prefix}phi": ((n * C, 2 * n + n * n),
                         nn.initializers.normal(0.02)),
        f"{prefix}alpha": ((3,), nn.initializers.constant(ALPHA_INIT)),
        f"{prefix}b_pre": ((n,), zeros),
        f"{prefix}b_post": ((n,), zeros),
        f"{prefix}b_res": ((n, n), nn.initializers.normal(1.0)),
    }


def expand(x, n: int):
    """``x [..., C]`` -> the streams ``[..., n C]``, float32: ``x`` in
    each."""
    with jax.named_scope(profiling.MHC):
        return jnp.concatenate([x.astype(_F32)] * n, -1)


def reduce(X, n: int):
    """The streams ``[..., n C]`` -> their sum ``[..., C]``: the slices
    added, so that no ``[..., n, C]`` array is laid out for it."""
    C = X.shape[-1] // n
    with jax.named_scope(profiling.MHC):
        return sum(X[..., i * C:(i + 1) * C] for i in range(n))


def sinkhorn(M, iters: int, eps: float):
    """``M [n, n, ...]`` (positive) -> ``iters`` alternations of column
    then row normalisation, each sum + ``eps``."""
    for _ in range(iters):
        M = M / (jnp.sum(M, 0, keepdims=True) + eps)
        M = M / (jnp.sum(M, 1, keepdims=True) + eps)
    return M


def _project(p, prefix, X, cfg):
    """``X [N, n, C]`` -> ``[p | q | r] = u Phi [N, 2n + n^2]``, ``u``
    the ungained norm of ``vec(X)``."""
    u = X.reshape(X.shape[0], -1)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    return jnp.dot(u, p[f"{prefix}phi"], precision=_HIGHEST)


def _read_weights(p, prefix, pqr, n):
    """``h_pre [N, n]`` from ``pqr``."""
    return jax.nn.sigmoid(p[f"{prefix}alpha"][0] * pqr[:, :n]
                          + p[f"{prefix}b_pre"])


def _write_coefficients(p, prefix, pqr, cfg):
    """``h_post [N, n]`` and ``H_res [n, n, N]`` from ``pqr``."""
    n = cfg.hc_mult
    a = p[f"{prefix}alpha"]
    h_post = 2.0 * jax.nn.sigmoid(a[1] * pqr[:, n:2 * n]
                                  + p[f"{prefix}b_post"])
    logits = a[2] * pqr[:, 2 * n:].reshape(-1, n, n) + p[f"{prefix}b_res"]
    logits = jnp.clip(logits, cfg.mhc_h_res_clamp_min,
                      cfg.mhc_h_res_clamp_max)
    with jax.named_scope(profiling.MHC_SINKHORN):
        H = sinkhorn(jnp.exp(jnp.moveaxis(logits, 0, -1)),
                     cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return h_post, H


def counters(H):
    """``H [n, n, N]`` -> this call's two counters: the mean
    off-diagonal mass and the largest distance of a row or column sum
    from 1."""
    n = H.shape[0]
    diagonal = jnp.trace(H, axis1=0, axis2=1)
    sums = jnp.concatenate([jnp.sum(H, 0), jnp.sum(H, 1)])
    return {OFFDIAG_MASS: jnp.mean(1.0 - diagonal / n),
            SINKHORN_RESIDUAL: jnp.max(jnp.abs(sums - 1.0))}


def pre_mix(X, h_pre):
    """``sum_i h_pre_i X_i``: ``[N, n, C]``, ``[N, n]`` -> ``[N, C]``."""
    return jnp.sum(h_pre[..., None] * X, -2)


def post_mix(X, y, h_post, H):
    """``X'_i = sum_j H_ij X_j + h_post_i y``: ``X [N, n, C]``, ``y [N,
    C]``, ``h_post [N, n]``, ``H [n, n, N]`` -> ``[N, n, C]``. Elementwise
    products and sums (a product of a 4 x 4 by a 4 x C matrix on the
    MXU would run in bfloat16 passes, at a sixteenth of its rows)."""
    H = jnp.moveaxis(H, -1, 0)                              # [N, n, n]
    mixed = jnp.sum(H[..., None] * X[:, None, :, :], -2)
    return mixed + h_post[..., None] * y[:, None, :].astype(_F32)


def sublayer(p, prefix, X, F, cfg):
    """One sublayer with hyper-connections: the streams ``X [..., n C]``
    float32 and ``F(h [..., C]) -> (y [..., C], aux)`` -> ``(X', aux,
    counters)``. Lowered for a TPU, at widths that tile its vector unit
    (``ops/pallas_hyper_connections.py``'s ``fits``), the two passes
    over the streams are that module's kernels, forward and backward;
    anywhere else the plain array operations above. The counter
    ``mhc_fused_share`` says which ran: 1 or 0."""
    n = cfg.hc_mult
    lead, C = X.shape[:-1], X.shape[-1] // n
    flat = X.reshape(-1, n * C)                  # tokens-major, no copy
    read = {prefix + k: p[prefix + k] for k in ("phi", "alpha", "b_pre")}

    def fits(ops):
        return ops.fits(flat, n)

    def pre_plain(x, read):
        streams = x.reshape(-1, n, C)
        pqr = _project(read, prefix, streams, cfg)
        return pre_mix(streams, _read_weights(read, prefix, pqr, n)), pqr, x

    def pre_kernel(ops):
        return lambda x, read: ops.pre(
            x, read[prefix + "phi"], read[prefix + "alpha"][0],
            read[prefix + "b_pre"], eps=cfg.rms_norm_eps,
        )

    def post_plain(x, y, h_post, H):
        return post_mix(x.reshape(-1, n, C), y, h_post, H).reshape(x.shape)

    with jax.named_scope(profiling.MHC):
        share = kernel_choice.kernel_ran(_KERNELS, fits)
        h, pqr, flat = kernel_choice.kernel_or_plain(
            _KERNELS, fits, pre_kernel, pre_plain, flat, read
        )
        h_post, H = _write_coefficients(p, prefix, pqr, cfg)
        stats = {**counters(H), FUSED_SHARE: share}
    y, aux = F(h.reshape(lead + (C,)))
    with jax.named_scope(profiling.MHC):
        out = kernel_choice.kernel_or_plain(
            _KERNELS, fits, lambda ops: ops.post, post_plain,
            flat, y.reshape(-1, C), h_post, H,
        )
        return out.reshape(X.shape), aux, stats
