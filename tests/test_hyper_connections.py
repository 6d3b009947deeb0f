"""Manifold-constrained hyper-connections (models/hyper_connections.py)
on the CPU: the Sinkhorn projection and the clamp before it, the ranges
of the read and write coefficients, the mixes against their formulas,
and, with one stream and every coefficient pinned to 1, the plain
pre-norm residual layer of models/kimi_vl.py in both forms. The kernels
of ops/pallas_hyper_connections.py run in the Pallas interpreter: their
mixes against the same formulas, a sublayer through them against the
plain one with its gradients, and which path a width takes.
"""

import dataclasses
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import (  # noqa: E402
    hyper_connections as hc,
)
from actor_critic_algs_on_tensorflow_tpu.models import kimi_vl as kv  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import xing  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models.decoder import (  # noqa: E402
    rms_norm,
)
from actor_critic_algs_on_tensorflow_tpu.ops import (  # noqa: E402
    pallas_hyper_connections as kernels,
)
from tests.helpers import kernels_interpreted  # noqa: E402

# the published numbers of the residual path, at a width for the CPU
CFG = dataclasses.replace(xing.XingConfig(), hidden_size=16)
N, n, C = 512, CFG.hc_mult, CFG.hidden_size


def _params(seed, b_res, scale=0.1):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"phi": scale * jax.random.normal(k[0], (n * C, 2 * n + n * n)),
            "alpha": jnp.ones(3),
            "b_pre": 0.5 * jax.random.normal(k[1], (n,)),
            "b_post": 0.5 * jax.random.normal(k[2], (n,)),
            "b_res": b_res}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sinkhorn_is_doubly_stochastic_and_the_clamp_holds(seed):
    """``hc_sinkhorn_iters`` (20) alternations on logits that vary by
    token and hold +-60 entries (a +60 on every row, at a permutation,
    and two -60 beside them): every row and column sums to 1 within
    1e-5; the result is the iterations on ``exp`` of the logits clamped
    to +-30, and not of the logits as they came; ``h_pre`` lies in (0,
    1) and ``h_post`` in (0, 2)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    b_res = jax.random.normal(k[0], (n, n))
    for i, j in ((0, 1), (1, 2), (2, 3), (3, 0)):
        b_res = b_res.at[i, j].set(60.0)
    b_res = b_res.at[0, 0].set(-60.0).at[2, 1].set(-60.0)
    p = _params(seed, b_res)
    X = jax.random.normal(k[1], (N, n, C))
    pqr = hc._project(p, "", X, CFG)
    h_pre = hc._read_weights(p, "", pqr, n)
    h_post, H = hc._write_coefficients(p, "", pqr, CFG)
    assert H.shape == (n, n, N)
    np.testing.assert_allclose(jnp.sum(H, 0), 1.0, atol=1e-5)   # columns
    np.testing.assert_allclose(jnp.sum(H, 1), 1.0, atol=1e-5)   # rows
    assert float(hc.counters(H)[hc.SINKHORN_RESIDUAL]) < 1e-5
    # by hand: the logits, clamped, through the same iterations
    u = X.reshape(N, -1)
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + CFG.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        r = (u @ p["phi"])[:, 2 * n:].reshape(N, n, n) + b_res
    want = hc.sinkhorn(jnp.exp(jnp.moveaxis(jnp.clip(r, -30, 30), 0, -1)),
                       CFG.hc_sinkhorn_iters, CFG.hc_eps)
    np.testing.assert_allclose(H, want, atol=1e-6)
    unclamped = hc.sinkhorn(jnp.exp(jnp.moveaxis(r, 0, -1)),
                            CFG.hc_sinkhorn_iters, CFG.hc_eps)
    # beside a +30 in its row a -30 ends near e^-60 (8.8e-27), a -60
    # beside a +60 near e^-120, below what a float32 holds
    assert float(jnp.min(H[0, 0])) > 1e-30 > float(jnp.max(unclamped[0, 0]))
    assert 0.0 < float(h_pre.min()) and float(h_pre.max()) < 1.0
    assert 0.0 < float(h_post.min()) and float(h_post.max()) < 2.0
    assert float(jnp.std(h_pre)) > 0.05 and float(jnp.std(h_post)) > 0.05


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_mixes_are_their_formulas(path):
    """``sum_i h_pre_i X_i`` and ``X'_i = sum_j H_ij X_j + h_post_i y``,
    against ``einsum`` at the highest precision, as the plain form and
    as the kernels compute them (at a width whose streams tile: there
    ``h_pre`` and ``pqr`` come out of the pass itself); and the counters
    of a matrix whose diagonal is known."""
    width = C if path == "plain" else 256
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    X = jax.random.normal(k[0], (N, n, width))
    h_pre, h_post = jax.random.uniform(k[1], (2, N, n))
    H = jax.random.uniform(k[2], (n, n, N))
    y = jax.random.normal(k[3], (N, width))
    with jax.default_matmul_precision("highest"):
        if path == "plain":
            h = hc.pre_mix(X, h_pre)
            out = hc.post_mix(X, y, h_post, H)
        else:
            cfg = dataclasses.replace(CFG, hidden_size=width)
            p = _params(5, jnp.zeros((n, n)))
            p["phi"] = 0.1 * jax.random.normal(k[4],
                                               (n * width, 2 * n + n * n))
            x = X.reshape(N, n * width)
            h, pqr, x_out = kernels.pre(x, p["phi"], p["alpha"][0],
                                        p["b_pre"], eps=cfg.rms_norm_eps,
                                        interpret=True)
            np.testing.assert_array_equal(x_out, x)
            want = hc._project(p, "", X, cfg)
            np.testing.assert_allclose(pqr, want, rtol=1e-5, atol=2e-5)
            h_pre = hc._read_weights(p, "", pqr, n)
            out = kernels.post(x, y, h_post, H, interpret=True).reshape(
                X.shape)
        np.testing.assert_allclose(
            h, jnp.einsum("ti,tic->tc", h_pre, X), atol=1e-5)
        np.testing.assert_allclose(
            out,
            jnp.einsum("ijt,tjc->tic", H, X) + h_post[..., None] * y[:, None],
            atol=1e-5)
    eye = jnp.broadcast_to(jnp.eye(n)[..., None], (n, n, N))
    assert float(hc.counters(eye)[hc.OFFDIAG_MASS]) == 0.0
    assert float(hc.counters(eye)[hc.SINKHORN_RESIDUAL]) == 0.0
    flat = jnp.full((n, n, N), 1.0 / n)
    assert float(hc.counters(flat)[hc.OFFDIAG_MASS]) == pytest.approx(0.75)


# the kernels of ops/pallas_hyper_connections.py, interpreted ---------------


def _through_the_kernels(monkeypatch):
    """Make ``sublayer`` take the kernels' side of its platform choice,
    their bodies in the interpreter; the names of the passes called."""
    calls = []
    for name in ("pre", "post"):
        def counted(*args, _kernel=getattr(kernels, name), _name=name,
                    **kwargs):
            calls.append(_name)
            return _kernel(*args, **kwargs)

        monkeypatch.setattr(kernels, name, counted)
    kernels_interpreted(monkeypatch)
    return calls


@pytest.fixture
def kernel_calls(monkeypatch):
    return _through_the_kernels(monkeypatch)


def _sublayer_loss(p, X, W, v, cfg):
    """A toy ``F(h) = tanh((h + v) W)``, so that ``v``'s gradient is the
    cotangent of ``F``'s input summed over the tokens."""
    def F(h):
        return jnp.tanh((h + v) @ W), None

    out, _, stats = hc.sublayer(p, "", X, F, cfg)
    return jnp.sum(jnp.sin(out)), stats


def test_a_sublayer_through_the_kernels_is_the_plain_one(monkeypatch):
    """At a width whose streams tile (``C`` 256, 128 tokens: two grid
    steps, so ``dPhi`` is summed over tiles), the sublayer's output and
    its gradients with respect to the streams, ``Phi``, the three gains,
    ``b_pre``, ``b_post``, ``B_res``, ``F``'s weight and ``F``'s input
    through the kernels (interpreted) are the plain path's, at float32
    tolerances. The gains are 1 and ``Phi`` large enough that every
    coefficient moves with its inputs."""
    width, tokens = 256, 128
    cfg = dataclasses.replace(CFG, hidden_size=width, hc_sinkhorn_iters=5)
    k = jax.random.split(jax.random.PRNGKey(11), 5)
    p = _params(11, jax.random.normal(k[0], (n, n)), scale=0.3)
    p["phi"] = 0.3 * jax.random.normal(k[1], (n * width, 2 * n + n * n))
    X = jax.random.normal(k[2], (tokens, n * width))
    W = 0.1 * jax.random.normal(k[3], (width, width))
    v = 0.1 * jax.random.normal(k[4], (width,))
    grad = jax.value_and_grad(_sublayer_loss, (0, 1, 2, 3), has_aux=True)
    (want, plain_stats), want_grads = grad(p, X, W, v, cfg)
    with monkeypatch.context() as m:
        calls = _through_the_kernels(m)
        (got, stats), got_grads = grad(p, X, W, v, cfg)
    assert calls == ["pre", "post"]
    assert float(stats[hc.FUSED_SHARE]) == 1.0
    assert float(plain_stats[hc.FUSED_SHARE]) == 0.0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(leaves) == 8
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(got_grads)):
        scale = float(jnp.max(jnp.abs(a)))
        assert scale > 0, path
        np.testing.assert_allclose(b, a, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("width, fused", [(256, True), (16, False)])
def test_a_width_that_does_not_tile_runs_the_plain_path(
    width, fused, kernel_calls
):
    """With the platform's choice made for the TPU: streams of 4 x 256
    take the kernels and count ``mhc_fused_share`` 1; streams of 4 x 16
    (not a lane tile) take the plain path whatever the platform, call
    no kernel and count 0. On the CPU's own choice it is 0 either way
    (``test_a_sublayer_through_the_kernels_is_the_plain_one``)."""
    cfg = dataclasses.replace(CFG, hidden_size=width, hc_sinkhorn_iters=3)
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    p = _params(3, jax.random.normal(k[0], (n, n)))
    p["phi"] = 0.1 * jax.random.normal(k[1], (n * width, 2 * n + n * n))
    X = jax.random.normal(k[2], (64, n * width))
    assert kernels.fits(X, n) is fused
    W, v = jnp.eye(width), jnp.zeros(width)
    _, stats = _sublayer_loss(p, X, W, v, cfg)
    assert kernel_calls == (["pre", "post"] if fused else [])
    assert float(stats[hc.FUSED_SHARE]) == float(fused)


# one stream, coefficients pinned to 1: the plain residual layer ------------

KIMI = PRESETS["ppo-kimivl-tiny"][1]["seq_model"]
# one stream: all of a config that the pinned path reads
ONE = types.SimpleNamespace(hc_mult=1, rms_norm_eps=1e-6)
T, B = 7, 2


@pytest.fixture(scope="module")
def kimi_layers():
    model = kv.KimiVLActorCritic(cfg=KIMI, cache_len=T)
    return model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, B), jnp.int32),
        jnp.zeros((1, B)), model.initialize_carry(B),
    )["params"]


@pytest.mark.parametrize("form", ["sequence", "step"])
@pytest.mark.parametrize("layer", [0, 1])
def test_one_stream_pinned_to_one_is_kimi_vls_residual_layer(
    form, layer, kimi_layers, monkeypatch
):
    """``n = 1`` with ``h_pre = h_post = H_res = 1``: ``x + MLA(N(x))``
    then ``x + F(N(x))``, Kimi-VL's own decoder layer (the dense one and
    an expert one), in the sequence form and in the step form through
    the cache."""
    def ones(*shape):
        return jnp.ones(shape)

    monkeypatch.setattr(hc, "_read_weights",
                        lambda p, prefix, pqr, n: ones(len(pqr), 1))
    monkeypatch.setattr(hc, "_write_coefficients",
                        lambda p, prefix, pqr, cfg: (ones(len(pqr), 1),
                                                     ones(1, 1, len(pqr))))
    # (one stream's read coefficients, which the pinned ones replace)
    p = dict(kimi_layers[f"layer_{layer}"], alpha=ones(3), b_pre=ones(1),
             phi=ones(KIMI.hidden_size, 3))
    expert = KIMI.is_expert_layer(layer)
    x = jax.random.normal(jax.random.PRNGKey(layer), (T, B, KIMI.hidden_size))
    eps = KIMI.rms_norm_eps
    ffn = lambda h: kv.feed_forward_branch(p, h, KIMI, jnp.float32, expert)
    if form == "sequence":
        attention = lambda h: (
            kv.mla_seq(p, rms_norm(h, p["input_norm"], eps), KIMI,
                       jnp.float32), None)
        X, _, _ = hc.sublayer(p, "", hc.expand(x, 1), attention, ONE)
        X, _, _ = hc.sublayer(p, "", X, ffn, ONE)
        want, _ = kv._decoder_layer_seq(p, x, KIMI, jnp.float32, expert)
        np.testing.assert_allclose(hc.reduce(X, 1), want, atol=1e-6)
        return
    caches = jax.random.normal(
        jax.random.PRNGKey(9), (B, KIMI.num_hidden_layers, T,
                                KIMI.cache_width))
    pos = jnp.array([3, 5])
    attention = lambda h: kv.mla_step(
        p, rms_norm(h, p["input_norm"], eps), caches, layer, pos, KIMI,
        jnp.float32)
    X, got_caches, _ = hc.sublayer(p, "", hc.expand(x[0], 1), attention, ONE)
    X, _, _ = hc.sublayer(p, "", X, ffn, ONE)
    want, want_caches, _ = kv._decoder_layer_step(
        p, x[0], caches, layer, pos, KIMI, jnp.float32, expert)
    np.testing.assert_allclose(hc.reduce(X, 1), want, atol=1e-6)
    np.testing.assert_array_equal(got_caches, want_caches)
