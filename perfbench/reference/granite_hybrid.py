"""Plain reference of the Granite-4.0-H-Micro policy: the forward pass
in straightforward ``jax.numpy``, for the comparison that decides
``correct``. Run it under ``jax.default_matmul_precision("highest")``.

Source: the published ``config.json`` of ibm-granite/granite-4.0-h-micro
(``model_type: granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json)
and the layer equations of the loader that reads it (``transformers``,
``modeling_granitemoehybrid.py``: ``GraniteMoeHybridMambaLayer``,
``GraniteMoeHybridAttention``, ``GraniteMoeHybridMLP``,
``GraniteMoeHybridRMSNormGated``, ``GraniteMoeHybridDecoderLayer``); the
state-space recurrence is section 3 of Dao & Gu 2024, "Transformers are
SSMs", arXiv:2405.21060. Written from the equations, not from the
program: a Mamba-2 layer is NEITHER the chunked form NOR a carried
state but the recurrence's unrolled sum,

    y_t = sum_{s <= t} exp(c_t - c_s) (C_t . B_s) Delta_s x_s + D x_t,

``c`` the cumulative sum of ``Delta A`` over the whole sequence: one
``[T, T]`` lower-triangular matrix a head and one product (67 MB an env
a layer at ``T = 512``, so the gradient fits in parts of a few envs,
where a scan over tokens would keep 1 GiB of states an env a layer).
The convolution is four shifted adds, attention one masked softmax over
the whole sequence: no cache, no carry, no chunks, no kernels. It
imports nothing from the package and reads the program's parameter tree
by its names.

The model: ``x_0 = embedding_multiplier E[token]``; layer ``i``: ``x +=
r Mixer(N(x)); x += r SwiGLU(N(x))`` with ``r = residual_multiplier``,
the mixer by ``layer_types[i]``; ``logits = N(x) E^T / logits_scaling``.
``N`` is the plain RMSNorm ``w x rsqrt(mean(x^2) + eps)``. The Mamba-2
mixer: ``[z | xBC | dt] = y W_in``; ``xBC = silu(conv(xBC) + b)``, a
causal depthwise convolution of 4 taps; ``[x | B | C] = xBC``, ``B`` and
``C`` shared by the heads; ``Delta = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; the sum above; ``g = y silu(z)``, normed over the whole
inner width AFTER the gate; ``g W_out``. The attention: grouped-query,
causal, no positions, no query/key norm, scores scaled by
``attention_multiplier``.

Departures from the published model, each the configuration file's: the
value head ``w_v . N(x) + b_v`` is this system's; the weights are
seeded, no checkpoint's (their initialisation is the program's, listed
under ``assumed``); the layers are the ``held["layer_types"]`` and the
vocabulary the ``held["vocab_size"]`` rows of the tied embedding.

Precision. As written it is float32 throughout. The configuration
states less for one kind of operation: the inputs of every matrix
product of a weight or of attention (projections, scores, values,
feed-forward, head) are rounded to bfloat16 and accumulated in float32,
while norms, convolution, ``Delta``, decays, the sum's two products,
softmax and the value head stay float32. ``products=jnp.bfloat16``
computes exactly that, and is what the program is held to. The steps
below the stated precision, which the comparison has to tell from it,
are ``lower``: ``"scan"`` (the inputs of the sum's two products, ``C .
B`` and the weighted sum over ``s``, rounded to bfloat16), ``"norms"``
(every RMSNorm, the gated one included, in bfloat16) and ``"state"``
(what a rollout through a bfloat16 state would give: for this control
alone the layer IS a recurrence over tokens, its state rounded to
bfloat16 after every step, since an unrolled sum has no state to
round); and ``dtype=jnp.bfloat16``: parameters and everything else in
it. ``remat`` recomputes each layer in the backward pass and changes no
value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.kimi_vl import _norm, objective
from perfbench.reference.qwen3_next import (  # noqa: F401  (re-exported)
    Precision,
    _mm,
    _silu,
    categorical,
    whiten,
)


def _scan_product(spec, a, b, prec):
    """A product of the unrolled sum: float32 as it comes, or with
    bfloat16 inputs and float32 sums where the scan is a step below."""
    if "scan" not in prec.lower:
        return jnp.einsum(spec, a, b)
    return jnp.einsum(
        spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(a.dtype)


def unrolled_sum(x, delta, A, B, C, prec=Precision()):
    """``y_t = sum_{s <= t} exp(c_t - c_s) (C_t . B_s) Delta_s x_s``:
    ``x [T, b, h, p]``, ``delta [T, b, h]``, ``A [h]``, ``B, C [T, b,
    n]`` -> ``[T, b, h, p]``."""
    T = x.shape[0]
    c = jnp.cumsum(delta * A, axis=0)                       # [T, b, h]
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])
    causal = causal[:, :, None, None]
    diff = c[:, None] - c[None, :]                          # [t, s, b, h]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    weights = _scan_product("tbn,sbn->tsb", C, B, prec)[..., None] * decay
    return _scan_product(
        "tsbh,sbhp->tbhp", weights, delta[..., None] * x, prec
    )


def _recurrence_in_bfloat16(x, delta, A, B, C, remat):
    """The ``"state"`` control: the recurrence a token at a time, its
    state held in bfloat16."""
    T, b, h, p = x.shape

    def step(S, xs):
        x_t, d_t, B_t, C_t = xs
        S = S.astype(jnp.float32) * jnp.exp(d_t * A)[..., None, None] + (
            (d_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        )
        S = S.astype(jnp.bfloat16)
        y = jnp.sum(S.astype(jnp.float32) * C_t[:, None, None, :], -1)
        return S, y

    S0 = jnp.zeros((b, h, p, B.shape[-1]), jnp.bfloat16)
    steps = (x, delta, B, C)
    if remat and T % 16 == 0:
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((T // 16, 16) + a.shape[1:]), steps
        )
        _, y = jax.lax.scan(
            jax.checkpoint(lambda S, block: jax.lax.scan(step, S, block)),
            S0, blocks,
        )
        return y.reshape((T,) + y.shape[2:])
    return jax.lax.scan(step, S0, steps)[1]


def mamba(p, x, model, remat=False, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: the Mamba-2 mixer from an empty
    history."""
    T, b, _ = x.shape
    d = model["mamba_expand"] * model["hidden_size"]
    h, ph = model["mamba_n_heads"], model["mamba_d_head"]
    n, K = model["mamba_d_state"], model["mamba_d_conv"]
    zxbcdt = _mm("tbh,hd->tbd", x, p["in_proj"], prec)
    z, xBC, dt = (zxbcdt[..., :d], zxbcdt[..., d:d + d + 2 * n],
                  zxbcdt[..., d + d + 2 * n:])
    # causal depthwise convolution over time: four shifted adds
    padded = jnp.concatenate(
        [jnp.zeros((K - 1,) + xBC.shape[1:], xBC.dtype), xBC], 0
    )
    conv = jnp.zeros_like(xBC) + p["conv_bias"]
    for j in range(K):
        conv = conv + padded[j: j + T] * p["conv"][j]
    xBC = _silu(conv)
    xs = xBC[..., :d].reshape(T, b, h, ph)
    B, C = xBC[..., d:d + n], xBC[..., d + n:]
    delta = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    if "state" in prec.lower:
        y = _recurrence_in_bfloat16(
            *(a.astype(jnp.float32) for a in (xs, delta, A, B, C)), remat
        ).astype(x.dtype)
    else:
        y = unrolled_sum(xs, delta, A, B, C, prec)
    y = y + p["D"][:, None] * xs
    g = _norm(y.reshape(T, b, d) * _silu(z), p["mamba_norm"],
              model["rms_norm_eps"], prec)
    return _mm("tbd,dh->tbh", g, p["out_proj"], prec)


def attention(p, x, model, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: causal grouped-query softmax
    attention with no position of any kind."""
    T, b, H = x.shape
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = H // nh
    q = _mm("tbh,hd->tbd", x, p["q_proj"], prec).reshape(T, b, nh, hd)
    k = _mm("tbh,hd->tbd", x, p["k_proj"], prec).reshape(T, b, nkv, hd)
    v = _mm("tbh,hd->tbd", x, p["v_proj"], prec).reshape(T, b, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)  # query head j reads j // (nh/nkv)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = _mm("tbhd,sbhd->bhts", q, k, prec) * model["attention_multiplier"]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = _mm("bhts,sbhd->tbhd", probs, v, prec)
    return _mm("tbd,dh->tbh", out.reshape(T, b, nh * hd), p["o_proj"], prec)


def feed_forward(p, x, prec=Precision()):
    hidden = _silu(_mm("tbh,hi->tbi", x, p["mlp_gate"], prec)) * _mm(
        "tbh,hi->tbi", x, p["mlp_up"], prec
    )
    return _mm("tbi,ih->tbh", hidden, p["mlp_down"], prec)


def forward(params, tokens, model, held, dtype=jnp.float32, remat=False,
            products=None, lower=()):
    """``tokens [T, B]`` int -> ``(logits [T, B, V], values [T, B])``,
    every sequence from its first token. ``params`` is the program's
    tree (``{"params": {"embedding", "layer_<i>": {...}, "final_norm",
    "value_w", "value_b"}}``; the head is the embedding)."""
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype), params["params"])
    prec = Precision(products, frozenset(lower))
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    x = model["embedding_multiplier"] * p["embedding"][tokens]

    def layer(lp, x, kind):
        h = _norm(x, lp["input_norm"], eps, prec)
        if kind == "mamba":
            x = x + r * mamba(lp, h, model, remat, prec)
        else:
            x = x + r * attention(lp, h, model, prec)
        h = _norm(x, lp["post_norm"], eps, prec)
        return x + r * feed_forward(lp, h, prec)

    for i, kind in enumerate(held["layer_types"]):
        f = lambda lp, x, k=kind: layer(lp, x, k)
        x = (jax.checkpoint(f) if remat else f)(p[f"layer_{i}"], x)
    h = _norm(x, p["final_norm"], eps, prec)
    logits = _mm("tbh,vh->tbv", h, p["embedding"], prec).astype(
        jnp.float32
    ) / model["logits_scaling"]
    values = (h @ p["value_w"] + p["value_b"]).astype(jnp.float32)
    return logits, values


def ppo_loss(params, batch, hp, model, held, remat=True, whitened=False,
             **precision):
    """The PPO objective of ``ppo_loss.py`` (``kimi_vl.objective``: same
    source, same departures) on whole sequences: ``batch`` holds ``obs``
    (tokens), ``actions``, ``old_log_probs``, ``old_values``,
    ``advantages``, ``returns``, each ``[T, B]``; advantages are
    whitened over the batch, or come ``whitened`` (over a larger batch
    of which this is one equal part). Returns ``(total, parts)``."""
    logits, values = forward(params, batch["obs"], model, held, remat=remat,
                             **precision)
    return objective(logits, values, batch, hp, whitened)
