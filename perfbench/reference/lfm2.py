"""Plain reference of the LFM2-24B-A2B policy: the forward pass in
straightforward ``jax.numpy``, for the comparison that decides
``correct``. Run it under ``jax.default_matmul_precision("highest")``.

Source: the published ``config.json`` of LiquidAI/LFM2-24B-A2B
(``model_type: lfm2_moe``,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json) and
the layer equations of the loader of its dense family (``transformers``,
``modeling_lfm2.py``: ``Lfm2ShortConv``, ``Lfm2Attention``,
``Lfm2RMSNorm``, ``Lfm2DecoderLayer``, ``Lfm2Model``) with the
``lfm2_moe`` expert block (sigmoid scores, the top-k of score +
``expert_bias``, the weights the scores renormalised over their sum +
1e-6 and scaled by ``routed_scaling_factor``). Written from the
equations, not from the program: a short convolution is an explicit sum
of ``conv_L_cache`` taps over shifted copies of its input, attention is
one causal softmax over the whole sequence with the key/value heads
repeated for their query heads, and the experts are a loop over the held
experts in which every token goes through every expert under a dense
weight (zero where the token did not choose it): no cache, no tail, no
carry, no sorting, no grouped products. It imports nothing from the
package and reads the program's parameter tree by its names.

Layer ``i``: ``x += M_i(N(x)); x += F_i(N(x))``; ``M_i`` by
``held["layer_types"][i]``: the short convolution ``[B | C | x~] = y
W_in``, ``u = B x~``, ``v_t = sum_j w_j u_{t - L + 1 + j}``, ``(C v)
W_out``; or attention, the query and key normed per head and rotated
(rotate-half, all of ``head_dim``, ``rope_theta``), scores scaled by
``head_dim^(-1/2)``. ``F_i`` is a dense SwiGLU for ``i <
held["num_dense_layers"]`` and the expert block after. ``N`` is the
plain RMSNorm ``w x rsqrt(mean(x^2) + eps)``; ``logits = N(x) E^T``,
the embedding tied.

Departures from the published model, each the configuration file's: the
value head ``w_v . N(x) + b_v`` is this system's; the weights are
seeded, no checkpoint's, the selection bias among them (a constant: no
gradient reaches it and nothing updates it); of ``num_experts`` only
``held["experts_held"]`` from ``held["first_expert"]`` on are computed —
the router is whole, top-k and its renormalisation are over all
experts, and what the absent experts would add is left out — and the
vocabulary is the ``held["vocab_size"]`` rows of the tied embedding.

Precision. As written it is float32 throughout. The configuration
states less for one kind of operation: the inputs of every matrix
product of a weight or of attention (projections, scores, values, dense
layer, experts, head) are rounded to bfloat16 and accumulated in
float32, while norms, the two gates, the convolution, router (product,
sigmoid, top-k, weights), softmax and the value head stay float32.
``products=jnp.bfloat16`` computes exactly that, and is what the program
is held to. The steps below the stated precision, which the comparison
has to tell from it, are ``lower``: a set of ``"tails"`` (what a
rollout through bfloat16 tails would give: the convolution's terms on
EARLIER tokens read their ``u`` rounded to bfloat16, the current
token's term its own), ``"router"`` (sigmoid, bias, top-k and weights
in bfloat16) and ``"norms"`` (every RMSNorm, the per-head ones included,
in bfloat16) with all else as stated; and ``dtype=jnp.bfloat16``:
parameters and everything else in it. ``remat`` recomputes each layer in
the backward pass and changes no value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.kimi_vl import _ffn, _norm, objective
from perfbench.reference.qwen3_next import (  # noqa: F401  (re-exported)
    Precision,
    _mm,
    categorical,
    whiten,
)

# The lfm2_moe routing's renormalisation epsilon (the configuration
# file's `assumed`).
ROUTE_EPS = 1e-6


def _rotate(x, positions, theta):
    """Rotate-half rotary embedding over all of ``x [T, B, heads,
    d]``'s last axis, ``positions [T]``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, None, :]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def short_conv(p, x, model, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: the double-gated short
    convolution from an empty history."""
    T, L = x.shape[0], model["conv_L_cache"]
    B, C, xt = jnp.split(_mm("tbh,hd->tbd", x, p["in_proj"], prec), 3, -1)
    u = B * xt
    # tap j reads u_{t - L + 1 + j}; the earlier tokens' terms through
    # what a tail would hold
    earlier = prec.at("tails", u).astype(u.dtype)
    v = u * p["conv"][L - 1]
    for j in range(L - 1):
        shift = L - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((shift,) + u.shape[1:], u.dtype), earlier[:T - shift]], 0
        )
        v = v + shifted * p["conv"][j]
    return _mm("tbh,hd->tbd", C * v, p["out_proj"], prec)


def attention(p, x, model, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: causal grouped-query softmax
    attention, query and key normed per head, then rotated."""
    T, b, H = x.shape
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, eps = H // nh, model["norm_eps"]
    theta = dict(model["rope_parameters"])["rope_theta"]
    positions = jnp.arange(T)
    q = _mm("tbh,hd->tbd", x, p["q_proj"], prec).reshape(T, b, nh, hd)
    k = _mm("tbh,hd->tbd", x, p["k_proj"], prec).reshape(T, b, nkv, hd)
    v = _mm("tbh,hd->tbd", x, p["v_proj"], prec).reshape(T, b, nkv, hd)
    q = _rotate(_norm(q, p["q_norm"], eps, prec), positions, theta)
    k = _rotate(_norm(k, p["k_norm"], eps, prec), positions, theta)
    k = jnp.repeat(k, nh // nkv, axis=2)  # query head j reads j // (nh/nkv)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = _mm("tbhd,sbhd->bhts", q, k, prec) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = _mm("bhts,sbhd->tbhd", probs, v, prec)
    return _mm("tbd,dh->tbh", out.reshape(T, b, nh * hd), p["o_proj"], prec)


def gate(p, x, model, prec=Precision()):
    """``x [N, H]`` -> the chosen experts ``[N, k]`` and their weights:
    the bias chooses, the scores weigh."""
    logits = (x @ p["router"]).astype(jnp.float32)
    scores = jax.nn.sigmoid(prec.at("router", logits))
    bias = prec.at("router", p["expert_bias"].astype(jnp.float32))
    _, top_e = jax.lax.top_k(scores + bias, model["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_e, -1)
    if model["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + ROUTE_EPS)
    return top_e, (top_w * model["routed_scaling_factor"]).astype(x.dtype)


def expert_block(p, x, model, first_expert: int, experts_held: int,
                 prec=Precision()):
    """``x [N, H]`` -> the routed sum over the held experts (there is no
    shared expert). ``p["w_gate"]`` etc. hold the held experts only, in
    order from ``first_expert``."""
    top_e, top_w = gate(p, x, model, prec)

    def one_expert(y, xs):
        e, w_gate, w_up, w_down = xs
        # the token's weight for expert e, zero where it did not choose it
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0), -1)
        return y + weight[:, None] * _ffn(x, w_gate, w_up, w_down, prec), None

    experts = first_expert + jnp.arange(experts_held)
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (experts, p["w_gate"], p["w_up"], p["w_down"]),
    )
    return routed


def forward(params, tokens, model, held, dtype=jnp.float32, remat=False,
            products=None, lower=()):
    """``tokens [T, B]`` int -> ``(logits [T, B, V], values [T, B])``,
    every sequence from its first token. ``params`` is the program's
    tree (``{"params": {"embedding", "layer_<i>": {...}, "final_norm",
    "value_w", "value_b"}}``; the head is the embedding)."""
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype), params["params"])
    prec = Precision(products, frozenset(lower))
    eps = model["norm_eps"]
    x = p["embedding"][tokens]
    T, B, H = x.shape

    def layer(lp, x, kind, expert):
        h = _norm(x, lp["input_norm"], eps, prec)
        if kind == "conv":
            x = x + short_conv(lp, h, model, prec)
        else:
            x = x + attention(lp, h, model, prec)
        h = _norm(x, lp["post_norm"], eps, prec).reshape(T * B, H)
        if expert:
            y = expert_block(lp, h, model, held["first_expert"],
                             held["experts_held"], prec)
        else:
            y = _ffn(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"], prec)
        return x + y.reshape(T, B, H)

    for i, kind in enumerate(held["layer_types"]):
        expert = i >= held["num_dense_layers"]
        f = lambda lp, x, k=kind, e=expert: layer(lp, x, k, e)
        x = (jax.checkpoint(f) if remat else f)(p[f"layer_{i}"], x)
    h = _norm(x, p["final_norm"], eps, prec)
    logits = _mm("tbh,vh->tbv", h, p["embedding"], prec).astype(jnp.float32)
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
