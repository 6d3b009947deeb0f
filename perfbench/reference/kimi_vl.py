"""Plain reference of the Kimi-VL-A3B language-model policy: the
forward pass in straightforward ``jax.numpy``, for the comparison that
decides ``correct``. Run it under
``jax.default_matmul_precision("highest")``.

Source: the published ``config.json`` of moonshotai/Kimi-VL-A3B-Instruct
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json,
the language model's keys; the DeepSeek-V3 layout) and the layer
equations of the loader that reads it (``modeling_deepseek.py``);
latent attention is section 2.1 of DeepSeek-AI 2024, "DeepSeek-V2",
arXiv:2405.04434. Written from the equations, not from the program:
attention is the EXPANDED form only — every token's latent is carried
up into per-head keys and values, the one rope key is repeated for every
head, and one masked softmax runs over the whole sequence — with no
cache, no carry and no absorbed product; the experts are a loop over the
held experts in which every token goes through every expert under a
dense weight (zero where the token did not choose it): no sorting, no
grouped products. It imports nothing from the package and reads the
program's parameter tree by its names.

Layer ``l``: ``x += MLA(N(x)); x += F_l(N(x))``; ``F_l`` is a dense
SwiGLU for ``l < first_k_dense_replace`` and the expert block after it.
``N`` is the plain RMSNorm ``w x rsqrt(mean(x^2) + eps)``. Rotary
embedding: the interleaved pairs ``(x[2i], x[2i + 1])`` of the rope
dimensions are rotated in place by ``t theta^(-2i / d)`` (the program
lays the rotated pairs out as halves, for queries and keys alike, which
no dot product sees). The gate is ``noaux_tc`` at ``n_group = topk_group
= 1``: sigmoid scores, the top-k of score + bias, the weights the scores
without the bias, renormalised (``+ 1e-20``) and scaled by
``routed_scaling_factor``.

Departures from the published model, each the configuration file's: no
vision tower and no projector (the input is token ids); no auxiliary
sequence-balance loss; the bias is a seeded constant; the value head
``w_v . N(x) + b_v`` is this system's; of ``n_routed_experts`` only
``held["experts_held"]`` from ``held["first_expert"]`` on are computed —
the router is whole, top-k and its renormalisation are over all experts,
and what the absent experts would add is left out — and the vocabulary
is the ``held["vocab_size"]`` rows of embedding and head.

Precision. As written it is float32 throughout. The configuration
states less for one kind of operation: the inputs of every matrix
product of a weight or of attention (projections, the latents carried
up, scores, values, dense layer, experts, head) are rounded to bfloat16
and accumulated in float32, while norms, router (product, sigmoid,
top-k, weights), softmax and the value head stay float32.
``products=jnp.bfloat16`` computes exactly that, and is what the program
is held to (``reference/qwen3_next.py`` says why). The latents and the
rope key are inputs of such products, so the stated precision of the
program's cache of them is bfloat16 and needs no argument here. The
steps below the stated precision, which the comparison has to tell from
it, are ``lower``: a set of ``"cache"`` (the latents and the rope key
rounded to 8 bits, float8 e4m3, the step below the bfloat16 they are
held in), ``"router"`` (sigmoid, bias, top-k and weights in bfloat16),
``"softmax"`` (the attention's softmax in bfloat16) and ``"norms"``
(every RMSNorm in bfloat16) with all else as stated; and
``dtype=jnp.bfloat16``: parameters and everything else in it. ``remat``
recomputes each layer in the backward pass and changes no value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.qwen3_next import (  # noqa: F401  (re-exported)
    Precision,
    _mm,
    _silu,
    categorical,
    whiten,
)


def _norm(x, w, eps, prec):
    xn, wn = prec.at("norms", x), prec.at("norms", w)
    return (
        xn * jax.lax.rsqrt(jnp.mean(xn * xn, -1, keepdims=True) + eps) * wn
    ).astype(x.dtype)


def _rotate(x, positions, theta):
    """``x [T, ..., d]``, ``positions [T]``: the interleaved pairs
    rotated in place."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape
    )


def _cached(x, prec):
    """What attention reads of a token's latent or rope key: as it
    comes, or through 8 bits where the cache is a step below."""
    if "cache" in prec.lower:
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x


def latent_attention(p, x, model, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: causal multi-head latent
    attention, expanded."""
    T, B, _ = x.shape
    nh, rank = model["num_attention_heads"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, theta = model["v_head_dim"], model["rope_theta"]
    positions = jnp.arange(T)
    q = _mm("tbh,hd->tbd", x, p["q_proj"], prec).reshape(T, B, nh, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], _rotate(q[..., dn:], positions, theta)], -1
    )
    kva = _mm("tbh,hd->tbd", x, p["kv_a_proj"], prec)
    c = _norm(kva[..., :rank], p["kv_a_norm"], model["rms_norm_eps"], prec)
    c = _cached(c, prec)
    k_rope = _cached(_rotate(kva[..., rank:], positions, theta), prec)
    kv = _mm("tbc,cd->tbd", c, p["kv_b_proj"], prec).reshape(
        T, B, nh, dn + dv
    )
    # one rope key for all heads, beside each head's own key
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_rope[:, :, None, :], (T, B, nh, dr))], -1
    )
    v = kv[..., dn:]
    scores = _mm("tbhd,sbhd->bhts", q, k, prec) * (dn + dr) ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(prec.at("softmax", scores), -1).astype(x.dtype)
    out = _mm("bhts,sbhd->tbhd", probs, v, prec)
    return _mm("tbd,dh->tbh", out.reshape(T, B, nh * dv), p["o_proj"], prec)


def _ffn(x, w_gate, w_up, w_down, prec):
    hidden = _silu(_mm("nh,hi->ni", x, w_gate, prec)) * _mm(
        "nh,hi->ni", x, w_up, prec
    )
    return _mm("ni,ih->nh", hidden, w_down, prec)


def gate(p, x, model, prec=Precision()):
    """``x [N, H]`` -> the chosen experts ``[N, k]`` and their weights:
    the bias chooses, the scores weigh."""
    logits = (x @ p["router"]).astype(jnp.float32)
    scores = jax.nn.sigmoid(prec.at("router", logits))
    bias = prec.at("router", p["e_score_correction_bias"].astype(jnp.float32))
    _, top_e = jax.lax.top_k(scores + bias, model["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_e, -1)
    if model["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_e, (top_w * model["routed_scaling_factor"]).astype(x.dtype)


def expert_block(p, x, model, first_expert: int, experts_held: int,
                 prec=Precision()):
    """``x [N, H]`` -> the routed sum over the held experts plus the
    shared experts. ``p["w_gate"]`` etc. hold the held experts only, in
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
    return routed + _ffn(
        x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"], prec
    )


def forward(params, tokens, model, held, dtype=jnp.float32, remat=False,
            products=None, lower=()):
    """``tokens [T, B]`` int -> ``(logits [T, B, V], values [T, B])``,
    every sequence from its first token. ``params`` is the program's
    tree (``{"params": {"embedding", "layer_<i>": {...}, "final_norm",
    "lm_head", "value_w", "value_b"}}``)."""
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype), params["params"])
    prec = Precision(products, frozenset(lower))
    eps = model["rms_norm_eps"]
    x = p["embedding"][tokens]
    T, B, H = x.shape

    def layer(lp, x, expert):
        h = _norm(x, lp["input_norm"], eps, prec)
        x = x + latent_attention(lp, h, model, prec)
        h = _norm(x, lp["post_norm"], eps, prec).reshape(T * B, H)
        if expert:
            y = expert_block(lp, h, model, held["first_expert"],
                             held["experts_held"], prec)
        else:
            y = _ffn(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"], prec)
        return x + y.reshape(T, B, H)

    for i in range(held["num_hidden_layers"]):
        expert = (i >= model["first_k_dense_replace"]
                  and i % model["moe_layer_freq"] == 0)
        f = lambda lp, x, e=expert: layer(lp, x, e)
        x = (jax.checkpoint(f) if remat else f)(p[f"layer_{i}"], x)
    h = _norm(x, p["final_norm"], eps, prec)
    logits = _mm("tbh,hv->tbv", h, p["lm_head"], prec).astype(jnp.float32)
    values = (h @ p["value_w"] + p["value_b"]).astype(jnp.float32)
    return logits, values


def objective(logits, values, batch, hp, whitened=False):
    """``ppo_loss.py``'s clipped objective (same source, same
    departures) on whole sequences ``[T, B]``: ``(total, parts)``."""
    log_probs, entropy = categorical(logits, batch["actions"])
    adv = batch["advantages"] if whitened else whiten(batch["advantages"])
    ratio = jnp.exp(log_probs - batch["old_log_probs"])
    eps = hp["clip_eps"]
    surrogate = jnp.minimum(
        ratio * adv, jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * adv
    )
    policy_loss = -jnp.mean(surrogate)
    clipped = batch["old_values"] + jnp.clip(
        values - batch["old_values"], -eps, eps
    )
    vf = 0.5 * jnp.mean(jnp.maximum(
        (values - batch["returns"]) ** 2, (clipped - batch["returns"]) ** 2
    ))
    ent = jnp.mean(entropy)
    total = policy_loss + hp["vf_coef"] * vf - hp["ent_coef"] * ent
    return total, {"policy_loss": policy_loss, "value_loss": vf,
                   "entropy": ent}


def ppo_loss(params, batch, hp, model, held, remat=True, whitened=False,
             **precision):
    """The PPO objective on whole sequences: ``batch`` holds ``obs``
    (tokens), ``actions``, ``old_log_probs``, ``old_values``,
    ``advantages``, ``returns``, each ``[T, B]``; advantages are
    whitened over the batch, or come ``whitened`` (over a larger batch
    of which this is one equal part). Returns ``(total, parts)``."""
    logits, values = forward(params, batch["obs"], model, held, remat=remat,
                             **precision)
    return objective(logits, values, batch, hp, whitened)
