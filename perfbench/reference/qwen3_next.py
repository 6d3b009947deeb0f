"""Plain reference of the Qwen3-Next policy: the forward pass in
straightforward ``jax.numpy``, for the comparison that decides
``correct``. Run it under ``jax.default_matmul_precision("highest")``.

Source: the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type: qwen3_next``,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
and the layer equations of the loader that reads it (``transformers``,
``modeling_qwen3_next.py``); the recurrence is eq. (10) of Yang et al.
2024, "Gated Delta Networks", arXiv:2412.06464. Written from the
equations, not from the program: the Gated DeltaNet is the step-by-step
recurrence (a ``lax.scan`` over time, no chunks), attention is one
masked softmax over the whole sequence, the experts are a loop over the
held experts in which every token goes through every expert under a
dense weight (zero where the token did not choose it): no cache, no
carry, no sorting, no grouped products. It imports nothing from the
package and reads the program's parameter tree by its names.

Layer ``i`` of a period of ``full_attention_interval``: ``x += Mixer(N(x));
x += Experts(N(x))``; the mixer is gated attention where ``(i + 1) %
interval == 0``, else Gated DeltaNet. ``N`` is the zero-centred RMSNorm
``x rsqrt(mean(x^2) + eps) (1 + w)``.

Departures from the published model, each the configuration file's:
the multi-token-prediction module is left out (the published loader
leaves it out too); no router auxiliary loss; the value head ``w_v .
N(x) + b_v`` is this system's; of ``num_experts`` routed experts only
``held["experts_held"]`` from ``held["first_expert"]`` on are computed —
the router is whole, top-k and its renormalisation are over all
experts, and what the absent experts would add is left out — and the
vocabulary is the ``held["vocab_size"]`` rows of embedding and head.

Precision. As written it is float32 throughout. The configuration
states less for one kind of operation: the inputs of every matrix
product of a weight or of attention (projections, scores, values,
experts, shared expert and its gate, head) are rounded to bfloat16 and
accumulated in float32, while norms, router (product, softmax, top-k),
DeltaNet gates, state and recurrence, softmax and the value head stay
float32. ``products=jnp.bfloat16`` computes exactly that, and is what
the program is held to: against float32 a top-10 of 512 router trades
its tenth and eleventh expert under the products' rounding alone, and
that hides everything else (PERF.md section 6, PR 27). The steps below
the stated precision, which the comparison has to tell from it, are
``lower``: a set of ``"state"`` (the DeltaNet's state and recurrence),
``"router"`` (the router's softmax, top-k and renormalisation) and
``"norms"`` (every RMSNorm) run in bfloat16 with all else as stated;
and ``dtype=jnp.bfloat16``: parameters and everything else in it.
``remat`` recomputes each layer in the backward pass and changes no
value; the gradient of 8,192 tokens does not fit beside the weights
without it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Precision:
    """``products``: the dtype a matrix product's inputs are rounded to
    (None: as they come); ``lower``: the components run in bfloat16."""

    products: Any = None
    lower: frozenset = frozenset()

    def at(self, name, x):
        """``x`` as the component ``name`` computes on it."""
        return x.astype(jnp.bfloat16) if name in self.lower else x


def _mm(spec, a, b, prec):
    """The matrix product ``spec`` of ``a`` and ``b`` in the dtype of
    ``a``, its inputs rounded to ``prec.products``, float32 sums."""
    if prec.products is None:
        return jnp.einsum(spec, a, b)
    return jnp.einsum(
        spec, a.astype(prec.products), b.astype(prec.products),
        preferred_element_type=jnp.float32,
    ).astype(a.dtype)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _norm(x, w, eps, prec):
    return (_rms(prec.at("norms", x), eps) * (1 + prec.at("norms", w))
            ).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rotate(x, positions, model):
    """Rotary embedding, rotate-half convention, on the first
    ``partial_rotary_factor`` of ``head_dim``; ``x [T, B, heads, hd]``,
    ``positions [T]``."""
    rot = int(model["head_dim"] * model["partial_rotary_factor"])
    inv_freq = 1.0 / (
        model["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    )
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, None, :]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    r, rest = x[..., :rot], x[..., rot:]
    r1, r2 = r[..., : rot // 2], r[..., rot // 2:]
    rotated = jnp.concatenate([-r2, r1], -1)
    return jnp.concatenate([r * cos + rotated * sin, rest], -1)


def gated_attention(p, x, model, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: causal softmax attention with
    a sigmoid output gate, grouped-query, QK-norm, partial rotary."""
    T, B, _ = x.shape
    nh, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    eps = model["rms_norm_eps"]
    qg = _mm("tbh,hd->tbd", x, p["q_proj"], prec).reshape(T, B, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm("tbh,hd->tbd", x, p["k_proj"], prec).reshape(T, B, nkv, hd)
    v = _mm("tbh,hd->tbd", x, p["v_proj"], prec).reshape(T, B, nkv, hd)
    positions = jnp.arange(T)
    q = _rotate(_norm(q, p["q_norm"], eps, prec), positions, model)
    k = _rotate(_norm(k, p["k_norm"], eps, prec), positions, model)
    k = jnp.repeat(k, nh // nkv, axis=2)  # each KV head serves nh/nkv
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = _mm("tbhd,sbhd->bhts", q, k, prec) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    out = _mm("bhts,sbhd->tbhd", probs, v, prec) * jax.nn.sigmoid(gate)
    return _mm("tbd,dh->tbh", out.reshape(T, B, nh * hd), p["o_proj"], prec)


def gated_deltanet(p, x, model, remat=False, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: the recurrence, one step at a
    time, from an empty state. ``remat`` walks the same steps in blocks
    of 16 whose insides the backward pass recomputes: a gradient that
    kept the state of every step would hold ``T`` copies of it."""
    T, B, _ = x.shape
    nk, nv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    K, r = model["linear_conv_kernel_dim"], nv // nk
    qkvz = _mm("tbh,hd->tbd", x, p["in_proj_qkvz"], prec).reshape(
        T, B, nk, 2 * dk + 2 * r * dv
    )
    ba = _mm("tbh,hd->tbd", x, p["in_proj_ba"], prec).reshape(T, B, nk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, B, nv, dv)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(T, B, nv))
    a = ba[..., r:].reshape(T, B, nv)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    # causal depthwise convolution over time, then SiLU
    mixed = jnp.concatenate([
        q.reshape(T, B, nk * dk), k.reshape(T, B, nk * dk),
        v.reshape(T, B, nv * dv),
    ], -1)
    padded = jnp.concatenate(
        [jnp.zeros((K - 1,) + mixed.shape[1:], mixed.dtype), mixed], 0
    )
    conv = jnp.zeros_like(mixed)
    for j in range(K):
        conv = conv + padded[j: j + T] * p["conv"][j]
    conv = _silu(conv)
    q = conv[..., : nk * dk].reshape(T, B, nk, dk)
    k = conv[..., nk * dk: 2 * nk * dk].reshape(T, B, nk, dk)
    v = conv[..., 2 * nk * dk:].reshape(T, B, nv, dv)
    q = jnp.repeat(q, r, axis=2)
    k = jnp.repeat(k, r, axis=2)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)

    def step(S, xs):  # S [B, nv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        delta = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)) * beta_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    steps = jax.tree_util.tree_map(
        lambda a: prec.at("state", a), (q, k, v, g.astype(x.dtype), beta)
    )
    S0 = jnp.zeros((B, nv, dk, dv), steps[0].dtype)
    if remat and T % 16 == 0:
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((T // 16, 16) + a.shape[1:]), steps
        )
        _, o = jax.lax.scan(
            jax.checkpoint(lambda S, block: jax.lax.scan(step, S, block)),
            S0, blocks,
        )
        o = o.reshape((T,) + o.shape[2:])
    else:
        _, o = jax.lax.scan(step, S0, steps)
    o = o.astype(x.dtype)
    o = (_rms(prec.at("norms", o), model["rms_norm_eps"])
         * prec.at("norms", p["gdn_norm"])).astype(x.dtype) * _silu(z)
    return _mm("tbd,dh->tbh", o.reshape(T, B, nv * dv), p["out_proj"], prec)


def expert_block(p, x, model, first_expert: int, experts_held: int,
                 prec=Precision()):
    """``x [N, H]`` -> the routed sum over the held experts plus the
    shared expert. ``p["w_gate"]`` etc. hold the held experts only, in
    order from ``first_expert``."""
    k = model["num_experts_per_tok"]
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(prec.at("router", logits), -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if model["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p.astype(x.dtype)

    def ffn(w_gate, w_up, w_down):
        hidden = _silu(_mm("nh,hi->ni", x, w_gate, prec)) * _mm(
            "nh,hi->ni", x, w_up, prec
        )
        return _mm("ni,ih->nh", hidden, w_down, prec)

    def one_expert(y, xs):
        e, w_gate, w_up, w_down = xs
        # the token's weight for expert e, zero where it did not choose it
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0), -1)
        return y + weight[:, None] * ffn(w_gate, w_up, w_down), None

    experts = first_expert + jnp.arange(experts_held)
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (experts, p["w_gate"], p["w_up"], p["w_down"]),
    )
    shared = ffn(p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"])
    gate = jax.nn.sigmoid(_mm("nh,ho->no", x, p["shared_gate"], prec))
    return routed + gate * shared


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

    def layer(lp, x, attention):
        h = _norm(x, lp["input_norm"], eps, prec)
        if attention:
            x = x + gated_attention(lp, h, model, prec)
        else:
            x = x + gated_deltanet(lp, h, model, remat, prec)
        h = _norm(x, lp["post_norm"], eps, prec)
        y = expert_block(lp, h.reshape(T * B, H), model,
                         held["first_expert"], held["experts_held"], prec)
        return x + y.reshape(T, B, H)

    for i in range(held["num_hidden_layers"]):
        attention = (i + 1) % model["full_attention_interval"] == 0
        f = lambda lp, x, a=attention: layer(lp, x, a)
        x = (jax.checkpoint(f) if remat else f)(p[f"layer_{i}"], x)
    h = _norm(x, p["final_norm"], eps, prec)
    logits = _mm("tbh,hv->tbv", h, p["lm_head"], prec).astype(jnp.float32)
    values = (h @ p["value_w"] + p["value_b"]).astype(jnp.float32)
    return logits, values


def categorical(logits, actions):
    """Log-probability of ``actions`` and the entropy, ``[...]``."""
    log_p = jax.nn.log_softmax(logits, -1)
    taken = jnp.take_along_axis(log_p, actions[..., None], -1)[..., 0]
    return taken, -jnp.sum(jnp.exp(log_p) * log_p, -1)


def whiten(adv):
    return (adv - jnp.mean(adv)) / jnp.sqrt(
        jnp.mean((adv - jnp.mean(adv)) ** 2) + 1e-8
    )


def ppo_loss(params, batch, hp, model, held, remat=True, whitened=False,
             **precision):
    """The PPO objective of ``ppo_loss.py`` (same source, same
    departures) on whole sequences: ``batch`` holds ``obs`` (tokens),
    ``actions``, ``old_log_probs``, ``old_values``, ``advantages``,
    ``returns``, each ``[T, B]``; advantages are whitened over the
    batch, or come ``whitened`` (over a larger batch of which this is
    one equal part: every term is a mean over tokens, so the whole
    batch's loss and gradient are the means of the parts'). Returns
    ``(total, parts)``."""
    logits, values = forward(params, batch["obs"], model, held, remat=remat,
                             **precision)
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
