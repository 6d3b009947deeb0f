"""Plain reference of the Xing4.0-29B-A4B policy: the forward pass in
straightforward ``jax.numpy``, for the comparison that decides
``correct``. Run it under ``jax.default_matmul_precision("highest")``.

Source: the published ``config.json`` of XingChen-AGI/Xing4.0-29B-A4B
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json,
``model_type: xing4_0``). Its residual path is manifold-constrained
hyper-connections (Xie et al., DeepSeek-AI, arXiv:2512.24880, on Zhu et
al., "Hyper-Connections", arXiv:2409.19606: ``hc_mult`` streams,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``);
the layer inside it is DeepSeek-V3's (arXiv:2412.19437 and its loader,
``modeling_deepseek.py``): latent attention with a low-rank query and
YaRN positions, a leading dense SwiGLU, sigmoid-routed experts with a
shared one. Written from the equations, not from the program, and
importing nothing from the package: attention is the EXPANDED form only
(no cache, no carry, no absorbed product; ``reference/kimi_vl.py``'s
pieces where the layer is the same), the experts a dense sum over the
held experts under the router's weights (zero where a token did not
choose an expert: no buffer), the Sinkhorn iterations a Python loop over
``[..., n, n]``, the mixes ``einsum``s over the streams.

The model on tokens ``[T, B]``: ``X_0 = [e; ...; e]`` (the embedding in
each of ``n`` streams); layer ``l`` is ``X = mHC(X, MLA . N_in)``, ``X =
mHC(X, F_l . N_post)``; ``h = N_f(sum_i X_L,i)``, logits ``h W_head``,
value ``w_v . h + b_v``. One mHC sublayer on a token's ``X [n, C]``:
``u = vec(X) / sqrt(mean(vec(X)^2) + eps)``; ``[p | q | r] = u Phi``;
``h_pre = sigmoid(a_0 p + b_pre)``, ``h_post = 2 sigmoid(a_1 q +
b_post)``; ``M = exp(clamp(a_2 mat(r) + B_res, lo, hi))``, then
``hc_sinkhorn_iters`` times each column divided by its sum + ``hc_eps``
and then each row by its sum + ``hc_eps``: ``H_res``; ``y = F(sum_i
h_pre_i X_i)``, ``X'_i = sum_j H_res_ij X_j + h_post_i y``. YaRN:
``f_i = theta^(-2i/d)``; ``low = floor(d ln(L_0 / (beta_fast 2 pi)) / (2
ln theta))``, ``high = ceil(d ln(L_0 / (beta_slow 2 pi)) / (2 ln
theta))``, clamped to ``[0, d - 1]``; ``ramp_i = clip((i - low) / (high
- low), 0, 1)``; the inverse frequency ``f_i / factor * ramp_i + f_i (1 -
ramp_i)``; the softmax scale ``(d_nope + d_rope)^(-1/2) m^2``, ``m = 0.1
mscale_all_dim ln(factor) + 1``.

Departures from the published model, each the configuration file's: no
multi-token-prediction module; no auxiliary loss; the routing bias a
seeded constant; the value head this system's; the streams summed at the
top (the sum is the published reduction's form, assumed); of
``n_routed_experts`` only ``held["experts_held"]`` from
``held["first_expert"]`` on are computed (the router whole), the
leading dense layers are ``held["first_k_dense_replace"]`` and the
vocabulary is ``held["vocab_size"]`` rows of embedding and head; the
initialisation (normal(0.02) matrices, norm gains 1, ``B_res`` normal(0,
1), the gains ``a`` 0.01) is assumed, this file takes whatever
parameters it is given.

Precision. As written it is float32 throughout. The stated precision
rounds the inputs of every matrix product of a weight or of attention to
bfloat16 with float32 sums (``products=jnp.bfloat16``), while norms,
router, softmax, heads AND the whole residual path (streams, coefficient
product, Sinkhorn, mixes) stay float32; the program is held to that.
``lower`` names steps below it: ``"streams"`` (each stream rounded to
bfloat16 where a sublayer writes it, and at the bottom), ``"sinkhorn"``
(the iterations in bfloat16), ``"norms"`` (every RMSNorm, the mHC one
too, in bfloat16), ``"cache"``, ``"router"``, ``"softmax"`` as in
``reference/kimi_vl.py``; ``dtype=jnp.bfloat16`` puts the parameters and
everything else in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference.kimi_vl import (  # noqa: F401  (re-exported)
    Precision,
    _cached,
    _ffn,
    _mm,
    _norm,
    categorical,
    expert_block,
    objective,
    whiten,
)


def inv_freq(model):
    """YaRN's rotary inverse frequencies ``[d_rope / 2]``."""
    d, theta = model["qk_rope_head_dim"], model["rope_theta"]
    plain = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    yarn = dict(model["rope_scaling"])
    L0 = yarn["original_max_position_embeddings"]

    def dim(rotations):
        return d * math.log(L0 / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim(yarn["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(model):
    """``(d_nope + d_rope)^(-1/2) m^2``, ``m = 0.1 mscale_all_dim
    ln(factor) + 1``."""
    yarn = dict(model["rope_scaling"])
    m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * (
        m * m)


def _rotate(x, positions, freq):
    """``x [T, ..., d]``: the interleaved pairs rotated in place by
    ``t freq_i``."""
    d = x.shape[-1]
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape
    )


def latent_attention(p, x, model, prec=Precision()):
    """``x [T, B, H]`` -> ``[T, B, H]``: causal multi-head latent
    attention with a low-rank query, expanded."""
    T, B, _ = x.shape
    nh, rank = model["num_attention_heads"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, eps = model["v_head_dim"], model["rms_norm_eps"]
    positions, freq = jnp.arange(T), inv_freq(model)
    cq = _norm(_mm("tbh,hr->tbr", x, p["q_a_proj"], prec), p["q_a_norm"],
               eps, prec)
    q = _mm("tbr,rd->tbd", cq, p["q_b_proj"], prec).reshape(
        T, B, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], positions, freq)],
                        -1)
    kva = _mm("tbh,hd->tbd", x, p["kv_a_proj"], prec)
    c = _cached(_norm(kva[..., :rank], p["kv_a_norm"], eps, prec), prec)
    k_rope = _cached(_rotate(kva[..., rank:], positions, freq), prec)
    kv = _mm("tbc,cd->tbd", c, p["kv_b_proj"], prec).reshape(
        T, B, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_rope[:, :, None, :], (T, B, nh, dr))], -1
    )
    scores = _mm("tbhd,sbhd->bhts", q, k, prec) * softmax_scale(model)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(prec.at("softmax", scores), -1).astype(x.dtype)
    out = _mm("bhts,sbhd->tbhd", probs, kv[..., dn:], prec)
    return _mm("tbd,dh->tbh", out.reshape(T, B, nh * dv), p["o_proj"], prec)


def _stream(X, prec):
    """A stream as it is held: as computed, or through bfloat16."""
    if "streams" in prec.lower:
        return X.astype(jnp.bfloat16).astype(X.dtype)
    return X


def sinkhorn(M, iters, eps):
    """``M [..., n, n]`` -> ``iters`` times: columns, then rows, each
    divided by its sum + ``eps``."""
    for _ in range(iters):
        M = M / (jnp.sum(M, -2, keepdims=True) + eps)
        M = M / (jnp.sum(M, -1, keepdims=True) + eps)
    return M


def mhc(p, prefix, X, F, model, prec=Precision()):
    """One sublayer with manifold-constrained hyper-connections: ``X
    [T, B, n, C]`` -> ``[T, B, n, C]``."""
    n = model["hc_mult"]
    T, B = X.shape[:2]
    u = prec.at("norms", X.reshape(T, B, -1))
    u = (u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True)
                           + model["rms_norm_eps"])).astype(X.dtype)
    pqr = u @ p[f"{prefix}phi"]
    a = p[f"{prefix}alpha"]
    h_pre = jax.nn.sigmoid(a[0] * pqr[..., :n] + p[f"{prefix}b_pre"])
    h_post = 2 * jax.nn.sigmoid(a[1] * pqr[..., n:2 * n]
                                + p[f"{prefix}b_post"])
    logits = a[2] * pqr[..., 2 * n:].reshape(T, B, n, n) + p[f"{prefix}b_res"]
    logits = jnp.clip(logits, model["mhc_h_res_clamp_min"],
                      model["mhc_h_res_clamp_max"])
    M = prec.at("sinkhorn", jnp.exp(logits))
    H = sinkhorn(M, model["hc_sinkhorn_iters"], model["hc_eps"]).astype(
        X.dtype)
    y = F(jnp.einsum("tbi,tbic->tbc", h_pre, X))
    out = jnp.einsum("tbij,tbjc->tbic", H, X) + h_post[..., None] * y[
        :, :, None, :]
    return _stream(out, prec)


def forward(params, tokens, model, held, dtype=jnp.float32, remat=False,
            products=None, lower=()):
    """``tokens [T, B]`` int -> ``(logits [T, B, V], values [T, B])``,
    every sequence from its first token. ``params`` is the program's
    tree (``{"params": {"embedding", "layer_<i>": {...}, "final_norm",
    "lm_head", "value_w", "value_b"}}``)."""
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype), params["params"])
    prec = Precision(products, frozenset(lower))
    eps, n = model["rms_norm_eps"], model["hc_mult"]
    e = p["embedding"][tokens]
    T, B, H = e.shape
    X = _stream(jnp.broadcast_to(e[:, :, None, :], (T, B, n, H)), prec)

    def layer(lp, X, expert):
        X = mhc(lp, "attn_hc_", X, lambda h: latent_attention(
            lp, _norm(h, lp["input_norm"], eps, prec), model, prec
        ), model, prec)

        def ffn(h):
            h = _norm(h, lp["post_norm"], eps, prec).reshape(T * B, H)
            if expert:
                y = expert_block(lp, h, model, held["first_expert"],
                                 held["experts_held"], prec)
            else:
                y = _ffn(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"],
                         prec)
            return y.reshape(T, B, H)

        return mhc(lp, "ffn_hc_", X, ffn, model, prec)

    for i in range(held["num_hidden_layers"]):
        # the leading dense layers as held (the published 2 count once)
        expert = (i >= held["first_k_dense_replace"]
                  and i % model["moe_layer_freq"] == 0)
        f = lambda lp, X, e=expert: layer(lp, X, e)
        X = (jax.checkpoint(f) if remat else f)(p[f"layer_{i}"], X)
    h = _norm(jnp.sum(X, 2), p["final_norm"], eps, prec)
    logits = _mm("tbh,hv->tbv", h, p["lm_head"], prec).astype(jnp.float32)
    values = (h @ p["value_w"] + p["value_b"]).astype(jnp.float32)
    return logits, values


def ppo_loss(params, batch, hp, model, held, remat=True, whitened=False,
             **precision):
    """The PPO objective on whole sequences (``reference/kimi_vl.py``'s
    ``objective``): ``batch`` holds ``obs`` (tokens), ``actions``,
    ``old_log_probs``, ``old_values``, ``advantages``, ``returns``, each
    ``[T, B]``. Returns ``(total, parts)``."""
    logits, values = forward(params, batch["obs"], model, held, remat=remat,
                             **precision)
    return objective(logits, values, batch, hp, whitened)
