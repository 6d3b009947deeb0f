"""Plain reference of the SDAR-30B-A3B-Chat policy, a language model
that generates by diffusion over blocks: the forward pass over a
sampling trajectory in straightforward ``jax.numpy``, for the
comparison that decides ``correct``. Run it under
``jax.default_matmul_precision("highest")``.

Source: the published ``config.json`` of JetLM/SDAR-30B-A3B-Chat
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
``model_type: sdar_moe``) and the ``qwen3_moe`` decoder layer the family
continues from; the attention mask is the block-diffusion training mask
of Arriola et al. 2025, arXiv:2503.09573 section 3 (a noisy copy of a
block sees itself and the clean blocks before it, a clean block the
clean blocks up to itself), laid over a sampling trajectory as TraceRL
(arXiv:2509.06949) trains this family: every pass of the trajectory is
a copy of its block, in time order. Written from the equations, not
from the program: one sequence at a time (``jax.vmap`` over the
sequences is the only batching), the WHOLE trajectory ``[T, L]`` of a
sequence as one pass over ``T * L`` positions under that mask — no
cache, no carry, no step form, so the rollout's passes through the
cache are held to this — every query head's key/value head repeated
for it, and the experts a loop over the held experts in which every
position goes through every expert under a dense weight (zero where it
did not choose it): no sorting, no grouped products. It imports nothing
from the package and reads the program's parameter tree by its names.

A pass ``t`` shows a block of ``L`` ids, ``mask_token_id`` where a
position is not yet revealed. It is a COMMIT pass where no id is the
mask. Position of ``(t, i)``: ``L * (commit passes before t) + i``;
query ``(t, i)`` sees key ``(s, j)`` iff ``s == t``, or ``s < t`` and
pass ``s`` is a commit.

Layer: ``x += A(N(x)); x += E(N(x))``. ``N`` is the plain RMSNorm ``w x
rsqrt(mean(x^2) + eps)``. ``A``: ``q = N_q(x W_q)``, ``k = N_k(x W_k)``
(RMSNorms over the head dimensions), rotary embedding over all
``head_dim`` dimensions with the halves paired — ``(x[i], x[i + d/2])``
rotated by ``n theta^(-2i/d)`` — softmax of ``q.k / sqrt(d)`` over the
visible keys, ``W_o``. ``E``: softmax router over all
``num_experts``, top-k, renormalised, SwiGLU experts, no shared expert.
After the last layer ``N``, ``logits = h W_head`` with the mask token's
column at ``-inf`` (a mask is never an action), and the value ``w_v .
mean_i(h_i) + b_v`` over the pass's block.

Departures from the published model, each the configuration file's: no
router auxiliary loss; the value head is this system's; of
``num_experts`` only ``held["experts_held"]`` from
``held["first_expert"]`` on are computed — the router is whole, top-k
and its renormalisation are over all experts, and what the absent
experts would add is left out — and the vocabulary is the
``held["vocab_size"]`` rows of embedding and head, the last of them the
mask token.

Precision. As written it is float32 throughout. The configuration
states less for one kind of operation: the inputs of every matrix
product of a weight or of attention are rounded to bfloat16 and
accumulated in float32, while norms, router, softmax and the value head
stay float32. ``products=jnp.bfloat16`` computes exactly that, and is
what the program is held to (``reference/qwen3_next.py`` says why).
Keys and values are inputs of such products, so the stated precision of
the program's cache of them is bfloat16 and needs no argument here.
The steps below the stated precision are ``lower``: a set of
``"cache"`` (keys and values rounded to 8 bits, float8 e4m3),
``"router"`` (softmax and top-k in bfloat16), ``"softmax"`` (the
attention's softmax in bfloat16) and ``"norms"`` (every RMSNorm in
bfloat16) with all else as stated; and ``dtype=jnp.bfloat16``:
parameters and everything else in it. ``remat`` recomputes each layer
in the backward pass and changes no value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.kimi_vl import _cached, _norm
from perfbench.reference.qwen3_next import (  # noqa: F401  (re-exported)
    Precision,
    _mm,
    _silu,
    whiten,
)


def _rotate(x, positions, theta):
    """``x [n, heads, d]``, ``positions [n]``: the pairs ``(x[i], x[i +
    d/2])`` rotated by ``positions * theta^(-2i/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def trajectory(tokens, mask_id):
    """``tokens [T, L]`` -> each position's token position ``[T * L]``
    and which keys each query sees ``[T * L, T * L]``."""
    T, L = tokens.shape
    commit = jnp.all(tokens != mask_id, -1)
    commits_before = jnp.cumsum(commit) - commit
    positions = (L * commits_before[:, None] + jnp.arange(L)).reshape(-1)
    pass_of = jnp.repeat(jnp.arange(T), L)
    query, key = pass_of[:, None], pass_of[None, :]
    visible = (key == query) | ((key < query) & jnp.repeat(commit, L)[None, :])
    return positions, visible


def attention(p, x, positions, visible, model, prec=Precision()):
    """``x [n, H]`` -> ``[n, H]``: grouped-query softmax attention of
    one sequence's positions under ``visible``."""
    n = x.shape[0]
    nh, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q = _mm("nh,hd->nd", x, p["q_proj"], prec).reshape(n, nh, hd)
    k = _mm("nh,hd->nd", x, p["k_proj"], prec).reshape(n, nkv, hd)
    v = _mm("nh,hd->nd", x, p["v_proj"], prec).reshape(n, nkv, hd)
    q = _rotate(_norm(q, p["q_norm"], eps, prec), positions, theta)
    k = _rotate(_norm(k, p["k_norm"], eps, prec), positions, theta)
    # each key/value head serves nh / nkv query heads
    k = jnp.repeat(_cached(k, prec), nh // nkv, axis=1)
    v = jnp.repeat(_cached(v, prec), nh // nkv, axis=1)
    scores = _mm("qhd,shd->hqs", q, k, prec) * hd ** -0.5
    scores = jnp.where(visible, scores, -jnp.inf)
    probs = jax.nn.softmax(prec.at("softmax", scores), -1).astype(x.dtype)
    out = _mm("hqs,shd->qhd", probs, v, prec)
    return _mm("nd,dh->nh", out.reshape(n, nh * hd), p["o_proj"], prec)


def expert_block(p, x, model, first_expert: int, experts_held: int,
                 prec=Precision()):
    """``x [N, H]`` -> the routed sum over the held experts.
    ``p["w_gate"]`` etc. hold the held experts only, in order from
    ``first_expert``."""
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(prec.at("router", logits), -1)
    top_p, top_e = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p.astype(x.dtype)

    def one_expert(y, xs):
        e, w_gate, w_up, w_down = xs
        hidden = _silu(_mm("nh,hi->ni", x, w_gate, prec)) * _mm(
            "nh,hi->ni", x, w_up, prec
        )
        # the position's weight for expert e, zero where it did not
        # choose it
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0), -1)
        return y + weight[:, None] * _mm("ni,ih->nh", hidden, w_down,
                                         prec), None

    experts = first_expert + jnp.arange(experts_held)
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (experts, p["w_gate"], p["w_up"], p["w_down"]),
    )
    return routed


def forward(params, tokens, model, held, dtype=jnp.float32, remat=False,
            products=None, lower=()):
    """``tokens [T, B, L]`` int, the passes of ``B`` sampling
    trajectories -> ``(logits [T, B, L, V], values [T, B])``, every
    sequence from its first pass. ``params`` is the program's tree
    (``{"params": {"embedding", "layer_<i>": {...}, "final_norm",
    "lm_head", "value_w", "value_b"}}``)."""
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype), params["params"])
    prec = Precision(products, frozenset(lower))
    eps, mask_id = model["rms_norm_eps"], held["mask_token_id"]

    def layer(lp, x, positions, visible):
        h = _norm(x, lp["input_norm"], eps, prec)
        x = x + attention(lp, h, positions, visible, model, prec)
        h = _norm(x, lp["post_norm"], eps, prec)
        return x + expert_block(lp, h, model, held["first_expert"],
                                held["experts_held"], prec)

    def one_sequence(tokens):                                   # [T, L]
        T, L = tokens.shape
        positions, visible = trajectory(tokens, mask_id)
        x = p["embedding"][tokens.reshape(-1)]
        for i in range(held["num_hidden_layers"]):
            f = jax.checkpoint(layer) if remat else layer
            x = f(p[f"layer_{i}"], x, positions, visible)
        h = _norm(x, p["final_norm"], eps, prec).reshape(T, L, -1)
        logits = _mm("tlh,hv->tlv", h, p["lm_head"], prec)
        logits = logits.astype(jnp.float32).at[..., mask_id].set(-jnp.inf)
        values = jnp.mean(h, 1) @ p["value_w"] + p["value_b"]
        return logits, values.astype(jnp.float32)

    return jax.vmap(one_sequence, in_axes=1, out_axes=1)(tokens)


def block_reveal(logits, blocks, actions, mask_id):
    """What one denoising pass's sampler is scored by: ``logits [..., L,
    V]``, the ``blocks`` shown and the blocks after the pass
    (``actions``), ``[..., L]`` each -> the log-probability of the ids
    revealed in the pass (masked before, not after; which positions
    were chosen is the sampler's and is not scored) and the mean entropy
    over the masked positions, ``[...]`` each; both 0 for a pass that
    shows no mask. The mask token is no outcome: the categorical is
    over the other ids."""
    outcomes = jnp.delete(logits, mask_id, axis=-1)
    log_p = jax.nn.log_softmax(outcomes, -1)
    masked = blocks == mask_id
    revealed = masked & (actions != mask_id)
    ids = jnp.where(revealed, actions - (actions > mask_id), 0)
    taken = jnp.take_along_axis(log_p, ids[..., None], -1)[..., 0]
    entropy = -jnp.sum(jnp.exp(log_p) * log_p, -1)
    return (
        jnp.sum(jnp.where(revealed, taken, 0.0), -1),
        jnp.sum(jnp.where(masked, entropy, 0.0), -1)
        / jnp.maximum(jnp.sum(masked, -1), 1),
    )


def objective(logits, values, batch, hp, mask_id, whitened=False):
    """``ppo_loss.py``'s clipped objective (same source, same
    departures) on whole trajectories, a pass a step: ``(total,
    parts)``."""
    log_probs, entropy = block_reveal(
        logits, batch["obs"], batch["actions"], mask_id
    )
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
    """The PPO objective on whole trajectories: ``batch`` holds ``obs``
    and ``actions`` (blocks ``[T, B, L]``), ``old_log_probs``,
    ``old_values``, ``advantages``, ``returns`` (``[T, B]``);
    advantages are whitened over the batch, or come ``whitened`` (over
    a larger batch of which this is one equal part). Returns ``(total,
    parts)``."""
    logits, values = forward(params, batch["obs"], model, held, remat=remat,
                             **precision)
    return objective(logits, values, batch, hp, held["mask_token_id"],
                     whitened)
