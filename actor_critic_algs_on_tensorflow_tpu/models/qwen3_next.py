"""Qwen3-Next as a sequence-policy core: Gated DeltaNet and gated
softmax attention as the recurrent mixers, a 512-way routed expert
block with a shared expert as every layer's MLP, a language-model head
as the policy and a linear value head.

Source of the layer equations: the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type: qwen3_next``) and the
loader that reads it (``transformers``' ``modeling_qwen3_next.py``:
``Qwen3NextGatedDeltaNet``, ``Qwen3NextAttention``,
``Qwen3NextSparseMoeBlock``, ``Qwen3NextRMSNorm``,
``torch_chunk_gated_delta_rule``); Yang et al. 2024, "Gated Delta
Networks", arXiv:2412.06464 for the recurrence and its chunked form.
Not here: the multi-token-prediction module and a router auxiliary
loss (the config gives no coefficient; the loss is the trainer's).

The carry interface (``algos/common.py::make_recurrent_policy_head``):
``__call__(tokens [T, B] int32, resets [T, B], carry)`` returns
``(logits [T, B, V], values [T, B], carry, stats)``, and
``initialize_carry(batch)`` gives the empty carry. Two forms share the
parameters:

* ``T == 1`` — the step form, decode through the carry: per Gated
  DeltaNet layer a float32 state ``[B, heads, d_k, d_v]`` and the last
  ``kernel - 1`` inputs of the causal convolution, per attention layer
  a key/value cache ``[B, cache_len, kv_heads, head_dim]`` and, for
  all, the position since the episode began. Where ``resets`` is set
  the state, the convolution's tail and the position are zeroed before
  the step (a cache row beyond the position is never read). The
  state's update (``_state_step``) is one Pallas kernel where the
  program is lowered for a TPU at widths that tile its vector unit
  (``ops/pallas_delta_step.py``: the state read once and written once,
  in place, the reset folded into the decay), and the plain
  ``gated_delta_step`` everywhere else; nothing but the lowering
  platform and the state's shape chooses.
* ``T > 1`` — the sequence form, the teacher-forced pass: the chunked
  Gated DeltaNet and causal attention over the whole sequence from an
  EMPTY carry, position 0 at step 0. ``carry`` and ``resets`` are not
  read and the carry is handed back as it came; the trainer holds the
  sequences to whole episodes (``make_ppo``'s refusal).

Parameters are float32. Matrix products run in ``dtype`` with float32
accumulation; the norms, the router (product, softmax, top-k), the
DeltaNet's gates, state and chunk products, the attention's softmax
and both heads' outputs are float32 whatever ``dtype`` says.

The expert layer is ``models/moe.py``'s, shared with the other sequence
cores: it is told which experts it holds (``first_expert``,
``experts_held`` of ``num_experts``), routes over all of them with this
model's ``route`` (softmax, the published top-k and its
renormalisation), computes only the terms of its own experts in one
dropless dispatch buffer and counts what did not fit; the sigmoid-gated
shared expert is this model's own.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.models import moe
from actor_critic_algs_on_tensorflow_tpu.models.moe import (
    mm as _mm,
    swiglu as _expert_ffn,
)
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_INIT_STD = 0.02  # every matrix, normal (assumed; the config gives none)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published keys of ``config.json`` (defaults: the 80B-A3B
    widths) and what this chip holds of them. ``num_hidden_layers``,
    ``vocab_size`` and ``experts_held`` are the held share; no other
    default differs from the source."""

    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    vocab_size: int = 151936
    # The share of the expert layer held here.
    first_expert: int = 0
    experts_held: int = 512
    # Rows of the dispatch buffer over the expected number of local
    # pairs, tokens * top_k * held / num_experts.
    capacity_factor: float = 2.0
    chunk_size: int = 64

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def expert_spec(self) -> moe.ExpertSpec:
        return moe.ExpertSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            first_expert=self.first_expert, experts_held=self.experts_held,
            capacity_factor=self.capacity_factor,
        )

    def moe_capacity(self, tokens: int) -> int:
        return self.expert_spec.moe_capacity(tokens)


# ---- parameters --------------------------------------------------------


def _normal(std):
    return nn.initializers.normal(std)


def _a_log_init(key, shape, dtype=_F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def layer_param_spec(cfg: Qwen3NextConfig, layer: int):
    """``{name: (shape, init)}`` of one decoder layer."""
    H, w = cfg.hidden_size, _normal(_INIT_STD)
    zeros, ones = nn.initializers.zeros_init(), nn.initializers.ones_init()
    spec = {"input_norm": ((H,), zeros), "post_norm": ((H,), zeros)}
    if cfg.is_attention(layer):
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        spec.update(
            q_proj=((H, nh * 2 * hd), w), k_proj=((H, nkv * hd), w),
            v_proj=((H, nkv * hd), w), q_norm=((hd,), zeros),
            k_norm=((hd,), zeros), o_proj=((nh * hd, H), w),
        )
    else:
        nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        spec.update(
            in_proj_qkvz=((H, 2 * nk * dk + 2 * nv * dv), w),
            in_proj_ba=((H, 2 * nv), w),
            conv=((cfg.linear_conv_kernel_dim, 2 * nk * dk + nv * dv), w),
            A_log=((nv,), _a_log_init), dt_bias=((nv,), ones),
            gdn_norm=((dv,), ones), out_proj=((nv * dv, H), w),
        )
    I, Is, E = (cfg.moe_intermediate_size,
                cfg.shared_expert_intermediate_size, cfg.experts_held)
    spec.update(
        router=((H, cfg.num_experts), w), shared_gate=((H, 1), w),
        shared_w_gate=((H, Is), w), shared_w_up=((H, Is), w),
        shared_w_down=((Is, H), w),
        w_gate=((E, H, I), w), w_up=((E, H, I), w), w_down=((E, I, H), w),
    )
    return spec


# ---- pieces ------------------------------------------------------------


def rms_norm(x, w, eps):
    """Zero-centred RMSNorm, float32: ``x * rsqrt(mean(x^2) + eps) *
    (1 + w)``."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w
    )


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _rotary(x, positions, cfg: Qwen3NextConfig):
    """Rotate-half rotary embedding on the first ``partial_rotary_factor``
    of the head's dims; ``x [..., heads, head_dim]``, ``positions``
    shaped like ``x`` without its last two axes."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, rot, 2, dtype=_F32) / rot)
    angles = positions.astype(_F32)[..., None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = jnp.concatenate(
        [-x_rot[..., rot // 2:], x_rot[..., : rot // 2]], -1
    )
    return jnp.concatenate([x_rot * cos + half * sin, x_pass], -1)


# ---- gated attention ---------------------------------------------------


def _attn_project(p, x, positions, cfg, dtype):
    """``x [..., H]`` -> query, gate ``[..., nh, hd]``, key, value
    ``[..., nkv, hd]``; query and key normed and rotated, float32."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    with jax.named_scope(profiling.MIXER_PROJ):
        qg, k, v = (
            _mm(x, p[w], dtype) for w in ("q_proj", "k_proj", "v_proj")
        )
    with jax.named_scope(profiling.MIXER_POINTWISE):
        qg = qg.reshape(x.shape[:-1] + (nh, 2 * hd))
        q, gate = qg[..., :hd], qg[..., hd:]
        k = k.reshape(x.shape[:-1] + (nkv, hd))
        v = v.reshape(x.shape[:-1] + (nkv, hd))
        q = _rotary(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions, cfg)
        k = _rotary(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions, cfg)
    return q, gate, k, v


def _attend(q, k, v, mask, cfg, dtype):
    """``q [b, tq, nh, hd]``, ``k, v [b, tk, nkv, hd]``, ``mask [b, tq,
    tk]`` (True = visible) -> ``[b, tq, nh, hd]``; softmax in float32."""
    b, tq, nh, hd = q.shape
    nkv = k.shape[2]
    q = q.reshape(b, tq, nkv, nh // nkv, hd)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q.astype(dtype), k.astype(dtype),
        preferred_element_type=_F32,
    ) * (hd ** -0.5)
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(dtype), v.astype(dtype),
        preferred_element_type=_F32,
    )
    return out.reshape(b, tq, nh, hd)


def gated_attention_seq(p, x, cfg, dtype):
    """``x [T, b, H]``, causal over the sequence, position = step."""
    T, b, _ = x.shape
    with jax.named_scope(profiling.MIXER_POINTWISE):
        xb = jnp.swapaxes(x, 0, 1)
        positions = jnp.broadcast_to(jnp.arange(T), (b, T))
    q, gate, k, v = _attn_project(p, xb, positions, cfg, dtype)
    with jax.named_scope(profiling.MIXER_CORE):
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (b, T, T))
        out = _attend(q, k, v, mask, cfg, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        out = (out * jax.nn.sigmoid(gate)).reshape(b, T, -1)
    with jax.named_scope(profiling.MIXER_PROJ):
        out = _mm(out, p["o_proj"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        return jnp.swapaxes(out, 0, 1)


def gated_attention_step(p, x, cache, pos, cfg, dtype):
    """One token an env against the cache: ``x [B, H]``, ``cache``
    ``{"k", "v"} [B, L, nkv, hd]``, ``pos [B]``. The new key and value
    are written at ``pos``; rows beyond it are masked."""
    with jax.named_scope(profiling.MIXER_POINTWISE):
        x, positions = x[:, None], pos[:, None]
    q, gate, k, v = _attn_project(p, x, positions, cfg, dtype)
    L = cache["k"].shape[1]
    with jax.named_scope(profiling.MIXER_CORE):
        here = (jnp.arange(L)[None, :] == pos[:, None])[..., None, None]
        cache = {
            "k": jnp.where(here, k.astype(cache["k"].dtype), cache["k"]),
            "v": jnp.where(here, v.astype(cache["v"].dtype), cache["v"]),
        }
        mask = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, :]
        out = _attend(q, cache["k"], cache["v"], mask, cfg, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        out = (out * jax.nn.sigmoid(gate)).reshape(pos.shape[0], -1)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out, p["o_proj"], dtype), cache


# ---- gated DeltaNet ----------------------------------------------------


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int):
    """The chunked form of the gated delta rule, float32.

    ``q, k [b, h, T, d_k]`` (normed, ``q`` scaled), ``v [b, h, T, d_v]``,
    ``g`` (log decay, <= 0) and ``beta [b, h, T]``. Equals, from an
    empty state, the recurrence ``S <- S exp(g_t); delta = (v_t - S^T
    k_t) beta_t; S <- S + k_t delta^T; o_t = S^T q_t``
    (``gated_delta_step``). Returns ``(o [b, h, T, d_v], S [b, h, d_k,
    d_v])``. A length that is no multiple of the chunk is padded with
    steps that change nothing (``k = v = beta = g = 0``)."""
    T, dv = q.shape[2], v.shape[-1]
    pad = -T % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (g, beta))
    n = (T + pad) // chunk

    def chunks(x):  # [b, h, n * c, ...] -> [n, b, h, c, ...]
        x = x.reshape(x.shape[:2] + (n, chunk) + x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, -1)  # the decay from the chunk's start, in log
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    with jax.named_scope(profiling.GDN_CHUNK_PRODUCTS):
        diff = gc[..., :, None] - gc[..., None, :]
        decay = jnp.exp(jnp.where(lower, diff, 0.0)) * lower

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    with jax.named_scope(profiling.GDN_CHUNK_SOLVE):
        k_beta, v_beta = k * beta[..., None], v * beta[..., None]
        # (I + A) [u | w] = [v beta | k beta exp(gc)], A strictly lower:
        # inside a chunk every delta depends on the ones before it.
        a = dot("nbhid,nbhjd->nbhij", k_beta, k) * decay * strict
        rhs = jnp.concatenate([v_beta, k_beta * jnp.exp(gc)[..., None]], -1)
        solved = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(chunk, dtype=a.dtype), rhs, lower=True,
            unit_diagonal=True,
        )
        u, w = solved[..., :dv], solved[..., dv:]
    with jax.named_scope(profiling.GDN_CHUNK_PRODUCTS):
        qk = dot("nbhid,nbhjd->nbhij", q, k) * decay

    def step(S, xs):
        q_i, k_i, u_i, w_i, qk_i, gc_i = xs
        v_new = u_i - dot("bhck,bhkv->bhcv", w_i, S)
        o = dot("bhck,bhkv->bhcv", q_i * jnp.exp(gc_i)[..., None], S)
        o = o + dot("bhij,bhjv->bhiv", qk_i, v_new)
        g_end = gc_i[..., -1]
        k_dec = k_i * jnp.exp(g_end[..., None] - gc_i)[..., None]
        S = S * jnp.exp(g_end)[..., None, None] + dot(
            "bhck,bhcv->bhkv", k_dec, v_new
        )
        return S, o

    with jax.named_scope(profiling.GDN_CHUNK_PRODUCTS):
        S0 = jnp.zeros(q.shape[1:3] + (q.shape[-1], dv), _F32)
        S, o = jax.lax.scan(step, S0, (q, k, u, w, qk, gc))
    o = jnp.moveaxis(o, 0, 2).reshape(o.shape[1:3] + (n * chunk, dv))
    return o[:, :, :T], S


def gated_delta_step(S, q, k, v, g, beta):
    """One step of the recurrence on ``S [B, h, d_k, d_v]``: ``q, k [B,
    h, d_k]``, ``v [B, h, d_v]``, ``g, beta [B, h]``. Float32 on the
    vector unit, no matrix product. The plain form: what runs off the
    TPU, what the tests differentiate, and the reference of the TPU's
    kernel (``ops/pallas_delta_step.py``). Compiled as it stands it is
    two passes over the state, a read for both contractions and then a
    read and a write, three transfers where the kernel makes two."""
    S = S * jnp.exp(g)[..., None, None]
    s_k = jnp.sum(S * k[..., :, None], -2)
    s_q = jnp.sum(S * q[..., :, None], -2)
    delta = (v - s_k) * beta[..., None]
    o = s_q + jnp.sum(q * k, -1, keepdims=True) * delta
    return S + k[..., :, None] * delta[..., None, :], o


def _gdn_inputs(p, x, cfg, dtype):
    """The Gated DeltaNet layer's projections of ``x [..., H]``: the
    convolution's input ``[..., C]`` (query, key and value channels),
    the output gate ``z [..., nv, d_v]``, ``beta`` and the log decay
    ``g [..., nv]`` (float32)."""
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, nv // nk
    lead = x.shape[:-1]
    with jax.named_scope(profiling.MIXER_PROJ):
        qkvz = _mm(x, p["in_proj_qkvz"], dtype)
        ba = _mm(x, p["in_proj_ba"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        qkvz = qkvz.reshape(lead + (nk, 2 * dk + 2 * r * dv))
        ba = ba.reshape(lead + (nk, 2 * r))
        q = qkvz[..., :dk].reshape(lead + (nk * dk,))
        k = qkvz[..., dk:2 * dk].reshape(lead + (nk * dk,))
        v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(lead + (nv * dv,))
        z = qkvz[..., 2 * dk + r * dv:].reshape(lead + (nv, dv))
        b = ba[..., :r].reshape(lead + (nv,))
        a = ba[..., r:].reshape(lead + (nv,))
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
        return jnp.concatenate([q, k, v], -1), z, beta, g


def _gdn_heads(qkv, cfg):
    """The convolved channels back as heads: ``q, k [..., nv, d_k]``
    (each key head serving ``nv / nk`` value heads; ``q`` l2-normed and
    scaled, ``k`` l2-normed), ``v [..., nv, d_v]``."""
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    lead = qkv.shape[:-1]
    with jax.named_scope(profiling.MIXER_POINTWISE):
        q = qkv[..., : nk * dk].reshape(lead + (nk, dk))
        k = qkv[..., nk * dk: 2 * nk * dk].reshape(lead + (nk, dk))
        v = qkv[..., 2 * nk * dk:].reshape(lead + (nv, dv))
        q = jnp.repeat(_l2norm(q) * dk ** -0.5, nv // nk, axis=-2)
        k = jnp.repeat(_l2norm(k), nv // nk, axis=-2)
    return q, k, v


def _gdn_output(p, o, z, cfg, dtype):
    """The gated norm over ``d_v`` (weight NOT zero-centred) and the
    output projection; ``o, z [..., nv, d_v]``."""
    with jax.named_scope(profiling.MIXER_POINTWISE):
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps
        ) * p["gdn_norm"] * jax.nn.silu(z)
        o = o.reshape(o.shape[:-2] + (-1,))
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(o, p["out_proj"], dtype)


def gated_deltanet_seq(p, x, cfg, dtype):
    """``x [T, b, H]`` from an empty state and convolution history."""
    T = x.shape[0]
    K = cfg.linear_conv_kernel_dim
    qkv, z, beta, g = _gdn_inputs(p, x, cfg, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        padded = jnp.pad(qkv, ((K - 1, 0), (0, 0), (0, 0)))
        qkv = jax.nn.silu(sum(
            padded[j: j + T] * p["conv"][j] for j in range(K)
        ))
    q, k, v = _gdn_heads(qkv, cfg)

    def bh(x):  # [T, b, h, ...] -> [b, h, T, ...]
        return jnp.moveaxis(x, 0, 2)

    with jax.named_scope(profiling.MIXER_POINTWISE):
        q, k, v, g, beta = map(bh, (q, k, v, g, beta))
    with jax.named_scope(profiling.MIXER_CORE):
        o, _ = chunk_gated_delta_rule(q, k, v, g, beta, cfg.chunk_size)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        o = jnp.moveaxis(o, 2, 0)
    return _gdn_output(p, o, z, cfg, dtype)


def _state_step(S, q, k, v, g, beta, keep):
    """``gated_delta_step`` on the state less the envs that start over
    (``keep [B]``, 0 at a reset). Lowered for a TPU, at widths that
    tile its vector unit, the one-pass kernel with the reset folded
    into the decay; anywhere else the plain form."""
    # Imported where it is used: Pallas is ~1.5 s of imports, and
    # cli/train.py's PRESETS import this module for every preset.
    from actor_critic_algs_on_tensorflow_tpu.ops import pallas_delta_step

    def plain(S, q, k, v, g, beta, keep):
        return gated_delta_step(
            S * keep[:, None, None, None], q, k, v, g, beta
        )

    if not pallas_delta_step.fits(S):
        return plain(S, q, k, v, g, beta, keep)
    return jax.lax.platform_dependent(
        S, q, k, v, g, beta, keep,
        tpu=pallas_delta_step.gated_delta_step, default=plain,
    )


def gated_deltanet_step(p, x, state, keep, cfg, dtype):
    """``x [B, H]``; ``state`` ``{"S" [B, nv, d_k, d_v], "conv" [B, K -
    1, C]}``; ``keep [B]``, 0 where the env starts over: its state and
    convolution history count as empty."""
    qkv, z, beta, g = _gdn_inputs(p, x, cfg, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        window = jnp.concatenate(
            [state["conv"] * keep[:, None, None], qkv[:, None]], 1
        )
        qkv = jax.nn.silu(jnp.sum(window * p["conv"], 1))
        conv = window[:, 1:]
    q, k, v = _gdn_heads(qkv, cfg)
    with jax.named_scope(profiling.MIXER_CORE), jax.named_scope(
        profiling.GDN_STATE
    ):
        S, o = _state_step(state["S"], q, k, v, g, beta, keep)
    return _gdn_output(p, o, z, cfg, dtype), {"S": S, "conv": conv}


# ---- the expert block --------------------------------------------------


def route(p, x, cfg):
    """``x [N, H]`` -> the top-k experts ``[N, k]`` of ALL
    ``num_experts`` and their weights, float32 throughout."""
    return moe.route_softmax_top_k(
        p, x, cfg.num_experts_per_tok, cfg.norm_topk_prob
    )


def routed_experts(p, x, cfg: Qwen3NextConfig, dtype):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum under this model's ``route``."""
    return moe.routed_experts(
        p, x, cfg.expert_spec, dtype, functools.partial(route, cfg=cfg)
    )


def shared_expert(p, x, dtype):
    """``sigmoid(x w_s) E_shared(x)``: what every chip computes alike."""
    with jax.named_scope(profiling.MOE_SHARED):
        return jax.nn.sigmoid(_mm(x, p["shared_gate"], dtype)) * _expert_ffn(
            x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"],
            dtype,
        )


def moe_block(p, x, cfg: Qwen3NextConfig, dtype):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum plus the shared expert."""
    routed, stats = routed_experts(p, x, cfg, dtype)
    return routed + shared_expert(p, x, dtype), stats


# ---- the model ---------------------------------------------------------


def _decoder_layer_seq(p, x, cfg, dtype, attention: bool):
    T, b, H = x.shape
    # (the norm is outside the mixer's own scope, where PR 27 left it:
    # gdn_time_share and gated_attn_time_share keep their meaning)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
    mixer, seq = (
        (profiling.GATED_ATTN, gated_attention_seq) if attention
        else (profiling.GDN, gated_deltanet_seq)
    )
    with jax.named_scope(mixer):
        y = seq(p, h, cfg, dtype)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + y
    with jax.named_scope(profiling.MOE):
        h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        y, stats = moe_block(p, h.reshape(T * b, H), cfg, dtype)
    return x + y.reshape(T, b, H), stats


def _decoder_layer_step(p, x, state, pos, keep, cfg, dtype, attention: bool):
    with jax.named_scope(profiling.MIXER_POINTWISE):
        h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
    if attention:
        with jax.named_scope(profiling.GATED_ATTN):
            y, state = gated_attention_step(p, h, state, pos, cfg, dtype)
    else:
        with jax.named_scope(profiling.GDN):
            y, state = gated_deltanet_step(p, h, state, keep, cfg, dtype)
    x = x + y
    with jax.named_scope(profiling.MOE):
        h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        y, stats = moe_block(p, h, cfg, dtype)
    return x + y, state, stats


class Qwen3NextActorCritic(nn.Module):
    """The policy over ``cfg.vocab_size`` tokens and the value."""

    cfg: Qwen3NextConfig
    cache_len: int
    dtype: Any = jnp.float32
    # The sequence form reads neither carry nor resets (see above).
    replays_from_empty_carry = True
    # (rollout rows, update rows, axis) -> an iteration's counters
    iteration_stats = staticmethod(moe.iteration_moe_stats)

    @nn.compact
    def __call__(self, tokens, resets, carry):
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        H, std = cfg.hidden_size, _INIT_STD
        zeros = nn.initializers.zeros_init()
        embedding = self.param(
            "embedding", _normal(std), (cfg.vocab_size, H), _F32
        )
        layers = [
            moe.Params(tuple(layer_param_spec(cfg, i).items()),
                    name=f"layer_{i}")()
            for i in range(cfg.num_hidden_layers)
        ]
        final_norm = self.param("final_norm", zeros, (H,), _F32)
        lm_head = self.param("lm_head", _normal(std), (H, cfg.vocab_size), _F32)
        value_w = self.param("value_w", _normal(std), (H,), _F32)
        value_b = self.param("value_b", zeros, (), _F32)

        T = tokens.shape[0]
        x = jnp.take(embedding, tokens.astype(jnp.int32), axis=0)
        all_stats = []
        if T == 1:
            keep = 1.0 - resets[0].astype(_F32)
            pos = (carry["pos"] * keep).astype(jnp.int32)
            x, new_layers = x[0], []
            for i, p in enumerate(layers):
                x, state, stats = _decoder_layer_step(
                    p, x, carry["layers"][i], pos, keep, cfg, dtype,
                    cfg.is_attention(i),
                )
                new_layers.append(state)
                all_stats.append(stats)
            x = x[None]
            carry = {"layers": new_layers, "pos": pos + 1}
        else:
            for i, p in enumerate(layers):
                # Each layer is recomputed in the backward pass: kept,
                # the activations of 8,192 tokens do not fit beside the
                # weights, their gradients and Adam's moments.
                layer = jax.checkpoint(
                    lambda p, x, a=cfg.is_attention(i): (
                        _decoder_layer_seq(p, x, cfg, dtype, a)
                    )
                )
                x, stats = layer(p, x)
                all_stats.append(stats)
        with jax.named_scope(profiling.LM_HEAD):
            h = rms_norm(x, final_norm, cfg.rms_norm_eps)
            logits = _mm(h, lm_head, dtype)
        values = jnp.dot(h, value_w, precision=_HIGHEST) + value_b
        return logits, values, carry, moe.stack_layer_stats(all_stats)

    def initialize_carry(self, batch: int) -> Dict[str, Any]:
        """The empty carry for ``batch`` environments."""
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        channels = 2 * cfg.linear_num_key_heads * dk + nv * dv
        layers = []
        for i in range(cfg.num_hidden_layers):
            if cfg.is_attention(i):
                shape = (batch, self.cache_len, cfg.num_key_value_heads,
                         cfg.head_dim)
                layers.append({"k": jnp.zeros(shape, dtype),
                               "v": jnp.zeros(shape, dtype)})
            else:
                layers.append({
                    "S": jnp.zeros((batch, nv, dk, dv), _F32),
                    "conv": jnp.zeros(
                        (batch, cfg.linear_conv_kernel_dim - 1, channels),
                        _F32,
                    ),
                })
        return {"layers": layers, "pos": jnp.zeros((batch,), jnp.int32)}
