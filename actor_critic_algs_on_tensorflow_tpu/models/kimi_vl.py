"""Kimi-VL-A3B's language model as a sequence-policy core: multi-head
latent attention (MLA) as the mixer, a leading dense SwiGLU layer, then
sigmoid-routed experts with two shared experts as every layer's MLP, a
language-model head as the policy and a linear value head.

Source of the layer equations: the published ``config.json`` of
moonshotai/Kimi-VL-A3B-Instruct (the language model's keys; the
DeepSeek-V3 layout) and the loader that reads it
(``modeling_deepseek.py``: ``DeepseekV3Attention``, ``MoEGate``,
``DeepseekV3MoE``, ``DeepseekV3RMSNorm``); DeepSeek-AI 2024,
"DeepSeek-V2", arXiv:2405.04434 section 2.1 for latent attention and
its absorbed form. Not here: the vision tower and its projector (the
catalog gives no width of theirs), the auxiliary sequence-balance loss
(``seq_aux``: the config gives no coefficient; the loss is the
trainer's), and the update of the routing bias (below).

The carry interface is ``models/qwen3_next.py``'s
(``algos/common.py::make_recurrent_policy_head``): ``__call__(tokens
[T, B] int32, resets [T, B], carry)`` returns ``(logits [T, B, V],
values [T, B], carry, stats)``. The carry holds per layer ONE cache of
latents ``[B, cache_len, kv_lora_rank + qk_rope_head_dim]`` in the
compute dtype — a token's normalised latent ``c`` and its rotated rope
key ``k^r``, shared by all heads: 576 numbers a token a layer where
per-head keys and values would be 5,120 — and ``pos [B]``, the position
since the episode began. The layers' caches are ONE array ``[B,
layers, cache_len, 576]`` (``carry["layers"][:, i]`` is layer ``i``'s;
the env axis leads, as the trainer shards every leaf of a carry): each
layer writes its row into it in place and reads its own part. As six
arrays of 75 MB the TPU's compiler staged each whole through VMEM and
back around its scatter, every step (PERF.md section 6, PR 32); the
one array is larger than VMEM and stays where it is. Two forms of one
layer share the parameters:

* ``T == 1`` — the step form, decode: ``(c, k^r)`` is written at
  ``pos`` and the query attends over the latents themselves, the key-up
  projection absorbed into the query (``q^ = q^n W_UK^T``) and the
  value-up projection applied after the weighted sum (``o = (sum_j a_j
  c_j) W_UV``). No key or value of the cache is ever rebuilt, and the
  cache is read as one operand for all heads: lowered for a TPU at the
  published widths by one Pallas kernel that reads each row up to
  ``pos`` once and none beyond its chunk (``ops/pallas_mla_step.py``),
  anywhere else by two plain products over the whole cache; nothing
  but the lowering platform and the cache's shape chooses. Where
  ``resets`` is set the position is zeroed before the step (a row
  beyond the position is never read).
* ``T > 1`` — the sequence form, the teacher-forced pass: the latents
  are expanded into per-head keys and values (``[k^n; v] = c W_kvb``)
  and causal attention runs over the whole sequence from an EMPTY
  carry, position 0 at step 0. ``carry`` and ``resets`` are not read
  and the carry is handed back as it came
  (``replays_from_empty_carry``).

Rotary embedding: over the ``qk_rope_head_dim`` rope dimensions the
INTERLEAVED pairs ``(x[2i], x[2i + 1])`` are rotated by ``pos *
rope_theta^(-2i / d)``, as the published loader pairs them; the result
is laid out as halves (all first elements, then all second ones) for
queries and keys alike, which no dot product sees.

Parameters are float32. Matrix products run in ``dtype`` with float32
accumulation, and the cache, an input of such products, is held in
``dtype``; the norms, the router (product, sigmoid, top-k, weights),
the softmax and both heads' outputs are float32 whatever ``dtype``
says. RMSNorm is the plain ``w * x / rms(x)``, ``w`` from 1.

The expert layer is ``models/moe.py``'s, shared with Qwen3-Next; this
model's ``route`` is the published ``noaux_tc`` gate at ``n_group =
topk_group = 1``: sigmoid scores, the top-k of ``score + bias``, the
weights the scores WITHOUT the bias, renormalised and scaled by
``routed_scaling_factor``. The bias (``e_score_correction_bias``) is a
parameter that no gradient reaches (it enters the selection only) and
that nothing updates: the balancing rule's speed is a recipe value the
config does not give. The two shared experts are one ungated SwiGLU of
twice the routed width.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.models import moe
from actor_critic_algs_on_tensorflow_tpu.models.moe import mm as _mm
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_INIT_STD = 0.02  # every matrix, normal (assumed; the config gives none)
# The step form's counter: the share of a cache's rows a step read, the
# same in every layer (1 where the plain form ran).
CACHE_ROWS_READ = "mla_cache_rows_read_share"


@dataclasses.dataclass(frozen=True)
class KimiVLConfig:
    """The published keys of ``config.json`` (defaults: the A3B
    language model's) and what this chip holds of them.
    ``num_hidden_layers``, ``vocab_size`` and ``experts_held`` are the
    held share; no other default differs from the source."""

    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    n_shared_experts: int = 2
    n_routed_experts: int = 64
    routed_scaling_factor: float = 2.446
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    num_experts_per_tok: int = 6
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 800000.0
    vocab_size: int = 163840
    # The share of the expert layer held here.
    first_expert: int = 0
    experts_held: int = 64
    capacity_factor: float = 2.0

    def __post_init__(self):
        built = {"q_lora_rank": None, "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid"}
        for key, value in built.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"KimiVLConfig.{key}={getattr(self, key)!r}: only "
                    f"{value!r} is built (the published value)"
                )
        if not any(map(self.is_expert_layer, range(self.num_hidden_layers))):
            raise ValueError("no expert layer among the layers held")

    def is_expert_layer(self, layer: int) -> bool:
        return (layer >= self.first_k_dense_replace
                and layer % self.moe_layer_freq == 0)

    @property
    def expert_spec(self) -> moe.ExpertSpec:
        return moe.ExpertSpec(
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            first_expert=self.first_expert, experts_held=self.experts_held,
            capacity_factor=self.capacity_factor,
        )

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


def layer_param_spec(cfg: KimiVLConfig, layer: int):
    """``{name: (shape, init)}`` of one decoder layer."""
    H, w = cfg.hidden_size, nn.initializers.normal(_INIT_STD)
    ones = nn.initializers.ones_init()
    nh, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    spec = {
        "input_norm": ((H,), ones), "post_norm": ((H,), ones),
        "q_proj": ((H, nh * (dn + dr)), w),
        "kv_a_proj": ((H, rank + dr), w), "kv_a_norm": ((rank,), ones),
        "kv_b_proj": ((rank, nh * (dn + dv)), w),
        "o_proj": ((nh * dv, H), w),
    }
    if not cfg.is_expert_layer(layer):
        I = cfg.intermediate_size
        spec.update(mlp_gate=((H, I), w), mlp_up=((H, I), w),
                    mlp_down=((I, H), w))
        return spec
    I, E = cfg.moe_intermediate_size, cfg.experts_held
    Is = cfg.n_shared_experts * I
    spec.update(
        router=((H, cfg.n_routed_experts), w),
        e_score_correction_bias=((cfg.n_routed_experts,), w),
        shared_w_gate=((H, Is), w), shared_w_up=((H, Is), w),
        shared_w_down=((Is, H), w),
        w_gate=((E, H, I), w), w_up=((E, H, I), w), w_down=((E, I, H), w),
    )
    return spec


# ---- pieces ------------------------------------------------------------


def rms_norm(x, w, eps):
    """RMSNorm, float32: ``x * rsqrt(mean(x^2) + eps) * w``."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _angles(positions, cfg: KimiVLConfig):
    """``positions [...]`` -> the rotation angles ``[..., d_rope / 2]``."""
    d = cfg.qk_rope_head_dim
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    return positions.astype(_F32)[..., None] * inv_freq


def _rope(x, angles):
    """Rotate the interleaved pairs of ``x [..., d_rope]`` by ``angles``
    (broadcast against ``[..., d_rope / 2]``); halves come out."""
    first, second = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], -1
    )


# ---- latent attention ---------------------------------------------------


def _dot(spec, a, b, dtype):
    """A product of attention (``einsum``) in ``dtype``, float32 sums."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32)


def _mla_project(p, x, angles, cfg, dtype):
    """``x [..., H]``, ``angles [..., d_rope / 2]`` -> the query's two
    parts ``q^n [..., nh, d_nope]`` and ``q^r [..., nh, d_rope]``
    (rotated), the normalised latent ``c [..., rank]`` and the rotated
    rope key ``k^r [..., d_rope]``, float32."""
    nh, rank, dn = (cfg.num_attention_heads, cfg.kv_lora_rank,
                    cfg.qk_nope_head_dim)
    with jax.named_scope(profiling.MIXER_PROJ):
        q = _mm(x, p["q_proj"], dtype)
        kva = _mm(x, p["kv_a_proj"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        q = q.reshape(x.shape[:-1] + (nh, -1))
        c = rms_norm(kva[..., :rank], p["kv_a_norm"], cfg.rms_norm_eps)
        return (q[..., :dn], _rope(q[..., dn:], angles[..., None, :]), c,
                _rope(kva[..., rank:], angles))


def _softmax_scale(cfg):
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_seq(p, x, cfg, dtype):
    """The expanded form: ``x [T, b, H]``, causal over the sequence,
    position = step; per-head keys and values from the latents."""
    T, b, _ = x.shape
    nh, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim
    with jax.named_scope(profiling.MIXER_POINTWISE):
        x, angles = jnp.swapaxes(x, 0, 1), _angles(jnp.arange(T), cfg)
    q_nope, q_rope, c, k_rope = _mla_project(p, x, angles, cfg, dtype)
    with jax.named_scope(profiling.MIXER_PROJ):
        kv = _mm(c, p["kv_b_proj"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        kv = kv.reshape(b, T, nh, -1)
        k_nope, v = kv[..., :dn], kv[..., dn:]

    with jax.named_scope(profiling.MIXER_CORE), jax.named_scope(
        profiling.MLA_SEQ_ATTEND
    ):
        # q . [k^n; k^r] with the one rope key shared by the heads
        scores = _softmax_scale(cfg) * (
            _dot("bqhd,bshd->bhqs", q_nope, k_nope, dtype)
            + _dot("bqhd,bsd->bhqs", q_rope, k_rope, dtype)
        )
        scores = jnp.where(
            jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf
        )
        probs = jax.nn.softmax(scores, axis=-1)
        out = _dot("bhqs,bshd->bqhd", probs, v, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        out = out.reshape(b, T, -1)
    with jax.named_scope(profiling.MIXER_PROJ):
        out = _mm(out, p["o_proj"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        return jnp.swapaxes(out, 0, 1)


def latent_attention(q, cache, pos, scale, rank, dtype):
    """The core of the absorbed form in plain array operations: ``q
    [B, h, rank + d_rope]`` over ``cache [B, L, rank + d_rope]``'s rows
    ``0..pos [B]``, rows beyond masked: ``[B, h, rank]`` float32. Two
    passes over the whole cache (scores, then the weighted sum)."""
    L = cache.shape[1]
    # one operand for all heads: [q^; q^r] . [c_j; k^r_j]
    scores = _dot("bhc,blc->bhl", q, cache, dtype) * scale
    visible = jnp.arange(L)[None, :] <= pos[:, None]
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # The weighted sum over the whole row; the rope key's columns come
    # out too and are dropped (a slice of the cache would be a copy of
    # it).
    return _dot("bhl,blc->bhc", probs, cache, dtype)[..., :rank]


def _kernel_or_plain(caches, rank, kernel, plain, *operands):
    """``kernel(pallas_mla_step, *operands)`` where the program is
    lowered for a TPU and ``caches [B, layers, L, rank + d_rope]`` take
    the one-pass kernel (the latent part whole lane tiles, the rows
    whole chunks), ``plain(*operands)`` anywhere else: nothing but the
    lowering platform and the caches' shape chooses."""
    # Imported where it is used: Pallas is ~1.5 s of imports, and
    # cli/train.py's PRESETS import this module for every preset.
    from actor_critic_algs_on_tensorflow_tpu.ops import pallas_mla_step

    if not pallas_mla_step.fits(caches, rank):
        return plain(*operands)
    return jax.lax.platform_dependent(
        *operands, tpu=functools.partial(kernel, pallas_mla_step),
        default=plain,
    )


def _latent_attention(q, caches, layer, pos, scale, rank, dtype):
    """``latent_attention`` over layer ``layer``'s cache in ``caches``:
    the one-pass kernel over the rows that exist
    (``ops/pallas_mla_step.py``), or the plain form, which reads every
    row."""

    def kernel(ops, q, caches, pos):
        return ops.latent_attention(
            q, caches, layer, pos, scale=scale, rank=rank
        )

    def plain(q, caches, pos):
        return latent_attention(q, caches[:, layer], pos, scale, rank, dtype)

    return _kernel_or_plain(caches, rank, kernel, plain, q, caches, pos)


def _rows_read_share(caches, pos, rank):
    """The share of a cache's rows that a step at ``pos`` read, the same
    in every layer: what the kernel's index map fetches, or every row."""

    def kernel(ops, pos):
        return ops.rows_read_share(pos, caches.shape[2])

    def plain(pos):
        return jnp.ones((), _F32)

    return _kernel_or_plain(caches, rank, kernel, plain, pos)


def mla_step(p, x, caches, layer, pos, cfg, dtype):
    """The absorbed form, one token an env: ``x [B, H]``, ``caches
    [B, layers, L, rank + d_rope]`` of which this is layer ``layer`` (a
    Python int), ``pos [B]``. The token's latent and rope key are
    written at ``pos``; the query, carried into the latent space,
    attends over the cache's rows up to ``pos``; rows beyond it are
    never read into the result."""
    B = caches.shape[0]
    nh, rank, dn = (cfg.num_attention_heads, cfg.kv_lora_rank,
                    cfg.qk_nope_head_dim)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        angles = _angles(pos, cfg)
    q_nope, q_rope, c, k_rope = _mla_project(p, x, angles, cfg, dtype)

    # The absorbed form's two products with the halves of kv_b_proj are
    # projections like the others; the rest of it is the core.
    with jax.named_scope(profiling.MLA_ABSORBED):
        with jax.named_scope(profiling.MIXER_CORE):
            row = jnp.concatenate([c, k_rope], -1).astype(caches.dtype)
            caches = caches.at[jnp.arange(B), layer, pos].set(
                row, indices_are_sorted=True, unique_indices=True
            )
        with jax.named_scope(profiling.MIXER_PROJ):
            w_kvb = p["kv_b_proj"].reshape(rank, nh, -1)
            w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]
            q_latent = _dot("bhn,chn->bhc", q_nope, w_uk, dtype)
        with jax.named_scope(profiling.MIXER_CORE):
            o_latent = _latent_attention(
                jnp.concatenate([q_latent, q_rope], -1), caches, layer, pos,
                _softmax_scale(cfg), rank, dtype,
            )
        with jax.named_scope(profiling.MIXER_PROJ):
            out = _dot("bhc,chv->bhv", o_latent, w_uv, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        out = out.reshape(B, -1)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out, p["o_proj"], dtype), caches


# ---- the expert block --------------------------------------------------


def route(p, x, cfg):
    """``x [N, H]`` -> the top-k experts ``[N, k]`` of ALL
    ``n_routed_experts`` and their weights, float32 throughout: the
    bias chooses, the scores weigh."""
    logits = jnp.dot(x.astype(_F32), p["router"], precision=_HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["e_score_correction_bias"])
    _, experts = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, experts, -1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return experts, weights * cfg.routed_scaling_factor


def routed_experts(p, x, cfg: KimiVLConfig, dtype):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum under this model's ``route``."""
    return moe.routed_experts(
        p, x, cfg.expert_spec, dtype, functools.partial(route, cfg=cfg)
    )


def shared_experts(p, x, dtype):
    """The ``n_shared_experts`` as one ungated SwiGLU of their summed
    width: what every chip computes alike."""
    with jax.named_scope(profiling.MOE_SHARED):
        return moe.swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                          p["shared_w_down"], dtype)


def moe_block(p, x, cfg: KimiVLConfig, dtype):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum plus the shared experts."""
    routed, stats = routed_experts(p, x, cfg, dtype)
    return routed + shared_experts(p, x, dtype), stats


# ---- the model ---------------------------------------------------------


def _feed_forward(p, x, cfg, dtype, expert: bool):
    """``x + F(N(x))`` on ``x [..., H]``: the expert block (with its
    counters) or the leading dense SwiGLU (``None``)."""
    if expert:
        with jax.named_scope(profiling.MOE):
            h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
            y, stats = moe_block(p, h.reshape(-1, x.shape[-1]), cfg, dtype)
        return x + y.reshape(x.shape), stats
    with jax.named_scope(profiling.DENSE_MLP):
        h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        y = moe.swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], dtype)
    return x + y, None


def _decoder_layer_seq(p, x, cfg, dtype, expert: bool):
    with jax.named_scope(profiling.MLA):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        y = mla_seq(p, h, cfg, dtype)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + y
    return _feed_forward(p, x, cfg, dtype, expert)


def _decoder_layer_step(p, x, caches, layer, pos, cfg, dtype, expert: bool):
    with jax.named_scope(profiling.MLA):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        y, caches = mla_step(p, h, caches, layer, pos, cfg, dtype)
    x, stats = _feed_forward(p, x + y, cfg, dtype, expert)
    return x, caches, stats


def iteration_stats(rollout_stats, update_stats, axis_name):
    """The expert layer's counters of one training iteration and, of
    the rollout's steps (the update runs the expanded form, which has
    no cache), the mean share of the cache's rows a step read."""
    stats = moe.iteration_moe_stats(rollout_stats, update_stats, axis_name)
    stats[CACHE_ROWS_READ] = jax.lax.pmean(
        jnp.mean(rollout_stats[CACHE_ROWS_READ]), axis_name
    )
    return stats


class KimiVLActorCritic(nn.Module):
    """The policy over ``cfg.vocab_size`` tokens and the value."""

    cfg: KimiVLConfig
    cache_len: int
    dtype: Any = jnp.float32
    # The sequence form reads neither carry nor resets (see above).
    replays_from_empty_carry = True
    # (rollout rows, update rows, axis) -> an iteration's counters
    iteration_stats = staticmethod(iteration_stats)

    @nn.compact
    def __call__(self, tokens, resets, carry):
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        H = cfg.hidden_size
        w = nn.initializers.normal(_INIT_STD)
        embedding = self.param("embedding", w, (cfg.vocab_size, H), _F32)
        layers = [
            moe.Params(tuple(layer_param_spec(cfg, i).items()),
                       name=f"layer_{i}")()
            for i in range(cfg.num_hidden_layers)
        ]
        final_norm = self.param(
            "final_norm", nn.initializers.ones_init(), (H,), _F32
        )
        lm_head = self.param("lm_head", w, (H, cfg.vocab_size), _F32)
        value_w = self.param("value_w", w, (H,), _F32)
        value_b = self.param(
            "value_b", nn.initializers.zeros_init(), (), _F32
        )

        x = jnp.take(embedding, tokens.astype(jnp.int32), axis=0)
        all_stats = []
        if tokens.shape[0] == 1:
            keep = 1.0 - resets[0].astype(_F32)
            pos = (carry["pos"] * keep).astype(jnp.int32)
            x, caches = x[0], carry["layers"]
            for i, p in enumerate(layers):
                x, caches, stats = _decoder_layer_step(
                    p, x, caches, i, pos, cfg, dtype,
                    cfg.is_expert_layer(i),
                )
                all_stats.append(stats)
            x = x[None]
            carry = {"layers": caches, "pos": pos + 1}
        else:
            for i, p in enumerate(layers):
                # Each layer is recomputed in the backward pass, as in
                # qwen3_next.py: kept, the activations of 8,192 tokens
                # do not fit beside the weights and Adam's moments.
                layer = jax.checkpoint(
                    lambda p, x, e=cfg.is_expert_layer(i): (
                        _decoder_layer_seq(p, x, cfg, dtype, e)
                    )
                )
                x, stats = layer(p, x)
                all_stats.append(stats)
        with jax.named_scope(profiling.LM_HEAD):
            h = rms_norm(x, final_norm, cfg.rms_norm_eps)
            logits = _mm(h, lm_head, dtype)
        values = jnp.dot(h, value_w, precision=_HIGHEST) + value_b
        stats = moe.stack_layer_stats(
            [s for s in all_stats if s is not None]
        )
        if tokens.shape[0] == 1:
            stats[CACHE_ROWS_READ] = _rows_read_share(
                carry["layers"], pos, cfg.kv_lora_rank
            )
        return logits, values, carry, stats

    def initialize_carry(self, batch: int) -> Dict[str, Any]:
        """The empty carry for ``batch`` environments."""
        shape = (batch, self.cfg.num_hidden_layers, self.cache_len,
                 self.cfg.cache_width)
        return {
            "layers": jnp.zeros(shape, jnp.dtype(self.dtype)),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
