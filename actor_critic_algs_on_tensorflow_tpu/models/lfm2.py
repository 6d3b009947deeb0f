"""LFM2-24B-A2B as a sequence-policy core: double-gated short
convolutions as most of the mixers, one grouped-query attention layer
with per-head query/key norms in every four, a leading dense SwiGLU,
then sigmoid-routed experts after both kinds of mixer, the tied
embedding as the language-model head and a linear value head.

Source of the layer equations: the published ``config.json`` of
LiquidAI/LFM2-24B-A2B (``model_type: lfm2_moe``,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json)
and the loader of its dense family (``transformers``'
``modeling_lfm2.py``: ``Lfm2ShortConv``, ``Lfm2Attention``,
``Lfm2RMSNorm``, ``Lfm2DecoderLayer``, ``Lfm2Model``); the expert
block is the ``lfm2_moe`` routing: sigmoid scores, the top-k of score +
``expert_bias``, the weights the scores renormalised.

The model. ``x_0 = E[token]``; each layer ``x <- x + M(N_op(x))``,
then ``x <- x + F(N_ffn(x))``, ``M`` the short convolution or the
attention as ``layer_types[i]`` says; ``h = N_f(x)`` (the loader's
``embedding_norm``), ``logits = h E^T`` (the tied matrix) and the value
``w_v . h + b_v``. ``N`` is the plain RMSNorm ``w * x / rms(x)``, ``w``
from 1, eps ``norm_eps``.

The short convolution (``Lfm2ShortConv``, no bias anywhere): ``[B | C |
x~] = y W_in``; ``u = B * x~``; ``v_t = sum_{j < L} w_j * u_{t - L + 1
+ j}``, a depthwise causal convolution of ``L = conv_L_cache`` taps;
``M = (C * v) W_out``. The step form carries the last ``L - 1`` values
of ``u`` of each convolution layer (two at the published ``L = 3``).

The attention (``Lfm2Attention``): grouped-query, causal; the query and
key normed per head (an RMSNorm over ``head_dim`` each), then
rotate-half rotary positions over all of ``head_dim`` at
``rope_theta``; scores scaled by ``head_dim ** -0.5``; no bias.

``F`` is a SwiGLU ``w2(silu(w1 x) * w3 x)``: dense of width
``intermediate_size`` in the layers ``< num_dense_layers``, the expert
layer in every later one: ``s = sigmoid(x W_g)`` over ``num_experts``,
the top-``num_experts_per_tok`` of ``s + expert_bias``, weighted by
``s`` there, renormalised (``/ (sum + 1e-6)``) where
``norm_topk_prob`` and scaled by ``routed_scaling_factor``; experts of
width ``moe_intermediate_size``; no shared expert. The routing is
``models/moe.py::route_sigmoid_top_k`` and the layer
``moe.routed_experts``, told which experts this chip holds.

The carry interface is ``models/qwen3_next.py``'s
(``algos/common.py::make_recurrent_policy_head``). The carry holds the
layers of a kind stacked into ONE array each (as several arrays the
compiler staged each whole through VMEM every step, PERF.md section
6): ``conv [B, conv layers, L - 1, hidden]`` float32 (the last
values of ``u``), ``k`` and ``v [B, attention layers, cache_len, kv
heads, head_dim]`` in the compute dtype (keys after their norm and
rotation), and ``pos [B]``. Two forms share the parameters:

* ``T == 1`` — the step form: one token an env. A convolution reads its
  carried values of ``u`` and the new one and shifts its tail by one;
  attention writes the token's key and value at ``pos`` and reads the
  rows up to it. Where ``resets`` is set, tails and position count as
  empty before the step (a cache row beyond the position is never
  read).
* ``T > 1`` — the sequence form, the teacher-forced pass from the EMPTY
  carry (``replays_from_empty_carry``): the convolution as a sum of
  ``L`` shifted slices of ``u`` and causal attention over the whole
  sequence. ``carry`` and ``resets`` are not read.

Parameters are float32. Matrix products of a weight, and the
attention's two products, run in ``dtype`` with float32 accumulation
(``decoder.mm``); the norms, the two gates, the convolution and its
tails, the router (product, sigmoid, top-k, weights), the softmax and
both heads' outputs are float32 whatever ``dtype`` says.

Initialisation (the config gives none; the configuration file lists it
under ``assumed``): matrices normal(0.02); norm weights 1; the
convolution's weight uniform in ``(-1/sqrt(L), 1/sqrt(L))``, the
framework's default for a depthwise kernel of ``L`` taps;
``expert_bias`` normal(0.02), a parameter no gradient reaches and
nothing updates. The counter ``shortconv_tail_share`` (below) says on
every run that the convolutions read their carried values.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.models import decoder, moe
from actor_critic_algs_on_tensorflow_tpu.models.decoder import (
    mm as _mm,
    rms_norm,
)
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
# The counter of both forms, one row a call: of the convolutions'
# output v, the root mean square of its carried taps' part (the terms
# on earlier tokens, which the step form reads from its tails) over
# that of the whole, a token and a layer, meaned over the call's tokens
# and convolution layers. 0 where every tail is empty.
TAIL_SHARE = "shortconv_tail_share"
# The renormalisation's epsilon of the lfm2_moe routing (assumed: the
# loader of that type is not the family's dense one).
_ROUTE_EPS = 1e-6
# The published 40 layers: attention at 2, 6, ..., 38, convolutions else.
_LAYER_TYPES = tuple(ATTENTION if i % 4 == 2 else CONV for i in range(40))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published keys of ``config.json`` under their published
    names (defaults: the row's; ``rope_parameters`` as a tuple of items)
    and what this chip holds of them (``first_expert``,
    ``experts_held``, ``capacity_factor``); the preset sets the held
    share and cuts ``num_hidden_layers``, ``num_dense_layers``,
    ``layer_types`` and ``vocab_size``."""

    conv_L_cache: int = 3
    conv_bias: bool = False
    hidden_size: int = 2048
    intermediate_size: int = 11776
    layer_types: Tuple[str, ...] = _LAYER_TYPES
    max_position_embeddings: int = 128000
    model_type: str = "lfm2_moe"
    moe_intermediate_size: int = 1536
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    rope_parameters: tuple = (("rope_theta", 1000000),
                              ("rope_type", "default"))
    routed_scaling_factor: float = 1
    use_expert_bias: bool = True
    vocab_size: int = 65536
    # The share of the expert layer held here.
    first_expert: int = 0
    experts_held: int = 64
    capacity_factor: float = 2.0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        decoder.refuse_unbuilt(self, {"conv_bias": False,
                                      "use_expert_bias": True})
        if dict(self.rope_parameters).get("rope_type") != "default":
            raise ValueError(
                f"Lfm2Config.rope_parameters={self.rope_parameters!r}: "
                "only the default rotary positions are built"
            )
        if len(self.layer_types) != self.num_hidden_layers or not (
            set(self.layer_types) <= {CONV, ATTENTION}
        ):
            raise ValueError(
                f"layer_types must name num_hidden_layers="
                f"{self.num_hidden_layers} layers, each {CONV!r} or "
                f"{ATTENTION!r}; got {self.layer_types!r}"
            )
        if self.conv_L_cache < 2:
            raise ValueError("a short convolution of one tap has no carry")
        if not any(map(self.is_expert_layer, range(self.num_hidden_layers))):
            raise ValueError("no expert layer among the layers held")

    def is_expert_layer(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return dict(self.rope_parameters)["rope_theta"]

    @property
    def expert_spec(self) -> moe.ExpertSpec:
        return moe.ExpertSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            first_expert=self.first_expert, experts_held=self.experts_held,
            capacity_factor=self.capacity_factor,
        )

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


# ---- parameters --------------------------------------------------------


def _uniform(bound):
    def init(key, shape, dtype=_F32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def layer_param_spec(cfg: Lfm2Config, layer: int):
    """``{name: (shape, init)}`` of one decoder layer."""
    H, w = cfg.hidden_size, nn.initializers.normal(decoder.INIT_STD)
    ones = nn.initializers.ones_init()
    spec = {"input_norm": ((H,), ones), "post_norm": ((H,), ones)}
    if cfg.layer_types[layer] == ATTENTION:
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        spec.update(
            q_proj=((H, nh * hd), w), k_proj=((H, nkv * hd), w),
            v_proj=((H, nkv * hd), w), o_proj=((nh * hd, H), w),
            q_norm=((hd,), ones), k_norm=((hd,), ones),
        )
    else:
        L = cfg.conv_L_cache
        spec.update(
            in_proj=((H, 3 * H), w),
            conv=((L, H), _uniform(1.0 / math.sqrt(L))),
            out_proj=((H, H), w),
        )
    if not cfg.is_expert_layer(layer):
        I = cfg.intermediate_size
        spec.update(mlp_gate=((H, I), w), mlp_up=((H, I), w),
                    mlp_down=((I, H), w))
        return spec
    I, E = cfg.moe_intermediate_size, cfg.experts_held
    spec.update(
        router=((H, cfg.num_experts), w),
        expert_bias=((cfg.num_experts,), w),
        w_gate=((E, H, I), w), w_up=((E, H, I), w), w_down=((E, I, H), w),
    )
    return spec


# ---- the double-gated short convolution --------------------------------


def _conv_inputs(p, x, dtype):
    """``x [..., H]`` -> the output gate ``C`` and ``u = B * x~``
    ``[..., H]``, float32."""
    with jax.named_scope(profiling.MIXER_PROJ):
        bcx = _mm(x, p["in_proj"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        B, C, xt = jnp.split(bcx, 3, axis=-1)
        return C, B * xt


def _tail_share(carried, whole):
    """The counter's reading of one layer: ``rms(carried) / rms(whole)``
    over the hidden axis, meaned over the tokens."""
    def rms(v):
        return jnp.sqrt(jnp.mean(v * v, -1))

    return jnp.mean(rms(carried) / jnp.maximum(rms(whole), 1e-30))


def _conv_output(p, C, v, dtype):
    with jax.named_scope(profiling.MIXER_POINTWISE):
        y = C * v
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(y, p["out_proj"], dtype)


def shortconv_seq(p, x, cfg, dtype):
    """``x [b, T, H]`` from empty tails -> ``([b, T, H], the layer's
    tail share)``."""
    T, L = x.shape[1], cfg.conv_L_cache
    C, u = _conv_inputs(p, x, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        padded = jnp.pad(u, ((0, 0), (L - 1, 0), (0, 0)))
        carried = sum(padded[:, j: j + T] * p["conv"][j]
                      for j in range(L - 1))
        v = carried + u * p["conv"][L - 1]
        share = _tail_share(carried, v)
    return _conv_output(p, C, v, dtype), share


def shortconv_step(p, x, tails, layer, keep, cfg, dtype):
    """``x [B, H]``; ``tails [B, layers, L - 1, H]`` of which this is
    convolution layer ``layer`` (a Python int), updated in place;
    ``keep [B]``, 0 where the env starts over: its tail counts as
    empty. -> ``(y [B, H], tails, the layer's tail share)``."""
    L = cfg.conv_L_cache
    C, u = _conv_inputs(p, x, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        tail = tails[:, layer] * keep[:, None, None]
        carried = jnp.sum(tail * p["conv"][:L - 1], 1)
        v = carried + u * p["conv"][L - 1]
        window = jnp.concatenate([tail, u[:, None]], 1)
        tails = tails.at[:, layer].set(window[:, 1:])
        share = _tail_share(carried, v)
    return _conv_output(p, C, v, dtype), tails, share


# ---- grouped-query attention with per-head query/key norms ---------------


def _project(p, x, positions, cfg, dtype):
    """``x [b, t, H]``, ``positions`` (``[t]`` or ``[b, t]``) -> query
    ``[b, t, nh, hd]``, key and value ``[b, t, nkv, hd]``, float32; the
    query and key normed per head, then rotated."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    with jax.named_scope(profiling.MIXER_PROJ):
        q, k, v = (
            _mm(x, p[w], dtype) for w in ("q_proj", "k_proj", "v_proj")
        )
    with jax.named_scope(profiling.MIXER_POINTWISE):
        q = rms_norm(q.reshape(x.shape[:-1] + (nh, hd)), p["q_norm"],
                     cfg.norm_eps)
        k = rms_norm(k.reshape(x.shape[:-1] + (nkv, hd)), p["k_norm"],
                     cfg.norm_eps)
        q = decoder.rotary(q, positions, cfg.rope_theta, hd)
        k = decoder.rotary(k, positions, cfg.rope_theta, hd)
        v = v.reshape(x.shape[:-1] + (nkv, hd))
    return q, k, v


def gqa_seq(p, x, positions, cfg, dtype):
    """``x [b, T, H]``, causal over the sequence, ``positions [T]`` the
    steps."""
    q, k, v = _project(p, x, positions, cfg, dtype)
    with jax.named_scope(profiling.MIXER_CORE):
        out = decoder.causal_attend(q, k, v, dtype)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out, p["o_proj"], dtype)


def gqa_step(p, x, keys, values, layer, pos, cfg, dtype):
    """One token an env against the cache: ``x [B, H]``, ``keys`` and
    ``values [B, layers, L, nkv, hd]`` of which this is attention layer
    ``layer`` (a Python int), ``pos [B]``. The new key and value are
    written at ``pos``, in place; rows beyond it are masked."""
    q, k, v = _project(p, x[:, None], pos[:, None], cfg, dtype)
    with jax.named_scope(profiling.MIXER_CORE):
        out, keys, values = decoder.cached_attend(
            q, k, v, keys, values, layer, pos, dtype
        )
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out[:, 0], p["o_proj"], dtype), keys, values


# ---- the feed-forward: dense, or the expert layer -------------------------


def route(p, x, cfg):
    """``x [N, H]`` -> the top-k experts ``[N, k]`` of ALL
    ``num_experts`` and their weights: the bias chooses, the scores
    weigh."""
    return moe.route_sigmoid_top_k(
        p, x, p["expert_bias"], cfg.num_experts_per_tok, cfg.norm_topk_prob,
        cfg.routed_scaling_factor, _ROUTE_EPS,
    )


def routed_experts(p, x, cfg: Lfm2Config, dtype):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum under this model's ``route``."""
    return moe.routed_experts(
        p, x, cfg.expert_spec, dtype, functools.partial(route, cfg=cfg)
    )


def _feed_forward(p, x, cfg, dtype, expert: bool):
    """``x + F(N(x))`` on ``x [..., H]``, and the expert layer's
    counters (``None`` for the dense layer)."""
    if expert:
        with jax.named_scope(profiling.MOE):
            h = rms_norm(x, p["post_norm"], cfg.norm_eps)
            y, stats = routed_experts(p, h.reshape(-1, x.shape[-1]), cfg,
                                      dtype)
        return x + y.reshape(x.shape), stats
    with jax.named_scope(profiling.DENSE_MLP):
        h = rms_norm(x, p["post_norm"], cfg.norm_eps)
        y = decoder.swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                           dtype)
        return x + y, None


# ---- the model -----------------------------------------------------------


def _mixer_scope(kind):
    return jax.named_scope(
        profiling.SHORTCONV if kind == CONV else profiling.GQA
    )


def _decoder_layer_seq(p, x, positions, cfg, dtype, kind: str,
                       expert: bool):
    """``x [b, T, H]``, ``positions [T]`` -> ``(x, the expert layer's
    counters or None, the layer's tail share or None)``."""
    share = None
    with _mixer_scope(kind):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.norm_eps)
        if kind == CONV:
            y, share = shortconv_seq(p, h, cfg, dtype)
        else:
            y = gqa_seq(p, h, positions, cfg, dtype)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + y
    x, stats = _feed_forward(p, x, cfg, dtype, expert)
    return x, stats, share


def _decoder_layer_step(p, x, carry, index, keep, cfg, dtype, kind: str,
                        expert: bool):
    """``x [B, H]`` through layer ``index`` of its kind (a Python int)
    -> ``(x, carry, the expert layer's counters or None, the layer's
    tail share or None)``; ``carry["pos"]`` is this step's position."""
    share = None
    with _mixer_scope(kind):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.norm_eps)
        if kind == CONV:
            y, tails, share = shortconv_step(
                p, h, carry["conv"], index, keep, cfg, dtype
            )
            carry = dict(carry, conv=tails)
        else:
            y, keys, values = gqa_step(
                p, h, carry["k"], carry["v"], index, carry["pos"], cfg, dtype
            )
            carry = dict(carry, k=keys, v=values)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + y
    x, stats = _feed_forward(p, x, cfg, dtype, expert)
    return x, carry, stats, share


def iteration_stats(rollout_stats, update_stats, axis_name):
    """The expert layer's counters of one training iteration and, of
    the rollout's steps, the mean tail share of the convolutions."""
    stats = moe.iteration_moe_stats(rollout_stats, update_stats, axis_name)
    stats[TAIL_SHARE] = jax.lax.pmean(
        jnp.mean(rollout_stats[TAIL_SHARE]), axis_name
    )
    return stats


class Lfm2ActorCritic(nn.Module):
    """The policy over ``cfg.vocab_size`` tokens and the value."""

    cfg: Lfm2Config
    cache_len: int
    dtype: Any = jnp.float32
    # The sequence form reads neither carry nor resets (see above).
    replays_from_empty_carry = True
    # (rollout rows, update rows, axis) -> an iteration's counters
    iteration_stats = staticmethod(iteration_stats)

    @nn.compact
    def __call__(self, tokens, resets, carry):
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        head, layers = decoder.parameters(
            self, cfg,
            [layer_param_spec(cfg, i) for i in range(cfg.num_hidden_layers)],
            nn.initializers.ones_init(), tied=True,
        )

        x = decoder.embed(head, tokens)
        expert_stats, shares = [], []
        kinds = zip(layers, cfg.layer_types,
                    map(cfg.is_expert_layer, range(cfg.num_hidden_layers)))
        if tokens.shape[0] == 1:
            keep, pos = decoder.step_position(carry, resets)
            x = x[0]
            carry = dict(carry, pos=pos)
            seen = {CONV: 0, ATTENTION: 0}  # layers of each kind so far
            for p, kind, expert in kinds:
                x, carry, stats, share = _decoder_layer_step(
                    p, x, carry, seen[kind], keep, cfg, dtype, kind, expert
                )
                seen[kind] += 1
                expert_stats.append(stats)
                shares.append(share)
            x = x[None]
            carry = dict(carry, pos=pos + 1)
        else:
            x = jnp.swapaxes(x, 0, 1)                       # [b, T, H]
            # (an argument of the layers: made inside a recomputed
            # layer, the constant is hoisted out of it without its scope)
            positions = jnp.arange(tokens.shape[0])
            for p, kind, expert in kinds:
                # Each layer is recomputed in the backward pass, as in
                # the other cores: kept, the activations of a
                # minibatch's tokens do not fit beside the weights,
                # their gradients and Adam's moments.
                layer = jax.checkpoint(
                    lambda p, x, t, kind=kind, expert=expert: (
                        _decoder_layer_seq(p, x, t, cfg, dtype, kind, expert)
                    )
                )
                x, stats, share = layer(p, x, positions)
                expert_stats.append(stats)
                shares.append(share)
            x = jnp.swapaxes(x, 0, 1)
        h, logits = decoder.lm_head(x, head, cfg.norm_eps, dtype)
        values = decoder.value(h, head)
        stats = moe.stack_layer_stats(
            [s for s in expert_stats if s is not None]
        )
        shares = [s for s in shares if s is not None]
        stats[TAIL_SHARE] = (jnp.mean(jnp.stack(shares)) if shares
                             else jnp.zeros((), _F32))
        return logits, values, carry, stats

    def initialize_carry(self, batch: int) -> Dict[str, Any]:
        """The empty carry for ``batch`` environments."""
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        cache = (batch, cfg.layers_of(ATTENTION), self.cache_len,
                 cfg.num_key_value_heads, cfg.head_dim)
        return {
            "conv": jnp.zeros(
                (batch, cfg.layers_of(CONV), cfg.conv_L_cache - 1,
                 cfg.hidden_size), _F32,
            ),
            "k": jnp.zeros(cache, dtype),
            "v": jnp.zeros(cache, dtype),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
