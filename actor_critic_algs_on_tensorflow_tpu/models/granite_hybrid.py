"""Granite-4.0-H-Micro as a sequence-policy core: Mamba-2 state-space
layers as the recurrent mixer, one grouped-query attention layer
without positions in every ten, a dense SwiGLU as every layer's
feed-forward (no router, no experts), the tied embedding as the
language-model head and a linear value head.

Source of the layer equations: the published ``config.json`` of
ibm-granite/granite-4.0-h-micro (``model_type: granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json)
and the loader that reads it (``transformers``'
``modeling_granitemoehybrid.py``: ``GraniteMoeHybridMambaLayer``,
``GraniteMoeHybridAttention``, ``GraniteMoeHybridMLP``,
``GraniteMoeHybridRMSNormGated``, ``GraniteMoeHybridDecoderLayer``);
Dao & Gu 2024, "Transformers are SSMs", arXiv:2405.21060, for the
recurrence and its chunked form (SSD).

The model. ``x_0 = embedding_multiplier * E[token]``; each layer ``x <-
x + r M(N_1(x))``, then ``x <- x + r F(N_2(x))`` with ``r =
residual_multiplier``, ``M`` the Mamba-2 mixer or the attention as
``layer_types[i]`` says and ``F`` the SwiGLU of width
``shared_intermediate_size``; ``h = N_f(x)``, ``logits = h E^T /
logits_scaling`` (the tied matrix) and the value ``w_v . h + b_v``.
``N`` is the plain RMSNorm ``w * x / rms(x)``, ``w`` from 1.

The Mamba-2 mixer (``d = mamba_expand * hidden`` inner channels as
``h`` heads of ``p``, state ``n``, one group): ``[z | xBC | dt] = y
W_in``; ``xBC <- silu(conv(xBC))``, a depthwise causal convolution of
``mamba_d_conv`` taps with bias; ``[x | B | C] = xBC``; ``Delta =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; a head's state ``S [p,
n]`` follows ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t (x) B_t`` and
gives ``y_t = S_t C_t + D x_t`` (``B``, ``C`` shared by the heads);
then the gated norm over the whole inner width, the gate first: ``g = y
* silu(z)``, ``g <- w_g * g / rms(g)``; and ``M = g W_out``.

The attention (``GraniteMoeHybridAttention`` under
``position_embedding_type: "nope"``): grouped-query, causal, no rotary
embedding or any other position, no query/key norm, no bias; the
softmax's scale is ``attention_multiplier`` (1/64 as published, not
``1 / sqrt(head_dim)``).

The carry interface is ``models/qwen3_next.py``'s
(``algos/common.py::make_recurrent_policy_head``). The carry holds the
layers of a kind stacked into ONE array each (as several arrays the
compiler staged each whole through VMEM every step, PERF.md section 6,
PR 32): ``state [B, mamba layers, h, p, n]`` float32, ``conv [B, mamba
layers, mamba_d_conv - 1, d + 2 n]`` float32 (the convolution's last
inputs, before the activation), ``k`` and ``v [B, attention layers,
cache_len, kv heads, head_dim]`` in the compute dtype, and ``pos [B]``,
kept for the cache's sake only. Two forms share the parameters:

* ``T == 1`` — the step form: one token an env through state, tail and
  cache. Where ``resets`` is set, state, tail and position count as
  empty before the step (a cache row beyond the position is never
  read). The state's update (``_state_step``) is one Pallas kernel
  where the program is lowered for a TPU at widths that tile its vector
  unit (``ops/pallas_mamba_step.py``: the named layer's state read once
  and written once, in place in the carry's array, the update and the
  read-out against ``C`` while it is in VMEM), and the plain
  ``mamba_step`` through a dynamic-update-slice everywhere else;
  nothing but the lowering platform and the state's shape chooses.
* ``T > 1`` — the sequence form, the teacher-forced pass from the EMPTY
  carry (``replays_from_empty_carry``): ``chunk_state_space_scan`` in
  chunks of ``mamba_chunk_size`` and causal attention over the whole
  sequence. ``carry`` and ``resets`` are not read.

Parameters are float32. Matrix products of a weight, and the
attention's two products, run in ``dtype`` with float32 accumulation
(``moe.mm``); the norms, the convolution, ``Delta``, the decays and
their cumulative sums, the state, every chunk product of the scan
(``Precision.HIGHEST``), the attention's softmax and both heads'
outputs are float32 whatever ``dtype`` says.

Initialisation (the config gives none; the configuration file lists it
under ``assumed``): matrices normal(0.02); norm weights and ``D`` 1; the
convolution's weight and bias uniform in (-1/2, 1/2), the framework's
default for a depthwise kernel of 4 taps, which loader and the Mamba-2
reference code leave in place; ``A_log = log U(1, 16)`` and ``dt_bias =
softplus^-1(Delta_0)``, ``Delta_0`` log-uniform in [0.001, 0.1], the
Mamba-2 reference code's. The counter ``mamba_state_retention`` (the
mean of ``exp(Delta A)`` over steps, heads and Mamba layers) says on
every run how much of a state a step keeps.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.models import moe
from actor_critic_algs_on_tensorflow_tpu.models.moe import mm as _mm
from actor_critic_algs_on_tensorflow_tpu.models.sdar import (
    _attend,
    rms_norm,
)
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_INIT_STD = 0.02  # every matrix, normal (assumed; the config gives none)
MAMBA, ATTENTION = "mamba", "attention"
# The counter of both forms, one row a call: the mean of exp(Delta A)
# over the call's tokens, heads and Mamba layers.
STATE_RETENTION = "mamba_state_retention"
_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published keys of ``config.json`` under their published
    names (defaults: the Micro widths) and what this chip holds of
    them: ``num_hidden_layers``, ``layer_types`` and ``vocab_size`` are
    the held share; no other default differs from the source."""

    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12
    hidden_act: str = "silu"
    hidden_size: int = 2048
    intermediate_size: int = 8192  # read by no layer: the MLP is "shared"
    layer_types: Tuple[str, ...] = _PERIOD * 4
    logits_scaling: float = 8
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_n_heads: int = 64
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 131072
    model_type: str = "granitemoehybrid"
    normalization_function: str = "rmsnorm"
    num_attention_heads: int = 32
    num_experts_per_tok: int = 0
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    num_local_experts: int = 0
    position_embedding_type: str = "nope"
    residual_multiplier: float = 0.22
    rms_norm_eps: float = 1e-5
    rope_scaling: Optional[Any] = None
    rope_theta: float = 10000
    shared_intermediate_size: int = 8192
    tie_word_embeddings: bool = True
    vocab_size: int = 100352

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        built = {
            "attention_bias": False, "hidden_act": "silu",
            "mamba_conv_bias": True, "mamba_n_groups": 1,
            "mamba_proj_bias": False, "normalization_function": "rmsnorm",
            "num_experts_per_tok": 0, "num_local_experts": 0,
            "position_embedding_type": "nope", "rope_scaling": None,
            "tie_word_embeddings": True,
        }
        for key, value in built.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not built "
                    f"(models/granite_hybrid.py has {value!r} only)"
                )
        if len(self.layer_types) != self.num_hidden_layers or not (
            set(self.layer_types) <= {MAMBA, ATTENTION}
        ):
            raise ValueError(
                f"layer_types must name num_hidden_layers="
                f"{self.num_hidden_layers} layers, each {MAMBA!r} or "
                f"{ATTENTION!r}; got {self.layer_types!r}"
            )
        if self.mamba_n_heads * self.mamba_d_head != self.inner_size:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must be mamba_expand * "
                "hidden_size"
            )

    @property
    def inner_size(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_channels(self) -> int:
        return self.inner_size + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


# ---- parameters --------------------------------------------------------


def _uniform(low, high):
    def init(key, shape, dtype=_F32):
        return jax.random.uniform(key, shape, dtype, low, high)

    return init


def _a_log_init(key, shape, dtype=_F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=_F32):
    """``softplus^-1(Delta_0)``, ``Delta_0`` log-uniform in [1e-3, 1e-1]."""
    low, high = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, low, high))
    return dt + jnp.log(-jnp.expm1(-dt))


def layer_param_spec(cfg: GraniteHybridConfig, layer: int):
    """``{name: (shape, init)}`` of one decoder layer."""
    H, w = cfg.hidden_size, nn.initializers.normal(_INIT_STD)
    ones = nn.initializers.ones_init()
    I = cfg.shared_intermediate_size
    spec = {"input_norm": ((H,), ones), "post_norm": ((H,), ones)}
    if cfg.layer_types[layer] == ATTENTION:
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        spec.update(
            q_proj=((H, nh * hd), w), k_proj=((H, nkv * hd), w),
            v_proj=((H, nkv * hd), w), o_proj=((nh * hd, H), w),
        )
    else:
        d, C, h = cfg.inner_size, cfg.conv_channels, cfg.mamba_n_heads
        spec.update(
            in_proj=((H, d + C + h), w),
            conv=((cfg.mamba_d_conv, C), _uniform(-0.5, 0.5)),
            conv_bias=((C,), _uniform(-0.5, 0.5)),
            dt_bias=((h,), _dt_bias_init), A_log=((h,), _a_log_init),
            D=((h,), ones), mamba_norm=((d,), ones),
            out_proj=((d, H), w),
        )
    spec.update(
        mlp_gate=((H, I), w), mlp_up=((H, I), w), mlp_down=((I, H), w),
    )
    return spec


# ---- the state-space recurrence ------------------------------------------


def mamba_step(S, x, dt, a, B, C):
    """One step of the recurrence on ``S [b, h, p, n]``: ``S <- a S +
    dt x (x) B; y = S C``, with ``x [b, h, p]``, ``dt`` and the decay
    ``a = exp(dt A) [b, h]`` (the caller folds a reset into it), ``B, C
    [b, n]``. Float32 on the vector unit, no matrix product. The plain
    form: XLA makes it one multiply-add pass over the state and one
    reduction over what that wrote (``ops/pallas_mamba_step.py`` is the
    same step with the state held in VMEM for both). Returns ``(S, y
    [b, h, p])``."""
    S = S * a[..., None, None] + (
        (dt[..., None] * x)[..., None] * B[:, None, None, :]
    )
    return S, jnp.sum(S * C[:, None, None, :], -1)


def chunk_state_space_scan(x, dt, A, B, C, chunk: int):
    """The chunked form of the state-space recurrence (SSD), float32.

    ``x [b, T, h, p]``, ``dt [b, T, h]`` (after the softplus), ``A [h]``
    (< 0), ``B, C [b, T, n]``. Equals, from an empty state, the
    recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t
    C_t`` (``mamba_step``). With ``c`` the cumulative sum of ``dt A``
    inside a chunk of ``Q`` steps: inside, ``y_t = sum_{s <= t} exp(c_t
    - c_s) (C_t . B_s) dt_s x_s`` (``C B^T`` is one ``[Q, Q]`` matrix a
    chunk for all heads); the chunk's own state ``Z = sum_s exp(c_Q -
    c_s) dt_s x_s (x) B_s``; over chunks ``S_k = exp(c_Q) S_{k-1} +
    Z_k`` from ``S_0 = 0``; and ``y_t += exp(c_t) S_{k-1} C_t``. Every
    exponent is <= 0. Returns ``(y [b, T, h, p], S [b, h, p, n])``. A
    length that is no multiple of the chunk is padded with steps that
    change nothing (``dt = 0``)."""
    b, T, h, p = x.shape
    pad = -T % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B, C = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (B, C))
    k = (T + pad) // chunk

    def chunks(v):  # [b, k * Q, ...] -> [b, k, Q, ...]
        return v.reshape((b, k, chunk) + v.shape[2:])

    def dot(spec, u, v):
        return jnp.einsum(spec, u, v, precision=_HIGHEST)

    x, dt, B, C = map(chunks, (x, dt, B, C))
    c = jnp.cumsum(dt * A, axis=2)                         # [b, k, Q, h]
    c = jnp.moveaxis(c, 3, 2)                              # [b, k, h, Q]
    dx = jnp.moveaxis(dt[..., None] * x, 3, 2)             # [b, k, h, Q, p]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # inside a chunk: the masked, decay-weighted product
    diff = c[..., :, None] - c[..., None, :]               # [b, k, h, Q, Q]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    scores = dot("bkin,bkjn->bkij", C, B)[:, :, None] * decay
    y = dot("bkhij,bkhjp->bkhip", scores, dx)
    # the chunk's own state, and the recurrence over chunks
    to_end = jnp.exp(c[..., -1:] - c)                      # [b, k, h, Q]
    Z = dot("bkhjp,bkjn->bkhpn", dx * to_end[..., None], B)
    whole = jnp.exp(c[..., -1])                            # [b, k, h]

    def step(S, xs):
        Z_k, whole_k = xs
        return S * whole_k[..., None, None] + Z_k, S

    S, before = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1]), _F32),
        (jnp.moveaxis(Z, 1, 0), jnp.moveaxis(whole, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)                    # [b, k, h, p, n]
    y = y + dot("bkin,bkhpn->bkhip", C, before) * jnp.exp(c)[..., None]
    y = jnp.moveaxis(y, 2, 3).reshape(b, k * chunk, h, p)
    return y[:, :T], S


# ---- the Mamba-2 mixer ---------------------------------------------------


def _mamba_inputs(p, x, cfg, dtype):
    """``x [..., H]`` -> the gate ``z [..., d]``, the convolution's
    input ``xBC [..., d + 2 n]`` and ``dt [..., h]`` before its bias
    and softplus, float32."""
    d, C = cfg.inner_size, cfg.conv_channels
    with jax.named_scope(profiling.MIXER_PROJ):
        zxbcdt = _mm(x, p["in_proj"], dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        return zxbcdt[..., :d], zxbcdt[..., d:d + C], zxbcdt[..., d + C:]


def _mamba_heads(p, xBC, dt, cfg):
    """The convolved channels and ``dt`` as the recurrence reads them:
    ``x [..., h, p]``, ``B, C [..., n]``, ``Delta [..., h]`` and ``A
    [h]``. (Under ``mixer_pointwise``, opened by the caller.)"""
    d, n = cfg.inner_size, cfg.mamba_d_state
    x = xBC[..., :d].reshape(
        xBC.shape[:-1] + (cfg.mamba_n_heads, cfg.mamba_d_head)
    )
    B, C = xBC[..., d:d + n], xBC[..., d + n:]
    return (x, B, C, jax.nn.softplus(dt + p["dt_bias"]),
            -jnp.exp(p["A_log"]))


def _mamba_output(p, y, x, z, cfg, dtype):
    """``y + D x``, the gate, the norm over the whole inner width (the
    gate first) and the output projection; ``y, x [..., h, p]``, ``z
    [..., d]``."""
    with jax.named_scope(profiling.MIXER_POINTWISE):
        y = y + p["D"][:, None] * x
        g = y.reshape(z.shape) * jax.nn.silu(z)
        g = rms_norm(g, p["mamba_norm"], cfg.rms_norm_eps)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(g, p["out_proj"], dtype)


def mamba_seq(p, x, cfg, dtype):
    """``x [b, T, H]`` from an empty state and convolution history ->
    ``([b, T, H], the mean decay)``."""
    T, K = x.shape[1], cfg.mamba_d_conv
    z, xBC, dt = _mamba_inputs(p, x, cfg, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
        xBC = jax.nn.silu(p["conv_bias"] + sum(
            padded[:, j: j + T] * p["conv"][j] for j in range(K)
        ))
        xs, B, C, dt, A = _mamba_heads(p, xBC, dt, cfg)
        retention = jnp.mean(jnp.exp(dt * A))
    with jax.named_scope(profiling.MIXER_CORE), jax.named_scope(
        profiling.MAMBA_CHUNK_SCAN
    ):
        y, _ = chunk_state_space_scan(
            xs, dt, A, B, C, min(cfg.mamba_chunk_size, T)
        )
    return _mamba_output(p, y, xs, z, cfg, dtype), retention


def _state_step(state, layer, x, dt, a, B, C):
    """``mamba_step`` on Mamba layer ``layer`` (a Python int) of ``state
    [B, layers, h, p, n]``, written back where it stood -> ``(state, y
    [B, h, p])``. Lowered for a TPU, at widths that tile its vector
    unit, the one-pass kernel that holds the layer's state in VMEM for
    the update and the read-out and writes it to the buffer it came
    from; anywhere else the plain form through a dynamic-update-slice."""
    # Imported where it is used: Pallas is ~1.5 s of imports, and
    # cli/train.py's PRESETS import this module for every preset.
    from actor_critic_algs_on_tensorflow_tpu.ops import pallas_mamba_step

    def plain(state, x, dt, a, B, C):
        S, y = mamba_step(state[:, layer], x, dt, a, B, C)
        return state.at[:, layer].set(S), y

    if not pallas_mamba_step.fits(state):
        return plain(state, x, dt, a, B, C)

    def kernel(state, x, dt, a, B, C):
        return pallas_mamba_step.mamba_step(state, layer, x, dt, a, B, C)

    return jax.lax.platform_dependent(
        state, x, dt, a, B, C, tpu=kernel, default=plain
    )


def mamba_mixer_step(p, x, state, tails, layer, keep, cfg, dtype):
    """``x [B, H]``; ``state [B, layers, h, p, n]`` and ``tails [B,
    layers, K - 1, d + 2 n]`` of which this is Mamba layer ``layer`` (a
    Python int), both updated in place; ``keep [B]``, 0 where the env
    starts over: its state and convolution history count as empty (the
    state's reset is folded into the decay, so it costs no pass: what
    ``_state_step`` runs, the kernel or the plain form, sees ``a *
    keep``)."""
    z, xBC, dt = _mamba_inputs(p, x, cfg, dtype)
    with jax.named_scope(profiling.MIXER_POINTWISE):
        window = jnp.concatenate(
            [tails[:, layer] * keep[:, None, None], xBC[:, None]], 1
        )
        tails = tails.at[:, layer].set(window[:, 1:])
        xBC = jax.nn.silu(p["conv_bias"] + jnp.sum(window * p["conv"], 1))
        xs, B, C, dt, A = _mamba_heads(p, xBC, dt, cfg)
        a = jnp.exp(dt * A)
        retention = jnp.mean(a)
    with jax.named_scope(profiling.MIXER_CORE), jax.named_scope(
        profiling.MAMBA_STATE
    ):
        state, y = _state_step(
            state, layer, xs, dt, a * keep[:, None], B, C
        )
    return _mamba_output(p, y, xs, z, cfg, dtype), state, tails, retention


# ---- grouped-query attention without positions ---------------------------


def _project(p, x, cfg, dtype):
    """``x [b, t, H]`` -> query ``[b, t, nh, hd]``, key and value ``[b,
    t, nkv, hd]``, float32. ``sdar._attend`` scales its scores by
    ``head_dim ** -0.5``: the query carries what is left of
    ``attention_multiplier`` (a power of two at the published widths,
    so the rounding to ``dtype`` is the same as without it)."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    with jax.named_scope(profiling.MIXER_PROJ):
        q, k, v = (
            _mm(x, p[w], dtype) for w in ("q_proj", "k_proj", "v_proj")
        )
    with jax.named_scope(profiling.MIXER_POINTWISE):
        q = q.reshape(x.shape[:-1] + (nh, hd)) * (
            cfg.attention_multiplier * hd ** 0.5
        )
        k = k.reshape(x.shape[:-1] + (nkv, hd))
        v = v.reshape(x.shape[:-1] + (nkv, hd))
    return q, k, v


def gqa_seq(p, x, cfg, dtype):
    """``x [b, T, H]``, causal over the sequence."""
    b, T, _ = x.shape
    q, k, v = _project(p, x, cfg, dtype)
    with jax.named_scope(profiling.MIXER_CORE):
        visible = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (b, T, T))
        out = _attend(q, k, v, visible, dtype)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out, p["o_proj"], dtype)


def gqa_step(p, x, keys, values, layer, pos, cfg, dtype):
    """One token an env against the cache: ``x [B, H]``, ``keys`` and
    ``values [B, layers, L, nkv, hd]`` of which this is attention layer
    ``layer`` (a Python int), ``pos [B]``. The new key and value are
    written at ``pos``, in place; rows beyond it are masked."""
    B = x.shape[0]
    q, k, v = _project(p, x[:, None], cfg, dtype)
    with jax.named_scope(profiling.MIXER_CORE):
        at = (jnp.arange(B), layer, pos)
        keys = keys.at[at].set(
            k[:, 0].astype(keys.dtype), unique_indices=True
        )
        values = values.at[at].set(
            v[:, 0].astype(values.dtype), unique_indices=True
        )
        visible = (jnp.arange(keys.shape[2])[None, :] <= pos[:, None])
        out = _attend(
            q, keys[:, layer], values[:, layer], visible[:, None], dtype
        )
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out[:, 0], p["o_proj"], dtype), keys, values


# ---- the model -----------------------------------------------------------


def _feed_forward(p, x, cfg, dtype):
    with jax.named_scope(profiling.DENSE_MLP):
        h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        y = moe.swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], dtype)
        return x + cfg.residual_multiplier * y


def _decoder_layer_seq(p, x, cfg, dtype, kind: str):
    """``x [b, T, H]`` -> ``(x, the layer's mean decay or None)``."""
    retention = None
    with jax.named_scope(profiling.MAMBA if kind == MAMBA else profiling.GQA):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        if kind == MAMBA:
            y, retention = mamba_seq(p, h, cfg, dtype)
        else:
            y = gqa_seq(p, h, cfg, dtype)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + cfg.residual_multiplier * y
    return _feed_forward(p, x, cfg, dtype), retention


def _decoder_layer_step(p, x, carry, index, keep, cfg, dtype, kind: str):
    """``x [B, H]`` through layer ``index`` of its kind (a Python int)
    -> ``(x, carry, the layer's mean decay or None)``; ``carry["pos"]``
    is this step's position."""
    retention = None
    with jax.named_scope(profiling.MAMBA if kind == MAMBA else profiling.GQA):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        if kind == MAMBA:
            y, state, tails, retention = mamba_mixer_step(
                p, h, carry["state"], carry["conv"], index, keep, cfg, dtype
            )
            carry = dict(carry, state=state, conv=tails)
        else:
            y, keys, values = gqa_step(
                p, h, carry["k"], carry["v"], index, carry["pos"], cfg, dtype
            )
            carry = dict(carry, k=keys, v=values)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + cfg.residual_multiplier * y
    return _feed_forward(p, x, cfg, dtype), carry, retention


def iteration_stats(rollout_stats, update_stats, axis_name):
    """This core's counter of one training iteration, replicated over
    ``axis_name``: the share of a Mamba-2 state that a step keeps, as
    the rollout's steps saw it."""
    del update_stats
    return {STATE_RETENTION: jax.lax.pmean(
        jnp.mean(rollout_stats[STATE_RETENTION]), axis_name
    )}


class GraniteHybridActorCritic(nn.Module):
    """The policy over ``cfg.vocab_size`` tokens and the value."""

    cfg: GraniteHybridConfig
    cache_len: int
    dtype: Any = jnp.float32
    # The sequence form reads neither carry nor resets (see above).
    replays_from_empty_carry = True
    # (rollout rows, update rows, axis) -> an iteration's counters
    iteration_stats = staticmethod(iteration_stats)

    @nn.compact
    def __call__(self, tokens, resets, carry):
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        H, w = cfg.hidden_size, nn.initializers.normal(_INIT_STD)
        embedding = self.param("embedding", w, (cfg.vocab_size, H), _F32)
        layers = [
            moe.Params(tuple(layer_param_spec(cfg, i).items()),
                       name=f"layer_{i}")()
            for i in range(cfg.num_hidden_layers)
        ]
        final_norm = self.param(
            "final_norm", nn.initializers.ones_init(), (H,), _F32
        )
        value_w = self.param("value_w", w, (H,), _F32)
        value_b = self.param(
            "value_b", nn.initializers.zeros_init(), (), _F32
        )

        T = tokens.shape[0]
        x = cfg.embedding_multiplier * jnp.take(
            embedding, tokens.astype(jnp.int32), axis=0
        )
        retention = []
        if T == 1:
            keep = 1.0 - resets[0].astype(_F32)
            pos = (carry["pos"] * keep).astype(jnp.int32)
            x = x[0]
            carry = dict(carry, pos=pos)
            seen = {MAMBA: 0, ATTENTION: 0}  # layers of each kind so far
            for p, kind in zip(layers, cfg.layer_types):
                x, carry, kept = _decoder_layer_step(
                    p, x, carry, seen[kind], keep, cfg, dtype, kind
                )
                seen[kind] += 1
                if kept is not None:
                    retention.append(kept)
            x = x[None]
            carry = dict(carry, pos=pos + 1)
        else:
            x = jnp.swapaxes(x, 0, 1)                       # [b, T, H]
            for p, kind in zip(layers, cfg.layer_types):
                # Each layer is recomputed in the backward pass, as in
                # the other cores: kept, the activations of a
                # minibatch's tokens do not fit beside the weights,
                # their gradients and Adam's moments.
                layer = jax.checkpoint(
                    lambda p, x, kind=kind: _decoder_layer_seq(
                        p, x, cfg, dtype, kind
                    )
                )
                x, kept = layer(p, x)
                if kept is not None:
                    retention.append(kept)
            x = jnp.swapaxes(x, 0, 1)
        with jax.named_scope(profiling.LM_HEAD):
            h = rms_norm(x, final_norm, cfg.rms_norm_eps)
            logits = _mm(h, embedding.T, dtype) / cfg.logits_scaling
        values = jnp.dot(h, value_w, precision=_HIGHEST) + value_b
        stats = {STATE_RETENTION: (
            jnp.mean(jnp.stack(retention)) if retention
            else jnp.ones((), _F32)
        )}
        return logits, values, carry, stats

    def initialize_carry(self, batch: int) -> Dict[str, Any]:
        """The empty carry for ``batch`` environments."""
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        n_mamba, n_attn = cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)
        cache = (batch, n_attn, self.cache_len, cfg.num_key_value_heads,
                 cfg.head_dim)
        return {
            "state": jnp.zeros(
                (batch, n_mamba, cfg.mamba_n_heads, cfg.mamba_d_head,
                 cfg.mamba_d_state), _F32,
            ),
            "conv": jnp.zeros(
                (batch, n_mamba, cfg.mamba_d_conv - 1, cfg.conv_channels),
                _F32,
            ),
            "k": jnp.zeros(cache, dtype),
            "v": jnp.zeros(cache, dtype),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
