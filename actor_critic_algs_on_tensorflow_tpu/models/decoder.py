"""The pieces the sequence cores build their decoders from, written once:
the parameter-group module, the matrix products, the SwiGLU, the
RMSNorm, the rotate-half rotary embedding, grouped-query softmax
attention (causal over a sequence, or one token against a cache), and
the actor-critic shell around a core's layers (the head
parameters, the step form's preamble, the policy and value heads).

Each core keeps what is its own: its mixers, its layer body, its layouts
and its carry. The shell is plain functions its ``__call__`` calls, in
this order::

    head, layers = decoder.parameters(self, cfg, specs, final_norm_init)
    x = decoder.embed(head, tokens)
    keep, pos = decoder.step_position(carry, resets)    # T == 1 only
    ... the layers ...
    h, logits = decoder.lm_head(x, head, cfg.rms_norm_eps, dtype)
    values = decoder.value(h, head)

Products of a weight run in ``dtype`` with float32 accumulation; norms,
softmax and the value are float32 whatever ``dtype`` says.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.utils import profiling

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02  # every matrix, normal (assumed; no config gives one)


def refuse_unbuilt(cfg, built):
    """Refuses a config whose key in ``built`` holds another value than
    the one the core builds (the published one)."""
    for key, value in built.items():
        if getattr(cfg, key) != value:
            raise ValueError(
                f"{type(cfg).__name__}.{key}={getattr(cfg, key)!r}: only "
                f"{value!r} is built (the published value)"
            )


class Params(nn.Module):
    """A named group of float32 parameters (a decoder layer's)."""

    spec: Any  # {name: (shape, init)}, hashable as a tuple of items

    @nn.compact
    def __call__(self):
        return {name: self.param(name, init, shape, F32)
                for name, (shape, init) in self.spec}


def mm(x, w, dtype):
    """A matrix product in ``dtype`` with float32 accumulation."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=F32)


def dot(spec, a, b, dtype):
    """A product of attention (``einsum``) in ``dtype``, float32 sums."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32)


def swiglu(x, w_gate, w_up, w_down, dtype):
    return mm(jax.nn.silu(mm(x, w_gate, dtype)) * mm(x, w_up, dtype),
              w_down, dtype)


def rms_norm(x, w, eps, zero_centred=False):
    """RMSNorm, float32: ``x * rsqrt(mean(x^2) + eps) * w``, or ``* (1 +
    w)`` where the weight is zero-centred (Qwen3-Next's)."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w if zero_centred else w
    )


def rotary(x, positions, theta, dims):
    """Rotate-half rotary embedding on the first ``dims`` of the head's
    dimensions (``x * cos + [-x2, x1] * sin``, the halves ``x1, x2`` of
    those dims paired; the rest pass unrotated); ``x [..., heads,
    head_dim]``, ``positions`` shaped like ``x`` without its last two
    axes."""
    inv_freq = theta ** (-jnp.arange(0, dims, 2, dtype=F32) / dims)
    angles = positions.astype(F32)[..., None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    whole = dims == x.shape[-1]
    x_rot, x_pass = (x, None) if whole else (x[..., :dims], x[..., dims:])
    half = jnp.concatenate(
        [-x_rot[..., dims // 2:], x_rot[..., : dims // 2]], -1
    )
    rotated = x_rot * cos + half * sin
    return rotated if whole else jnp.concatenate([rotated, x_pass], -1)


def attend(q, k, v, visible, dtype):
    """Grouped-query softmax attention: ``q [b, tq, nh, hd]``, ``k, v [b,
    tk, nkv, hd]``, ``visible [b, tq, tk]`` (True = seen) -> ``[b, tq,
    nkv, nh / nkv, hd]``, query head ``j`` reading key/value head ``j //
    (nh / nkv)``. The heads come out grouped: the caller lays them out
    for its next product in one reshape. Softmax in float32."""
    b, tq, nh, hd = q.shape
    nkv = k.shape[2]
    q = q.reshape(b, tq, nkv, nh // nkv, hd)
    scores = dot("bqkgd,bskd->bkgqs", q, k, dtype) * hd ** -0.5
    scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return dot("bkgqs,bskd->bqkgd", probs, v, dtype)


def causal_attend(q, k, v, dtype):
    """``attend`` over a whole sequence, causal: ``q [b, T, nh, hd]``,
    ``k, v [b, T, nkv, hd]`` -> ``[b, T, nh * hd]``."""
    b, T = q.shape[:2]
    visible = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (b, T, T))
    return attend(q, k, v, visible, dtype).reshape(b, T, -1)


def cached_attend(q, k, v, keys, values, layer, pos, dtype):
    """One token an env against a key/value cache: ``q [B, 1, nh,
    hd]``, ``k, v [B, 1, nkv, hd]`` written at ``pos [B]`` into ``keys``
    and ``values [B, layers, L, nkv, hd]``'s attention layer ``layer`` (a
    Python int), in place; rows beyond ``pos`` are masked. -> ``(out [B,
    1, nh * hd], keys, values)``."""
    B = q.shape[0]
    at = (jnp.arange(B), layer, pos)
    keys = keys.at[at].set(k[:, 0].astype(keys.dtype), unique_indices=True)
    values = values.at[at].set(
        v[:, 0].astype(values.dtype), unique_indices=True
    )
    visible = (jnp.arange(keys.shape[2])[None, :] <= pos[:, None])
    out = attend(
        q, keys[:, layer], values[:, layer], visible[:, None], dtype
    ).reshape(B, 1, -1)
    return out, keys, values


# ---- the actor-critic shell ------------------------------------------------


def parameters(module, cfg, layer_specs, final_norm_init, tied=False):
    """Declares, on ``module`` (inside its compact ``__call__``), the
    parameters of a decoder over ``cfg.vocab_size`` tokens of width
    ``cfg.hidden_size``, in this order, which numbers their random
    draws: ``embedding``, the layers ``layer_<i>`` (a ``Params`` of each
    ``{name: (shape, init)}`` of ``layer_specs``), ``final_norm`` (from
    ``final_norm_init``), ``lm_head`` (not where ``tied``: the logits
    then read the embedding), ``value_w`` and ``value_b``. Returns
    ``(head, layers)``, ``head`` the five (four) by name."""
    H, w = cfg.hidden_size, nn.initializers.normal(INIT_STD)
    head = {"embedding": module.param("embedding", w, (cfg.vocab_size, H),
                                      F32)}
    layers = [Params(tuple(spec.items()), name=f"layer_{i}")()
              for i, spec in enumerate(layer_specs)]
    head["final_norm"] = module.param("final_norm", final_norm_init, (H,),
                                      F32)
    if not tied:
        head["lm_head"] = module.param("lm_head", w, (H, cfg.vocab_size), F32)
    head["value_w"] = module.param("value_w", w, (H,), F32)
    head["value_b"] = module.param("value_b", nn.initializers.zeros_init(),
                                   (), F32)
    return head, layers


def embed(head, tokens):
    """``tokens [...]`` -> their rows of the embedding ``[..., H]``."""
    return jnp.take(head["embedding"], tokens.astype(jnp.int32), axis=0)


def step_position(carry, resets):
    """The step form's preamble: ``keep [B]``, 0 where the env starts
    over at this step (``resets [1, B]``), else 1, and this step's
    position, ``carry["pos"]`` zeroed there."""
    keep = 1.0 - resets[0].astype(F32)
    return keep, (carry["pos"] * keep).astype(jnp.int32)


def lm_head(x, head, eps, dtype, zero_centred=False):
    """The policy's head, under scope ``lm_head``: ``h = N_f(x)`` and the
    logits ``h W``, ``W`` the ``lm_head`` or, where the model ties it,
    the embedding's transpose. Returns ``(h, logits)``, both float32."""
    with jax.named_scope(profiling.LM_HEAD):
        h = rms_norm(x, head["final_norm"], eps, zero_centred)
        w = head["lm_head"] if "lm_head" in head else head["embedding"].T
        return h, mm(h, w, dtype)


def value(h, head):
    """The value head, float32: ``h . value_w + value_b``."""
    return jnp.dot(h, head["value_w"], precision=HIGHEST) + head["value_b"]


def one_cache_carry(batch, cfg, cache_len, dtype):
    """The empty carry of a core whose layers' caches are ONE array ``[B,
    layers, cache_len, cfg.cache_width]`` in ``dtype``, and ``pos [B]``."""
    shape = (batch, cfg.num_hidden_layers, cache_len, cfg.cache_width)
    return {"layers": jnp.zeros(shape, jnp.dtype(dtype)),
            "pos": jnp.zeros((batch,), jnp.int32)}
