"""Xing4.0-29B-A4B as a sequence-policy core: Kimi-VL's decoder layer
(``models/kimi_vl.py``: multi-head latent attention, a leading dense
SwiGLU, then sigmoid-routed experts with a shared one) with a low-rank
query and YaRN positions, every sublayer wrapped in manifold-constrained
hyper-connections (``models/hyper_connections.py``): the residual
stream is four float32 streams, mixed by a Sinkhorn-projected matrix.

Sources: the published ``config.json`` of XingChen-AGI/Xing4.0-29B-A4B
(``model_type: xing4_0``; the catalog row's ``config``); its residual
keys (``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min`` / ``_max``) are the parameters of Xie et al.
(DeepSeek-AI), arXiv:2512.24880, on Zhu et al., arXiv:2409.19606; the
rest is DeepSeek-V3's layer (arXiv:2412.19437, its loader's
``DeepseekV3Attention``, ``DeepseekV3YarnRotaryEmbedding``,
``MoEGate``). Not here: the multi-token-prediction module
(``num_nextn_predict_layers`` is carried and built by nothing: PPO's
objective has no next-but-one token to train it on, and it would take
2.5 GB of the chip), and the update of the routing bias (as in
Kimi-VL).

Layer ``l`` on the streams ``X [..., n C]`` (``n = hc_mult``, stream
``i`` in ``[i C, (i + 1) C)``):
``X = mHC(X, MLA . N_in)``, then ``X = mHC(X, F_l . N_post)``, ``F_l``
the dense SwiGLU for ``l < first_k_dense_replace`` and the expert block
after it. The embedding ``e`` is copied into every stream at the bottom
(``X_0 = [e; ...; e]``), the streams are summed at the top, and the
heads read ``N_f`` of that sum.

Attention is Kimi-VL's in both forms, and so is the carry: ``T == 1``
the absorbed step over ONE cache of latents ``[B, layers, cache_len,
kv_lora_rank + qk_rope_head_dim]`` (``ops/pallas_mla_step.py`` on a
TPU, with this model's softmax scale), ``T > 1`` the expanded causal
pass from an empty carry (``replays_from_empty_carry``). The query runs
through ``q_lora_rank`` with its own norm, the rotary angles are YaRN's
(``kimi_vl.yarn_inv_freq``) and the softmax scale is ``(d_nope +
d_rope)^(-1/2) mscale^2`` (``kimi_vl.yarn_mscale``); the published
``mscale`` and ``mscale_all_dim`` are equal, so cos and sin are scaled
by 1 (another pair is refused).

Precision: as Kimi-VL (matrix products in ``dtype`` with float32 sums;
norms, router, softmax and heads float32), and the streams, the mHC
coefficient product, the Sinkhorn iterations and both mixes float32
whatever ``dtype`` says.

Counters, over the rollout's steps (``iteration_stats``, ``train.py``'s
log row): ``mhc_res_offdiag_mass``, the mean over tokens and sublayers
of ``1 - tr(H_res) / n`` (0 at the identity), and
``mhc_sinkhorn_residual``, the largest ``|row or column sum - 1|`` of
any ``H_res``. ``B_res`` starts at normal(0, 1) (assumed), so the first
is far from 0 and the comparison with the reference tests the
projection; a loader's identity-like start would test nothing of it.
Beside them ``mhc_fused_share``: the share of the iteration's sublayer
calls, rollout and update, whose two passes over the streams ran as
``ops/pallas_hyper_connections.py``'s kernels (1 on a TPU at the
published widths, 0 where the plain form ran).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.models import decoder
from actor_critic_algs_on_tensorflow_tpu.models import hyper_connections as hc
from actor_critic_algs_on_tensorflow_tpu.models import kimi_vl as kv
from actor_critic_algs_on_tensorflow_tpu.models import moe
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

# The published YaRN parameters, as a tuple of items (a config is hashed).
_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 64),
         ("mscale", 1), ("mscale_all_dim", 1),
         ("original_max_position_embeddings", 4096), ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class XingConfig:
    """The published keys of ``config.json`` (defaults: the row's) and
    what this chip holds of them (``first_expert``, ``experts_held``,
    ``capacity_factor``); the preset sets the held share."""

    attention_bias: bool = False
    ep_size: int = 1
    first_k_dense_replace: int = 2
    hidden_act: str = "silu"
    hidden_size: int = 3584
    intermediate_size: int = 9216
    kv_lora_rank: int = 512
    max_position_embeddings: int = 262144
    model_type: str = "xing4_0"
    moe_intermediate_size: int = 1024
    moe_layer_freq: int = 1
    n_group: int = 1
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 4
    num_hidden_layers: int = 40
    num_key_value_heads: int = 32
    num_nextn_predict_layers: int = 1  # carried; the module is not built
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    q_lora_rank: Optional[int] = 768
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = _YARN
    routed_scaling_factor: float = 2.0
    scoring_func: str = "sigmoid"
    tie_word_embeddings: bool = False
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    v_head_dim: int = 128
    vocab_size: int = 131072
    # The share of the expert layer held here.
    first_expert: int = 0
    experts_held: int = 64
    capacity_factor: float = 2.0

    def __post_init__(self):
        built = {"attention_bias": False, "hidden_act": "silu",
                 "tie_word_embeddings": False, "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
                 "num_key_value_heads": self.num_attention_heads}
        decoder.refuse_unbuilt(self, built)
        if self.rope_scaling is not None:
            yarn = dict(self.rope_scaling)
            if yarn.get("type") != "yarn" or (
                yarn["mscale"] != yarn["mscale_all_dim"]
            ):
                raise ValueError(
                    f"XingConfig.rope_scaling={yarn!r}: only YaRN with "
                    "mscale == mscale_all_dim (cos and sin unscaled) is "
                    "built"
                )
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1:
            raise ValueError("hyper-connections need a stream and an "
                             "iteration")
        if not any(map(self.is_expert_layer, range(self.num_hidden_layers))):
            raise ValueError("no expert layer among the layers held")

    # The layer pattern, the expert layer's share and the cache row are
    # Kimi-VL's, read off the same keys.
    is_expert_layer = kv.KimiVLConfig.is_expert_layer
    expert_spec = kv.KimiVLConfig.expert_spec
    cache_width = kv.KimiVLConfig.cache_width


def layer_param_spec(cfg: XingConfig, layer: int):
    """``{name: (shape, init)}`` of one decoder layer: Kimi-VL's (the
    query through ``q_lora_rank``) and each sublayer's mHC
    coefficients (``attn_hc_*``, ``ffn_hc_*``)."""
    return {**kv.layer_param_spec(cfg, layer),
            **hc.param_spec(cfg, "attn_hc_"), **hc.param_spec(cfg, "ffn_hc_")}


def _decoder_layer_seq(p, X, cfg, dtype, expert: bool):
    """The streams ``X [T, b, n H]`` -> ``(X', expert counters, mHC
    counters)``."""
    def attention(h):
        with jax.named_scope(profiling.MLA):
            with jax.named_scope(profiling.MIXER_POINTWISE):
                h = decoder.rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
            return kv.mla_seq(p, h, cfg, dtype), None

    X, _, attn = hc.sublayer(p, "attn_hc_", X, attention, cfg)
    X, stats, ffn = hc.sublayer(
        p, "ffn_hc_", X,
        lambda h: kv.feed_forward_branch(p, h, cfg, dtype, expert), cfg,
    )
    return X, stats, (attn, ffn)


def _decoder_layer_step(p, X, caches, layer, pos, cfg, dtype, expert: bool):
    """The streams ``X [B, n H]`` -> ``(X', caches, expert counters, mHC
    counters)``: the step form through layer ``layer``'s cache."""
    def attention(h):
        with jax.named_scope(profiling.MLA):
            with jax.named_scope(profiling.MIXER_POINTWISE):
                h = decoder.rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
            return kv.mla_step(p, h, caches, layer, pos, cfg, dtype)

    X, caches, attn = hc.sublayer(p, "attn_hc_", X, attention, cfg)
    X, stats, ffn = hc.sublayer(
        p, "ffn_hc_", X,
        lambda h: kv.feed_forward_branch(p, h, cfg, dtype, expert), cfg,
    )
    return X, caches, stats, (attn, ffn)


def _mhc_stats(counters):
    """The sublayers' mHC counters of one forward call as one row."""
    return {
        hc.OFFDIAG_MASS: jnp.mean(jnp.stack(
            [c[hc.OFFDIAG_MASS] for c in counters]
        )),
        hc.SINKHORN_RESIDUAL: jnp.max(jnp.stack(
            [c[hc.SINKHORN_RESIDUAL] for c in counters]
        )),
        hc.FUSED_SHARE: jnp.mean(jnp.stack(
            [c[hc.FUSED_SHARE] for c in counters]
        )),
    }


def iteration_stats(rollout_stats, update_stats, axis_name):
    """Kimi-VL's counters of one training iteration and, of the
    rollout's steps, the mean off-diagonal mass of ``H_res`` and the
    largest distance of its row and column sums from 1; of the rollout's
    and the update's forward calls together, the share of sublayers
    whose passes over the streams ran as the kernels."""
    stats = kv.iteration_stats(rollout_stats, update_stats, axis_name)
    stats[hc.OFFDIAG_MASS] = jax.lax.pmean(
        jnp.mean(rollout_stats[hc.OFFDIAG_MASS]), axis_name
    )
    stats[hc.SINKHORN_RESIDUAL] = jax.lax.pmax(
        jnp.max(rollout_stats[hc.SINKHORN_RESIDUAL]), axis_name
    )
    calls = jnp.concatenate([rollout_stats[hc.FUSED_SHARE].ravel(),
                             update_stats[hc.FUSED_SHARE].ravel()])
    stats[hc.FUSED_SHARE] = jax.lax.pmean(jnp.mean(calls), axis_name)
    return stats


class XingActorCritic(nn.Module):
    """The policy over ``cfg.vocab_size`` tokens and the value."""

    cfg: XingConfig
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
            nn.initializers.ones_init(),
        )

        X = hc.expand(decoder.embed(head, tokens), cfg.hc_mult)
        expert_stats, mhc_stats = [], []
        if tokens.shape[0] == 1:
            _, pos = decoder.step_position(carry, resets)
            X, caches = X[0], carry["layers"]
            for i, p in enumerate(layers):
                X, caches, stats, counters = _decoder_layer_step(
                    p, X, caches, i, pos, cfg, dtype,
                    cfg.is_expert_layer(i),
                )
                expert_stats.append(stats)
                mhc_stats.extend(counters)
            X = X[None]
            carry = {"layers": caches, "pos": pos + 1}
        else:
            for i, p in enumerate(layers):
                # Each layer is recomputed in the backward pass, as in
                # the other cores: the checkpoint keeps the layer's
                # four-stream input, 235 MB at 4,096 tokens.
                layer = jax.checkpoint(
                    lambda p, X, e=cfg.is_expert_layer(i): (
                        _decoder_layer_seq(p, X, cfg, dtype, e)
                    )
                )
                X, stats, counters = layer(p, X)
                expert_stats.append(stats)
                mhc_stats.extend(counters)
        h, logits = decoder.lm_head(
            hc.reduce(X, cfg.hc_mult), head, cfg.rms_norm_eps, dtype
        )
        values = decoder.value(h, head)
        stats = moe.stack_layer_stats(
            [s for s in expert_stats if s is not None]
        )
        stats.update(_mhc_stats(mhc_stats))
        if tokens.shape[0] == 1:
            stats[kv.CACHE_ROWS_READ] = kv.rows_read_share(
                carry["layers"], pos, cfg.kv_lora_rank
            )
        return logits, values, carry, stats

    def initialize_carry(self, batch: int) -> Dict[str, Any]:
        """The empty carry for ``batch`` environments."""
        return decoder.one_cache_carry(batch, self.cfg, self.cache_len,
                                       self.dtype)
