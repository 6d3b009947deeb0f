"""SDAR-30B-A3B-Chat as a sequence-policy core: a language model that
generates by diffusion over blocks. Grouped-query softmax attention as
the mixer, a routed mixture of SwiGLU experts (no shared expert) as
every layer's MLP, a language-model head as the policy and a linear
value head.

Source of the layer equations: the published ``config.json`` of
JetLM/SDAR-30B-A3B-Chat (``model_type: sdar_moe``) — the ``qwen3_moe``
decoder layer the family continues from (query and key RMSNorms over
the head dimensions, rotate-half rotary embedding over all of them, a
softmax router whose top-k is renormalised) — and, for generation and
its training mask, Arriola et al. 2025, "Block Diffusion",
arXiv:2503.09573 section 3 and TraceRL (arXiv:2509.06949), which trains
this family with a PPO-clipped objective over the tokens revealed at
each step of the sampling trajectory. Not here: a router auxiliary
loss (the loss is the trainer's), the diffusion pre-training loss and
its noise schedule (PPO's objective uses neither).

Generation by diffusion over blocks: the sequence grows a block of
``block_length`` positions at a time. A block starts as mask tokens;
each DENOISING pass runs the model over the whole block — attention
bidirectional inside the block, causal to the finished blocks through
their cached keys and values — and the sampler reveals the positions it
is most confident of (``ops/distributions.py::BlockReveal``); when no
mask is left one more pass over the finished block, the COMMIT pass,
writes its keys and values into the cache for good. So a step of this
core is a pass over a block, not a token: ``__call__(tokens [T, B,
block_length] int32, resets [T, B], carry)`` returns ``(logits [T, B,
block_length, V], values [T, B], carry, stats)``, one value a pass, and
a pass is a commit exactly where its block holds no mask token (the
model reads that off its input; nobody tells it). The rest of the
carry interface is ``models/qwen3_next.py``'s
(``algos/common.py::make_recurrent_policy_head``, which pairs the
logits with ``BlockReveal`` because ``reveals_blocks`` says so).

The carry is a key/value cache whose length does NOT advance with the
step: ONE array for all layers ``[B, layers, cache_len, 2 *
num_key_value_heads * head_dim]`` in the compute dtype (a token's keys,
then its values; the env axis leads, as the trainer shards every leaf of
a carry; one array because a cache a layer is staged whole through VMEM
around its scatter, PERF.md section 6, PR 32) and ``pos [B]``, the
tokens committed since the episode began. ``cache_len`` is the tokens
an episode commits. Two forms of one layer share the parameters:

* ``T == 1`` — the step form, one pass: the block's positions are
  ``pos .. pos + block_length - 1``; its keys and values are written
  into the cache there, in place, and each of its queries sees the rows
  ``j < pos + block_length``: every finished block and all of its own,
  no causal mask inside the block. Then ``pos`` advances by
  ``block_length`` on a commit pass and stays on a denoising pass, whose
  rows the next pass over the same block overwrites: only a commit's
  rows are ever seen by a later block. Where ``resets`` is set the
  position is zeroed before the pass.
* ``T > 1`` — the sequence form, the teacher-forced pass over a
  trajectory of ``T`` passes as ``T * block_length`` positions, every
  sequence from the EMPTY carry (``replays_from_empty_carry``): the
  position of ``(t, i)`` is ``block_length * (commits before t) + i``,
  and query ``(t, i)`` sees key ``(s, j)`` iff ``s == t``, or ``s < t``
  and pass ``s`` was a commit. That is the block-diffusion training mask
  (a noisy copy sees its own block and the clean earlier blocks, a clean
  block the clean blocks) with the rollout's passes as the copies, in
  time order. ``carry`` and ``resets`` are not read. The mask is two
  vectors (``trajectory_steps``: a row's pass, and whether that pass was
  a commit). Where the program is lowered for a TPU and the heads are
  whole lane tiles (the published 128), scores, mask, softmax and
  weighted sum are ``ops/pallas_block_attention.py``'s kernel pair,
  which rebuilds the mask a tile at a time and keeps the scores in VMEM,
  forward and backward; on the CPU, and at ``ppo-sdar-tiny``'s heads
  anywhere, they are the plain ``_attend`` over the dense ``[b, n, n]``
  mask (``_attend_seq`` chooses, by the lowering platform and the
  shapes alone; the log row's ``gqa_score_tiles_computed_share`` says
  which ran).

Rotary embedding: rotate-half over all ``head_dim`` dimensions
(``x * cos + [-x2, x1] * sin`` with the halves ``x1, x2`` paired),
``rope_theta`` 1e6, no scaling; positions are token positions, shared by
all passes over one block, not step indices.

Parameters are float32. Matrix products run in ``dtype`` with float32
accumulation, and the cache, an input of such products, is held in
``dtype``; the norms, the router (product, softmax, top-k, weights),
the attention's softmax and both heads' outputs are float32 whatever
``dtype`` says. RMSNorm is the plain ``w * x / rms(x)``, ``w`` from 1.

The expert layer is ``models/moe.py``'s, shared with the other cores,
under its softmax-top-k-renormalise ``route``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.models import moe
from actor_critic_algs_on_tensorflow_tpu.models.moe import mm as _mm
from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_INIT_STD = 0.02  # every matrix, normal (assumed; the config gives none)
# The step form's counter, one row a rollout pass: the tokens a pass
# committed, mean over the envs.
COMMITTED_TOKENS = "diffusion_committed_tokens"
# The sequence form's, one row an update block, read off the
# trajectory's observations: the positions its denoising passes
# revealed (masked in a pass and no longer in the next), those passes,
# and every position the pass computed logits for.
REVEALED_POSITIONS = "diffusion_revealed_positions"
DENOISE_PASSES = "diffusion_denoise_passes"
POSITIONS = "diffusion_positions"
# And the share of the sequence form's (tile of queries, chunk of keys)
# pairs whose scores were computed: 1 where the plain form ran.
SCORE_TILES_COMPUTED = "gqa_score_tiles_computed_share"


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    """The published keys of ``config.json`` (defaults: the 30B-A3B
    widths) and what this chip holds of them. ``num_hidden_layers``,
    ``vocab_size`` and ``experts_held`` are the held share; no other
    default differs from the source. ``block_length``,
    ``denoising_steps`` and ``mask_token_id`` are the sampler's (the
    family's published generation defaults; the config gives none)."""

    hidden_size: int = 2048
    intermediate_size: int = 6144  # read by no layer: none is dense
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    attention_bias: bool = False
    rope_theta: float = 1e6
    rope_scaling: Optional[Any] = None
    use_sliding_window: bool = False
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    vocab_size: int = 151936
    # The share of the expert layer held here.
    first_expert: int = 0
    experts_held: int = 128
    capacity_factor: float = 2.0
    # The sampler: a block, the denoising passes over it, the mask id.
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151935

    def __post_init__(self):
        built = {"use_sliding_window": False, "rope_scaling": None,
                 "mlp_only_layers": (), "decoder_sparse_step": 1,
                 "attention_bias": False}
        for key, value in built.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"SDARConfig.{key}={getattr(self, key)!r}: only "
                    f"{value!r} is built (the published value)"
                )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must share the key/value heads "
                             "evenly")
        if self.block_length % self.denoising_steps:
            raise ValueError(
                f"block_length={self.block_length} is not a whole number "
                f"of positions a denoising pass ({self.denoising_steps})"
            )
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id is not a row of the vocabulary")

    @property
    def expert_spec(self) -> moe.ExpertSpec:
        return moe.ExpertSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            first_expert=self.first_expert, experts_held=self.experts_held,
            capacity_factor=self.capacity_factor,
        )

    @property
    def reveal(self) -> int:
        """Positions a denoising pass reveals: the static schedule."""
        return self.block_length // self.denoising_steps

    @property
    def cache_width(self) -> int:
        return 2 * self.num_key_value_heads * self.head_dim


def layer_param_spec(cfg: SDARConfig):
    """``{name: (shape, init)}`` of one decoder layer."""
    H, w = cfg.hidden_size, nn.initializers.normal(_INIT_STD)
    ones = nn.initializers.ones_init()
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    I, E = cfg.moe_intermediate_size, cfg.experts_held
    return {
        "input_norm": ((H,), ones), "post_norm": ((H,), ones),
        "q_proj": ((H, nh * hd), w), "k_proj": ((H, nkv * hd), w),
        "v_proj": ((H, nkv * hd), w), "o_proj": ((nh * hd, H), w),
        "q_norm": ((hd,), ones), "k_norm": ((hd,), ones),
        "router": ((H, cfg.num_experts), w),
        "w_gate": ((E, H, I), w), "w_up": ((E, H, I), w),
        "w_down": ((E, I, H), w),
    }


# ---- pieces ------------------------------------------------------------


def rms_norm(x, w, eps):
    """RMSNorm, float32: ``x * rsqrt(mean(x^2) + eps) * w``."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, positions, cfg: SDARConfig):
    """Rotate-half rotary embedding over all of the head's dimensions;
    ``x [..., heads, head_dim]``, ``positions`` shaped like ``x``
    without its last two axes."""
    hd = cfg.head_dim
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, hd, 2, dtype=_F32) / hd)
    angles = positions.astype(_F32)[..., None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    half = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + half * sin


def is_commit(tokens, cfg: SDARConfig):
    """``tokens [..., block_length]`` -> whether each pass is a commit:
    its block holds no mask token."""
    return jnp.all(tokens != cfg.mask_token_id, axis=-1)


# ---- grouped-query attention ---------------------------------------------


def _project(p, x, positions, cfg, dtype):
    """``x [b, n, H]``, ``positions [b, n]`` -> query ``[b, n, nh, hd]``,
    key and value ``[b, n, nkv, hd]``; query and key normed and rotated,
    float32."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    with jax.named_scope(profiling.MIXER_PROJ):
        q, k, v = (
            _mm(x, p[w], dtype) for w in ("q_proj", "k_proj", "v_proj")
        )
    with jax.named_scope(profiling.MIXER_POINTWISE):
        q = q.reshape(x.shape[:-1] + (nh, hd))
        k = k.reshape(x.shape[:-1] + (nkv, hd))
        v = v.reshape(x.shape[:-1] + (nkv, hd))
        q = _rotary(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions, cfg)
        k = _rotary(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions, cfg)
    return q, k, v


def _dot(spec, x, y, dtype):
    """A product of attention (``einsum``) in ``dtype``, float32 sums."""
    return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                      preferred_element_type=_F32)


def _attend(q, k, v, visible, dtype):
    """``q [b, tq, nh, hd]``, ``k, v [b, tk, nkv, hd]``, ``visible [b,
    tq, tk]`` -> ``[b, tq, nh * hd]``; query head ``j`` reads key/value
    head ``j // (nh / nkv)``; softmax in float32."""
    b, tq, nh, hd = q.shape
    nkv = k.shape[2]
    q = q.reshape(b, tq, nkv, nh // nkv, hd)
    scores = _dot("bqkgd,bskd->bkgqs", q, k, dtype) * hd ** -0.5
    scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _dot("bkgqs,bskd->bqkgd", probs, v, dtype).reshape(b, tq, nh * hd)


def _attend_cached(q, cache, visible, cfg, dtype):
    """``q [b, n, nh, hd]`` over the rows of ``cache [b, L, 2 * nkv *
    hd]`` (a token's keys, then its values) that ``visible [b, L]``
    marks -> ``[b, n, nh * hd]``, as ``_attend`` would give it. A pair
    of products a key/value head over ITS ``hd`` columns of the rows,
    which are whole lane tiles at the published ``head_dim`` of 128:
    the TPU's compiler stages the layer's rows through VMEM once a pass
    and slices them there. (Reshaped into per-head keys and values for
    ``_attend`` it copies the layer's cache out and transposes both
    halves every pass, and with both products over the whole 1,024-wide
    row as one operand the query operand is as large as the cache: 4.89
    and 4.43 ms a pass of six layers against 2.57; PERF.md section 6,
    PR 33.)"""
    b, n, nh, hd = q.shape
    nkv = cfg.num_key_value_heads
    g, w = nh // nkv, nkv * hd
    q = q.reshape(b, n, nkv, g, hd)
    out = []
    for i in range(nkv):
        keys = cache[..., i * hd:(i + 1) * hd]
        values = cache[..., w + i * hd: w + (i + 1) * hd]
        scores = _dot(
            "bmd,bsd->bms", q[:, :, i].reshape(b, n * g, hd), keys, dtype
        ) * hd ** -0.5
        probs = jax.nn.softmax(
            jnp.where(visible[:, None], scores, -jnp.inf), axis=-1
        )
        out.append(
            _dot("bms,bsd->bmd", probs, values, dtype).reshape(b, n, g, hd)
        )
    return jnp.stack(out, axis=2).reshape(b, n, nh * hd)


def gqa_block_step(p, x, caches, layer, pos, cfg, dtype):
    """The step form, one pass: ``x [B, block_length, H]``, ``caches
    [B, layers, L, cache_width]`` of which this is layer ``layer`` (a
    Python int), ``pos [B]`` the tokens committed. The block's keys and
    values are written at ``pos ..``, and its queries attend over the
    rows below ``pos + block_length``: the finished blocks and the whole
    of their own."""
    B, n, _ = x.shape
    with jax.named_scope(profiling.MIXER_POINTWISE):
        positions = pos[:, None] + jnp.arange(n)
    q, k, v = _project(p, x, positions, cfg, dtype)

    with jax.named_scope(profiling.MIXER_CORE), jax.named_scope(
        profiling.GQA_BLOCK_STEP
    ):
        rows = jnp.concatenate(
            [k.reshape(B, n, -1), v.reshape(B, n, -1)], -1
        ).astype(caches.dtype)
        caches = caches.at[jnp.arange(B)[:, None], layer, positions].set(
            rows, indices_are_sorted=True, unique_indices=True
        )
        visible = jnp.arange(caches.shape[2])[None, :] < (pos + n)[:, None]
        out = _attend_cached(q, caches[:, layer], visible, cfg, dtype)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out, p["o_proj"], dtype), caches


def trajectory_steps(commit, block_length: int):
    """``commit [T, b]`` (bool) -> the positions ``[b, n]`` of the
    sequence form's ``n = T * block_length`` rows (a pass's positions
    follow the tokens committed before it) and the two vectors its mask
    is made of: ``step [n]``, a row's pass, and ``key_commit [b, n]``,
    whether that pass was a commit."""
    T, b = commit.shape
    committed = jnp.cumsum(commit.astype(jnp.int32), 0) - commit
    positions = (block_length * committed)[..., None] + jnp.arange(
        block_length
    )                                                   # [T, b, L]
    positions = jnp.swapaxes(positions, 0, 1).reshape(b, -1)
    step = jnp.repeat(jnp.arange(T), block_length)
    key_commit = jnp.repeat(commit.T, block_length, axis=1)
    return positions, step, key_commit


def _visible(step, key_commit):
    """The mask ``[b, n, n]``: a query sees its own pass's block and
    the blocks of earlier commit passes."""
    own = step[:, None] == step[None, :]
    earlier = step[None, :] < step[:, None]             # key before query
    return own | (earlier & key_commit[:, None, :])


def trajectory_mask(commit, block_length: int):
    """``commit [T, b]`` (bool) -> the positions ``[b, T *
    block_length]`` and the dense mask ``[b, n, n]`` of the sequence
    form."""
    positions, step, key_commit = trajectory_steps(commit, block_length)
    return positions, _visible(step, key_commit)


def _kernel_or_plain(q, k, v, kernel, plain, *operands):
    """``kernel(pallas_block_attention, *operands)`` where the program
    is lowered for a TPU and the heads take the kernels (``head_dim``
    whole lane tiles, ``n`` whole sublane tiles), ``plain(*operands)``
    anywhere else: nothing but the lowering platform and the shapes
    chooses."""
    # Imported where it is used: Pallas is ~1.5 s of imports, and
    # cli/train.py's PRESETS import this module for every preset.
    from actor_critic_algs_on_tensorflow_tpu.ops import (
        pallas_block_attention,
    )

    if not pallas_block_attention.fits(q, k, v):
        return plain(*operands)
    return jax.lax.platform_dependent(
        *operands, tpu=functools.partial(kernel, pallas_block_attention),
        default=plain,
    )


def _attend_seq(q, k, v, step, key_commit, dtype):
    """``_attend`` under the trajectory's mask, rounded to ``dtype``
    (the output projection's product rounds it there anyway): the
    kernels that keep the scores in VMEM
    (``ops/pallas_block_attention.py``), or the plain form over the
    dense mask."""

    def kernel(ops, q, k, v, key_commit):
        return ops.block_attention(q, k, v, step, key_commit, dtype)

    def plain(q, k, v, key_commit):
        out = _attend(q, k, v, _visible(step, key_commit), dtype)
        return out.astype(dtype)

    with jax.named_scope(profiling.MIXER_CORE), jax.named_scope(
        profiling.GQA_SEQ_ATTEND
    ):
        return _kernel_or_plain(q, k, v, kernel, plain, q, k, v, key_commit)


def _score_tiles_computed_share(q, k, v, step):
    """The share of the sequence form's (tile of queries, chunk of
    keys) pairs whose scores are computed, the same in every layer:
    what the kernels' skip rule visits, or every one."""

    def kernel(ops, step):
        return ops.score_tiles_computed_share(step)

    def plain(step):
        return jnp.ones((), _F32)

    return _kernel_or_plain(q, k, v, kernel, plain, step)


def gqa_seq(p, x, positions, step, key_commit, cfg, dtype):
    """The sequence form: ``x [b, n, H]`` over ``n = T * block_length``
    positions at ``positions [b, n]`` under the mask that ``step [n]``
    and ``key_commit [b, n]`` make."""
    q, k, v = _project(p, x, positions, cfg, dtype)
    out = _attend_seq(q, k, v, step, key_commit, dtype)
    with jax.named_scope(profiling.MIXER_PROJ):
        return _mm(out, p["o_proj"], dtype)


# ---- the expert block --------------------------------------------------


def routed_experts(p, x, cfg: SDARConfig, dtype, every_pair=False):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum (there is no shared expert). ``every_pair``: the
    dispatch buffer may hold every pair the ``N`` positions could send
    here (at most ``N * top_k`` rows) instead of ``capacity_factor``
    times the expected ones."""
    spec = cfg.expert_spec
    if every_pair:
        spec = dataclasses.replace(
            spec, capacity_factor=cfg.num_experts / cfg.experts_held
        )
    return moe.routed_experts(
        p, x, spec, dtype,
        functools.partial(
            moe.route_softmax_top_k, top_k=cfg.num_experts_per_tok,
            renormalise=cfg.norm_topk_prob,
        ),
    )


# ---- the model ---------------------------------------------------------


def _expert_layer(p, x, cfg, dtype, every_pair=False):
    """``x + E(N(x))`` on ``x [..., H]`` with the layer's counters."""
    with jax.named_scope(profiling.MOE):
        h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        y, stats = routed_experts(
            p, h.reshape(-1, x.shape[-1]), cfg, dtype, every_pair
        )
    return x + y.reshape(x.shape), stats


def _decoder_layer_seq(p, x, positions, step, key_commit, cfg, dtype):
    with jax.named_scope(profiling.GQA):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        y = gqa_seq(p, h, positions, step, key_commit, cfg, dtype)
        with jax.named_scope(profiling.MIXER_POINTWISE):
            x = x + y
    return _expert_layer(p, x, cfg, dtype)


def _decoder_layer_step(p, x, caches, layer, pos, cfg, dtype):
    with jax.named_scope(profiling.GQA):
        with jax.named_scope(profiling.MIXER_POINTWISE):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        y, caches = gqa_block_step(p, h, caches, layer, pos, cfg, dtype)
    # A pass's mask tokens route alike (one embedding; attention
    # outputs that are averages of the same values): where the whole
    # batch's blocks are masks, the held experts get none of the pairs
    # or several times the expected ones, by the seed (more than twice
    # in 1 seed of 10). So the most the buffer may hold is every pair;
    # the update's, over a trajectory's mix of positions, is
    # cfg.capacity_factor's. Run at all those rows whatever landed, the
    # buffer cost the chip 5.6 ms of every 9.05 ms pass for rows an
    # eighth full (PERF.md section 6, PR 36): moe.routed_experts runs
    # each call at the lowest rung of a short ladder of row counts that
    # holds the pairs it counted, and the top rung only where they need
    # it.
    x, stats = _expert_layer(p, x + y, cfg, dtype, every_pair=True)
    return x, caches, stats


def iteration_stats(rollout_stats, update_stats, axis_name):
    """The expert layer's counters of one training iteration, the
    sampler's (the passes the rollout ran for each token it committed,
    the positions a denoising pass revealed, the share of the positions
    the head computed whose logits the log-prob reads) and the share of
    the update's attention scores, by tile, that were computed."""
    stats = moe.iteration_moe_stats(rollout_stats, update_stats, axis_name)
    committed = jax.lax.pmean(
        jnp.mean(rollout_stats[COMMITTED_TOKENS]), axis_name
    )
    revealed, denoise, positions = (
        jax.lax.psum(jnp.sum(update_stats[k]), axis_name)
        for k in (REVEALED_POSITIONS, DENOISE_PASSES, POSITIONS)
    )
    stats.update(
        diffusion_passes_per_committed_token=1.0 / jnp.maximum(
            committed, 1e-9
        ),
        diffusion_revealed_per_denoise_pass=revealed / jnp.maximum(
            denoise, 1.0
        ),
        diffusion_scored_position_share=revealed / positions,
    )
    stats[SCORE_TILES_COMPUTED] = jax.lax.pmean(
        jnp.mean(update_stats[SCORE_TILES_COMPUTED]), axis_name
    )
    return stats


class SDARActorCritic(nn.Module):
    """The policy over blocks of ``cfg.block_length`` tokens of
    ``cfg.vocab_size`` and the value, one a pass."""

    cfg: SDARConfig
    cache_len: int
    dtype: Any = jnp.float32
    # The sequence form reads neither carry nor resets (see above).
    replays_from_empty_carry = True
    # An observation and an action are a block, and the head pairs the
    # logits with ops/distributions.py::BlockReveal.
    reveals_blocks = True
    # (rollout rows, update rows, axis) -> an iteration's counters
    iteration_stats = staticmethod(iteration_stats)

    @nn.compact
    def __call__(self, tokens, resets, carry):
        cfg, dtype = self.cfg, jnp.dtype(self.dtype)
        H, n = cfg.hidden_size, cfg.block_length
        w = nn.initializers.normal(_INIT_STD)
        embedding = self.param("embedding", w, (cfg.vocab_size, H), _F32)
        spec = tuple(layer_param_spec(cfg).items())
        layers = [moe.Params(spec, name=f"layer_{i}")()
                  for i in range(cfg.num_hidden_layers)]
        final_norm = self.param(
            "final_norm", nn.initializers.ones_init(), (H,), _F32
        )
        lm_head = self.param("lm_head", w, (H, cfg.vocab_size), _F32)
        value_w = self.param("value_w", w, (H,), _F32)
        value_b = self.param(
            "value_b", nn.initializers.zeros_init(), (), _F32
        )

        tokens = tokens.astype(jnp.int32)
        T, B = tokens.shape[:2]
        commit = is_commit(tokens, cfg)                       # [T, B]
        x = jnp.take(embedding, tokens, axis=0)               # [T, B, n, H]
        all_stats = []
        if T == 1:
            keep = 1.0 - resets[0].astype(_F32)
            pos = (carry["pos"] * keep).astype(jnp.int32)
            x, caches = x[0], carry["layers"]
            for i, p in enumerate(layers):
                x, caches, stats = _decoder_layer_step(
                    p, x, caches, i, pos, cfg, dtype
                )
                all_stats.append(stats)
            x = x[None]
            committed = n * commit[0].astype(jnp.int32)
            carry = {"layers": caches, "pos": pos + committed}
        else:
            positions, step, key_commit = trajectory_steps(commit, n)
            x = jnp.swapaxes(x, 0, 1).reshape(B, T * n, H)
            # Each layer is recomputed in the backward pass, as in the
            # other cores: kept, the activations of a minibatch's
            # positions do not fit beside the weights and Adam's
            # moments. One function for all layers, the mask's vectors
            # among its arguments: jax.checkpoint then traces it once.
            layer = jax.checkpoint(
                functools.partial(_decoder_layer_seq, cfg=cfg, dtype=dtype)
            )
            for p in layers:
                x, stats = layer(p, x, positions, step, key_commit)
                all_stats.append(stats)
            x = jnp.swapaxes(x.reshape(B, T, n, H), 0, 1)
        with jax.named_scope(profiling.LM_HEAD):
            h = rms_norm(x, final_norm, cfg.rms_norm_eps)
            logits = _mm(h, lm_head, dtype)
            # a mask is never an action
            logits = jnp.where(
                jnp.arange(cfg.vocab_size) == cfg.mask_token_id, -jnp.inf,
                logits,
            )
        values = jnp.dot(
            jnp.mean(h, axis=-2), value_w, precision=_HIGHEST
        ) + value_b
        stats = moe.stack_layer_stats(all_stats)
        if T == 1:
            stats[COMMITTED_TOKENS] = jnp.mean(committed.astype(_F32))
        else:
            masked = jnp.sum(tokens == cfg.mask_token_id, -1)  # [T, B]
            after = jnp.concatenate([masked[1:], jnp.zeros_like(masked[:1])])
            stats[REVEALED_POSITIONS] = jnp.sum(
                jnp.where(commit, 0, masked - after)
            ).astype(_F32)
            stats[DENOISE_PASSES] = jnp.sum(~commit).astype(_F32)
            stats[POSITIONS] = jnp.asarray(tokens.size, _F32)
            heads = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.num_key_value_heads)
            stats[SCORE_TILES_COMPUTED] = _score_tiles_computed_share(
                *(jax.ShapeDtypeStruct((B, T * n, h, cfg.head_dim), _F32)
                  for h in heads), step,
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
