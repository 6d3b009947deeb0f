"""Matrix products and the short convolutions' bytes of the
LFM2-24B-A2B policy, from its shapes alone.

Source of the shapes: the published ``config.json`` of
LiquidAI/LFM2-24B-A2B as the configuration file's ``model`` group holds
it (``published``), cut to what this chip holds (``held``: the layers
of ``layer_types``, the leading dense ones, the experts, the vocabulary
rows). One row a product and a token, summed over the layers that have
it, counted as the mathematics requires whatever implements it:

* projections (``shortconv_in_proj``: ``hidden x 3 hidden``;
  ``shortconv_out_proj``, ``gqa_q_proj``, ``gqa_kv_proj``,
  ``gqa_out_proj``), ``dense_mlp`` (three ``hidden x
  intermediate_size`` products in the leading dense layers), the router
  and the head (the tied embedding, with the value head's ``hidden``):
  ``in x out`` multiply-adds a token, weights read once a call;
* ``shortconv_pointwise`` (no weights): the two gates and the ``L``
  taps, ``L + 2`` multiply-adds a channel, a token and a layer; its
  input the in projection's three outputs, its output the gated one,
  float32 (4 bytes an element);
* ``shortconv_tails``: no multiply-adds, bytes only — a layer's tail,
  ``L - 1`` float32 values of ``u`` a channel, read once and written
  once a token. That is the rollout's step form; the sequence form reads
  the same values from its own input, so ``rules/scope_roofline.py``
  counts this row for the acting forward passes alone
  (``rollout_only``);
* ``gqa_scores_values`` (no weights): causal attention at the cell's
  ``T``, ``(T + 1) / 2`` keys a query on average, scores and values;
  ``gqa_cache``: bytes only, the key/value cache read once a step,
  ``(T + 1) / 2`` rows of ``2 x kv heads x head_dim`` elements of the
  compute dtype a token on average, plus the row written;
  ``rollout_only`` too;
* ``moe_routed``: three ``hidden x moe_intermediate_size`` products a
  (token, expert) pair at the pairs a token that landed on the held
  experts, COUNTED by the program in the measured window
  (``runner.moe_pairs_per_token``; the expected number, ``top_k x held
  / experts``, where no window has run); a call reads the weights of the
  experts that got a pair (``runner.moe_experts_touched_share``; all
  held experts where no window has run).
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer


def layers(config: dict, runner) -> List[Layer]:
    m, held = config["model"]["published"], config["model"]["held"]
    H, T = m["hidden_size"], int(runner.cfg.rollout_length)
    kinds = held["layer_types"]
    n_layers, n_conv = len(kinds), kinds.count("conv")
    n_attn = n_layers - n_conv
    n_dense = held["num_dense_layers"]
    n_moe = n_layers - n_dense
    L = m["conv_L_cache"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = H // nh
    I, E, k = m["moe_intermediate_size"], m["num_experts"], (
        m["num_experts_per_tok"]
    )
    n_held = held["experts_held"]
    pairs = getattr(runner, "moe_pairs_per_token", None)
    if pairs is None:
        pairs = k * n_held / E
    touched = getattr(runner, "moe_experts_touched_share", None)
    if touched is None:
        touched = 1.0
    cache_bytes = 2 if runner.cfg.compute_dtype == "bfloat16" else 4

    def dense(name, count, fan_in, fan_out):
        return Layer(name, count * fan_in * fan_out, count * fan_in,
                     count * fan_out, count * fan_in * fan_out, 2, True)

    return [
        dense("shortconv_in_proj", n_conv, H, 3 * H),
        Layer("shortconv_pointwise", n_conv * (L + 2) * H, n_conv * 3 * H,
              n_conv * H, 0, 4, True),
        Layer("shortconv_tails", 0, 2 * n_conv * (L - 1) * H, 0, 0, 4,
              True),
        dense("shortconv_out_proj", n_conv, H, H),
        dense("gqa_q_proj", n_attn, H, nh * hd),
        dense("gqa_kv_proj", n_attn, H, 2 * nkv * hd),
        Layer("gqa_scores_values", n_attn * nh * hd * (T + 1),
              n_attn * (nh + 2 * nkv) * hd, n_attn * nh * hd, 0, 2, True),
        Layer("gqa_cache", 0, n_attn * 2 * nkv * hd * (T + 1) // 2,
              n_attn * 2 * nkv * hd, 0, cache_bytes, True),
        dense("gqa_out_proj", n_attn, nh * hd, H),
        dense("dense_mlp", n_dense, H, 3 * m["intermediate_size"]),
        dense("moe_router", n_moe, H, E),
        Layer("moe_routed", int(round(n_moe * pairs * 3 * H * I)),
              int(round(n_moe * pairs * H)), int(round(n_moe * pairs * H)),
              int(round(n_moe * touched * n_held * 3 * H * I)), 2, True),
        dense("lm_head", 1, H, held["vocab_size"] + 1),
    ]
