"""Matrix products of the Granite-4.0-H-Micro policy, from its shapes
alone.

Source of the shapes: the published ``config.json`` of
ibm-granite/granite-4.0-h-micro as the configuration file's ``model``
group holds it (``published``), cut to what this chip holds (``held``:
the layers of ``layer_types`` and the vocabulary rows). One row a
product and a token, summed over the layers that have it, counted as
the mathematics requires whatever implements it:

* projections (``mamba_in_proj``, ``mamba_out_proj``, ``gqa_q_proj``,
  ``gqa_kv_proj``, ``gqa_out_proj``), ``dense_mlp`` (every layer's
  SwiGLU, three ``hidden x shared_intermediate_size`` products) and the
  head (the tied embedding, with the value head's ``hidden``): ``in x
  out`` multiply-adds a token, weights read once a call;
* ``mamba_scan`` (no weights): the state-space recurrence in its
  chunked form at the published chunk ``Q``, a token and a layer —
  ``Q n / 2`` for ``C B^T`` (one matrix a chunk for all heads, causal
  inside the chunk so counted at half), ``h p Q / 2`` for the masked
  product, ``h p n`` for the chunk's own state and ``h p n`` for the
  state's read-out. Inputs and outputs are float32 (``x``, ``B``,
  ``C``, ``dt`` in; ``y`` out);
* ``gqa_scores_values`` (no weights): causal attention at the cell's
  ``T``, ``(T + 1) / 2`` keys a query on average, scores and values;
* ``mamba_state``: no multiply-adds, bytes only — the float32 state
  ``[h, p, n]`` read once and written once a token a layer (2 MiB each
  at the published widths). That is the rollout's step form; the
  chunked form keeps a state a chunk, so ``rules/scope_roofline.py``
  counts this row for the acting forward passes alone
  (``rollout_only``);
* ``gqa_cache``: no multiply-adds, bytes only — the key/value cache
  read once a step, ``(T + 1) / 2`` rows of ``2 x kv heads x head_dim``
  elements of the compute dtype a token on average, plus the one row
  written; ``rollout_only`` too.
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer


def layers(config: dict, runner) -> List[Layer]:
    m, held = config["model"]["published"], config["model"]["held"]
    H, T = m["hidden_size"], int(runner.cfg.rollout_length)
    kinds = held["layer_types"]
    n_layers, n_mamba = len(kinds), kinds.count("mamba")
    n_attn = n_layers - n_mamba
    d = m["mamba_expand"] * H
    h, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    Q = m["mamba_chunk_size"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = H // nh
    cache_bytes = 2 if runner.cfg.compute_dtype == "bfloat16" else 4

    def dense(name, count, fan_in, fan_out):
        return Layer(name, count * fan_in * fan_out, count * fan_in,
                     count * fan_out, count * fan_in * fan_out, 2, True)

    scan = Q * n // 2 + h * p * Q // 2 + 2 * h * p * n
    return [
        dense("mamba_in_proj", n_mamba, H, 2 * d + 2 * n + h),
        Layer("mamba_scan", n_mamba * scan, n_mamba * (d + 2 * n + h),
              n_mamba * d, 0, 4, True),
        Layer("mamba_state", 0, 2 * n_mamba * h * p * n, 0, 0, 4, True),
        dense("mamba_out_proj", n_mamba, d, H),
        dense("gqa_q_proj", n_attn, H, nh * hd),
        dense("gqa_kv_proj", n_attn, H, 2 * nkv * hd),
        Layer("gqa_scores_values", n_attn * nh * hd * (T + 1),
              n_attn * (nh + 2 * nkv) * hd, n_attn * nh * hd, 0, 2, True),
        Layer("gqa_cache", 0, n_attn * 2 * nkv * hd * (T + 1) // 2,
              n_attn * 2 * nkv * hd, 0, cache_bytes, True),
        dense("gqa_out_proj", n_attn, nh * hd, H),
        dense("dense_mlp", n_layers, H, 3 * m["shared_intermediate_size"]),
        dense("lm_head", 1, H, held["vocab_size"] + 1),
    ]
