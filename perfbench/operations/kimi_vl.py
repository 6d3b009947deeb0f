"""Matrix products of the Kimi-VL language-model policy, from its
shapes alone.

Source of the shapes: the published ``config.json`` of
moonshotai/Kimi-VL-A3B-Instruct (the language model's keys), as the
configuration file's ``model`` group holds it (``published``), cut to
what this chip holds (``held``: layers, experts, vocabulary rows). One
row a product and a token, summed over the layers that have it, counted
as the mathematics requires whatever implements it:

* projections (``mla_q_proj``, ``mla_kv_a_proj``, ``mla_out_proj``),
  ``dense_mlp``, router, shared experts, head: ``in x out`` multiply-adds
  a token, weights read once a call;
* ``mla_kv_b_proj``: ``kv_lora_rank x heads x (d_nope + d_v)``
  multiply-adds a token — carrying the latent up into keys and values in
  the expanded form; the absorbed form's two products (the query into
  the latent space, the weighted sum out of it) cost the same;
* ``mla_scores_values`` (no weights): causal attention at the published
  head sizes and the cell's ``T``, ``(T + 1) / 2`` keys a query on
  average, ``d_nope + d_rope`` for a score and ``d_v`` for a value (the
  absorbed form computes both over the 576-wide latent instead: more
  operations, none of them required);
* ``mla_cache``: no multiply-adds, bytes only — the cache of latents read
  once a step, ``(T + 1) / 2`` rows of ``kv_lora_rank + d_rope``
  elements of the compute dtype a token a layer on average, plus the one
  row written. That is the rollout's step form; ``rules/scope_roofline.py``
  counts this row for the acting forward passes alone (``rollout_only``);
* ``moe_routed``: three ``hidden x moe_intermediate_size`` products a
  (token, expert) pair at the pairs a token that landed on the held
  experts, COUNTED by the program in the measured window
  (``runner.moe_pairs_per_token``; the expected number, ``top_k x held /
  experts``, where no window has run); a call reads the weights of the
  experts that got a pair (``runner.moe_experts_touched_share``; all
  held experts where no window has run).
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer


def layers(config: dict, runner) -> List[Layer]:
    m, held = config["model"]["published"], config["model"]["held"]
    H, T = m["hidden_size"], int(runner.cfg.rollout_length)
    n_layers = held["num_hidden_layers"]
    n_moe = sum(
        i >= m["first_k_dense_replace"] and i % m["moe_layer_freq"] == 0
        for i in range(n_layers)
    )
    n_dense = n_layers - n_moe
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    I, Is = m["moe_intermediate_size"], (
        m["n_shared_experts"] * m["moe_intermediate_size"]
    )
    k, E, n_held = m["num_experts_per_tok"], m["n_routed_experts"], (
        held["experts_held"]
    )
    pairs = getattr(runner, "moe_pairs_per_token", None)
    if pairs is None:
        pairs = k * n_held / E
    touched = getattr(runner, "moe_experts_touched_share", None)
    if touched is None:
        touched = 1.0
    cache_bytes = 2 if runner.cfg.compute_dtype == "bfloat16" else 4

    def dense(name, n, fan_in, fan_out):
        return Layer(name, n * fan_in * fan_out, n * fan_in, n * fan_out,
                     n * fan_in * fan_out, 2, True)

    return [
        dense("mla_q_proj", n_layers, H, nh * (dn + dr)),
        dense("mla_kv_a_proj", n_layers, H, rank + dr),
        dense("mla_kv_b_proj", n_layers, rank, nh * (dn + dv)),
        Layer("mla_scores_values",
              n_layers * nh * (dn + dr + dv) * (T + 1) // 2,
              n_layers * (nh * (dn + dr) + rank + dr), n_layers * nh * dv,
              0, 2, True),
        Layer("mla_cache", 0, n_layers * (rank + dr) * (T + 1) // 2,
              n_layers * (rank + dr), 0, cache_bytes, True),
        dense("mla_out_proj", n_layers, nh * dv, H),
        dense("dense_mlp", n_dense, H, 3 * m["intermediate_size"]),
        dense("moe_router", n_moe, H, E),
        dense("moe_shared", n_moe, H, 3 * Is),
        Layer("moe_routed", int(round(n_moe * pairs * 3 * H * I)),
              int(round(n_moe * pairs * H)), int(round(n_moe * pairs * H)),
              int(round(n_moe * touched * n_held * 3 * H * I)), 2, True),
        dense("lm_head", 1, H, held["vocab_size"] + 1),
    ]
