"""Matrix products of the Qwen3-Next policy, from its shapes alone.

Source of the shapes: the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct, as the configuration file's ``model``
group holds it (``published``), cut to what this chip holds (``held``:
layers, experts, vocabulary rows). One row a product and a token, summed
over the layers that have it, counted as the mathematics requires
whatever implements it:

* projections, router, shared expert, head: ``in x out`` multiply-adds a
  token, weights read once a call;
* ``moe_routed``: three ``hidden x moe_intermediate_size`` products a
  (token, expert) pair at the pairs a token that landed on the held
  experts, COUNTED by the program in the measured window
  (``runner.moe_pairs_per_token``; the expected number,
  ``top_k x held / experts``, where no window has run); a call reads
  the weights of the experts that got a pair, and the program counts
  those too (``runner.moe_experts_touched_share``: the mean over the
  rollout's steps, which are 257 of an iteration's 261 forward calls;
  all held experts where no window has run);
* ``gdn_delta_rule`` (no weights): the delta rule in its chunked form at
  chunk ``c``, a value head and a token — ``3 d_k d_v`` for the state's
  three contractions and, causal inside the chunk so counted at half,
  ``c (2 d_k + d_v) / 2`` for the two ``c x c`` score products and their
  application, ``c (d_k + d_v) / 2`` for the triangular solve;
* ``attn_scores_values`` (no weights): causal attention at the cell's
  ``T``, ``(T + 1) / 2`` keys a query on average, scores and values;
* ``gdn_state``: no multiply-adds, bytes only — the float32 state read
  once and written once a token, so two elements of 4 B moved an
  element of state. That is the rollout's step form; the
  chunked form keeps it a chunk, so ``rules/scope_roofline.py`` counts
  this row for the acting forward passes alone (``rollout_only``).
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer


def layers(config: dict, runner) -> List[Layer]:
    m, held = config["model"]["published"], config["model"]["held"]
    H, T = m["hidden_size"], int(runner.cfg.rollout_length)
    n_layers = held["num_hidden_layers"]
    n_attn = n_layers // m["full_attention_interval"]
    n_gdn = n_layers - n_attn
    nk, nv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    c = int(runner.cfg.seq_model.chunk_size)
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    I, Is = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    k, E = m["num_experts_per_tok"], m["num_experts"]
    pairs = getattr(runner, "moe_pairs_per_token", None)
    if pairs is None:
        pairs = k * held["experts_held"] / E
    V = held["vocab_size"]
    n_held = held["experts_held"]
    touched = getattr(runner, "moe_experts_touched_share", None)
    if touched is None:
        touched = 1.0

    def dense(name, n, fan_in, fan_out):
        return Layer(name, n * fan_in * fan_out, n * fan_in, n * fan_out,
                     n * fan_in * fan_out, 2, True)

    qkvz_ba = 2 * nk * dk + 2 * nv * dv + 2 * nv
    delta = nv * (3 * dk * dv + c * (2 * dk + dv) // 2 + c * (dk + dv) // 2)
    state = nv * dk * dv
    return [
        dense("gdn_in_proj", n_gdn, H, qkvz_ba),
        Layer("gdn_delta_rule", n_gdn * delta, n_gdn * nv * (2 * dk + dv),
              n_gdn * nv * dv, 0, 4, True),
        Layer("gdn_state", 0, 2 * n_gdn * state, 0, 0, 4, True),
        dense("gdn_out_proj", n_gdn, nv * dv, H),
        dense("attn_in_proj", n_attn, H, 2 * nh * hd + 2 * nkv * hd),
        Layer("attn_scores_values", n_attn * nh * hd * (T + 1),
              n_attn * (nh + 2 * nkv) * hd, n_attn * nh * hd, 0, 2, True),
        dense("attn_out_proj", n_attn, nh * hd, H),
        dense("moe_router", n_layers, H, E),
        dense("moe_shared", n_layers, H, 3 * Is + 1),
        Layer("moe_routed", int(round(n_layers * pairs * 3 * H * I)),
              int(round(n_layers * pairs * H)),
              int(round(n_layers * pairs * H)),
              int(round(n_layers * touched * n_held * 3 * H * I)), 2, True),
        dense("lm_head", 1, H, V + 1),
    ]
