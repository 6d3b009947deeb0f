"""Matrix products of the SDAR block-diffusion policy, from its shapes
alone.

Source of the shapes: the published ``config.json`` of
JetLM/SDAR-30B-A3B-Chat as the configuration file's ``model`` group
holds it (``published``), cut to what this chip holds (``held``: layers,
experts, vocabulary rows, and the sampler's block). **A sample is an env
step, and an env step is one pass over a block of ``block_length``
positions**: every row below is ``block_length`` times its count a
position, in the rollout and in the update alike (the update's pass
over a trajectory computes every position of every pass again). One row
a product, summed over the layers, counted as the mathematics requires
whatever implements it:

* projections (``gqa_q_proj``, ``gqa_kv_proj``, ``gqa_out_proj``),
  router, head: ``in x out`` multiply-adds a position, weights read once
  a call; the head's row also holds the value head, ``hidden`` a pass;
* ``gqa_scores_values`` (no weights): ``heads x head_dim`` multiply-adds
  for a score and as many for a value, for each key a position sees:
  its own block and the blocks committed before it, the mean over the
  env's schedule (``visible_rows``: 99.3 of 192 in the cell). The step
  form computes both over the whole 1,024-wide cache row instead, 8
  times the operations, none of them required;
* ``gqa_cache``: no multiply-adds, bytes only — the visible rows of the
  key/value cache read once a PASS and layer (``2 x kv heads x
  head_dim`` elements of the compute dtype a row), the block's rows
  written. That is the rollout's step form;
  ``rules/scope_roofline.py`` counts this row for the acting forward
  passes alone (``rollout_only``);
* ``moe_routed``: three ``hidden x moe_intermediate_size`` products a
  (position, expert) pair at the pairs a position that landed on the
  held experts, COUNTED by the program in the measured window
  (``runner.moe_pairs_per_token``; the expected number, ``top_k x held /
  experts``, where no window has run); a call reads the weights of the
  experts that got a pair (``runner.moe_experts_touched_share``; all
  held experts where no window has run).
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer


def visible_rows(env) -> float:
    """Rows of the cache a pass sees, the mean over an episode of
    ``envs/block_turns.py``: turn ``k`` opens on ``2 L k`` committed
    tokens; the pass over the env's block sees those and its own ``L``,
    each of the turn's other passes ``L`` more."""
    L, D = env.block_length, env.denoise_steps
    before = 2 * L * (env.turns - 1) / 2
    return ((before + L) + (D + 1) * (before + 2 * L)) / (D + 2)


def layers(config: dict, runner) -> List[Layer]:
    m, held = config["model"]["published"], config["model"]["held"]
    H, n_layers, L = m["hidden_size"], held["num_hidden_layers"], (
        held["block_length"]
    )
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    I, k, E, n_held = (m["moe_intermediate_size"], m["num_experts_per_tok"],
                       m["num_experts"], held["experts_held"])
    seen = visible_rows(runner.cfg.env_params)
    pairs = getattr(runner, "moe_pairs_per_token", None)
    if pairs is None:
        pairs = k * n_held / E
    touched = getattr(runner, "moe_experts_touched_share", None)
    if touched is None:
        touched = 1.0
    cache_bytes = 2 if runner.cfg.compute_dtype == "bfloat16" else 4

    def dense(name, n, fan_in, fan_out, extra=0):
        return Layer(name, L * n * fan_in * fan_out + extra,
                     L * n * fan_in, L * n * fan_out,
                     n * fan_in * fan_out + extra, 2, True)

    return [
        dense("gqa_q_proj", n_layers, H, nh * hd),
        dense("gqa_kv_proj", n_layers, H, 2 * nkv * hd),
        Layer("gqa_scores_values",
              int(round(L * n_layers * nh * 2 * hd * seen)),
              L * n_layers * (nh + 2 * nkv) * hd, L * n_layers * nh * hd,
              0, 2, True),
        Layer("gqa_cache", 0, int(round(n_layers * 2 * nkv * hd * seen)),
              L * n_layers * 2 * nkv * hd, 0, cache_bytes, True),
        dense("gqa_out_proj", n_layers, nh * hd, H),
        dense("moe_router", n_layers, H, E),
        Layer("moe_routed", int(round(L * n_layers * pairs * 3 * H * I)),
              int(round(L * n_layers * pairs * H)),
              int(round(L * n_layers * pairs * H)),
              int(round(n_layers * touched * n_held * 3 * H * I)), 2, True),
        # the value head: one product of `hidden` a pass
        dense("lm_head", 1, H, held["vocab_size"], extra=H),
    ]
