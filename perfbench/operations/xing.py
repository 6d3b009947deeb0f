"""Matrix products and the residual path's bytes of the Xing4.0-29B-A4B
policy, from its shapes alone.

Source of the shapes: the published ``config.json`` of
XingChen-AGI/Xing4.0-29B-A4B as the configuration file's ``model`` group
holds it (``published``), cut to what this chip holds (``held``: layers,
experts, vocabulary rows). One row a product and a token, summed over
the layers that have it, counted as the mathematics requires whatever
implements it.

The layer's rows are ``operations/kimi_vl.py``'s (the same latent
attention, dense layer, router, shared and routed experts and head, read
off the same keys) but for the query, which runs through
``q_lora_rank``: ``mla_q_proj`` is ``hidden x q_lora_rank + q_lora_rank
x heads x (d_nope + d_rope)`` multiply-adds a token. Beside them, the
manifold-constrained hyper-connections, two sublayers a layer, ``n =
hc_mult`` streams of ``C = hidden_size`` float32 numbers a token:

* ``mhc_coeffs``: the coefficient product ``u Phi``, ``n C x (2n + n^2)``
  multiply-adds a token and a sublayer; its input the streams (``n C``
  float32 numbers, read once for the norm and the product), ``Phi`` read
  once a call;
* ``mhc_mix``: no product, bytes only at the least — the streams read
  twice (the pre-mix before the sublayer, the residual mix after it),
  the sublayer's output read once and the new streams written once:
  ``(3n + 1) C`` float32 numbers a token and a sublayer (counted as the
  row's input; ``n^2 C + 2 n C`` multiply-adds, elementwise);
* ``mhc_sinkhorn``: ``hc_sinkhorn_iters x 2`` normalisations of the
  ``n x n`` matrix, ``n^2`` multiply-adds each, its ``n^2`` numbers in
  and out.

The counts are of the work, not of a program: a fused kernel later reads
the same rows. An update's token counts as ``harness/flops.py::passes``
counts any row (the forward pass and two backward products of the
forward's size); what runs is the forward pass, the per-layer
checkpoint's recomputation of it and the backward pass, about 3.1 x the
forward's bytes where the passes count 2.5 x: the roofline share that
reads these rows is a lower bound (PERF.md section 3).
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer
from perfbench.operations import kimi_vl


def layers(config: dict, runner) -> List[Layer]:
    m, held = config["model"]["published"], config["model"]["held"]
    H, n = m["hidden_size"], m["hc_mult"]
    n_layers = held["num_hidden_layers"]
    q_out = m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    )
    r = m["q_lora_rank"]
    q_macs = n_layers * (H * r + r * q_out)
    # (Kimi-VL's rows read the dense layers off the published key; the
    # held layers' pattern is the held one)
    held_pattern = {"model": {"held": held, "published": dict(
        m, first_k_dense_replace=held["first_k_dense_replace"]
    )}}
    rows = [
        Layer("mla_q_proj", q_macs, n_layers * H, n_layers * q_out, q_macs,
              2, True)
        if row.name == "mla_q_proj" else row
        for row in kimi_vl.layers(held_pattern, runner)
    ]
    sublayers, coeffs = 2 * n_layers, 2 * n + n * n
    iters = m["hc_sinkhorn_iters"]
    return rows + [
        Layer("mhc_coeffs", sublayers * n * H * coeffs, sublayers * n * H,
              sublayers * coeffs, sublayers * n * H * coeffs, 4, True),
        Layer("mhc_mix", sublayers * (n * n * H + 2 * n * H),
              sublayers * (3 * n + 1) * H, 0, 0, 4, True),
        Layer("mhc_sinkhorn", sublayers * iters * 2 * n * n,
              sublayers * n * n, sublayers * n * n, 0, 4, True),
    ]
