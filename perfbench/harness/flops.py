"""Operations and bytes of a model's matrix products, from its shapes
alone: the arithmetic every model shares. The shapes themselves come
from the operations function the configuration file names
(``perfbench/operations/<name>.py``), as a list of ``Layer`` rows.

Counting rule (on-chip-measurement guide, section 4): a multiply-add is
2 operations; the backward pass of a layer is one weight-gradient and
one input-gradient product, each as many operations as the forward
one; a layer whose input is data has no input gradient.
Nothing recomputed counts. Bytes are what an ideal kernel moves once:
the layer's input (``in_bytes`` an element: 1 for uint8 frames, 2 for
bf16 activations), its output in bf16, and the weights once per call.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    macs: int          # multiply-adds per sample, forward
    in_elems: int      # per sample
    out_elems: int     # per sample
    w_elems: int
    in_bytes: int      # bytes per input element
    input_grad: bool   # False for the layer that reads the frames


def passes(layer: Layer) -> Dict[str, tuple]:
    """``{pass: (flops per sample, bytes per sample, bytes per call)}``
    for the forward, weight-gradient and input-gradient products."""
    act = 2  # bf16 activations and activation gradients
    out = {
        "forward": (
            2 * layer.macs,
            layer.in_elems * layer.in_bytes + layer.out_elems * act,
            layer.w_elems * act,
        ),
        # reads the input and the output gradient, writes f32 weights' grad
        "weight_grad": (
            2 * layer.macs,
            layer.in_elems * layer.in_bytes + layer.out_elems * act,
            layer.w_elems * 4,
        ),
    }
    if layer.input_grad:
        out["input_grad"] = (
            2 * layer.macs,
            layer.out_elems * act + layer.in_elems * act,
            layer.w_elems * act,
        )
    return out


def forward_flops_per_sample(layers: List[Layer]) -> int:
    return sum(2 * l.macs for l in layers)


def train_flops_per_sample(layers: List[Layer]) -> int:
    """Forward plus backward, as the update needs them."""
    return sum(f for l in layers for f, _, _ in passes(l).values())


def model_flops(layers: List[Layer], work: dict) -> float:
    """Operations required by ``work``: ``forward_samples`` policy or
    value evaluations and ``train_samples`` samples of an update."""
    return (
        work.get("forward_samples", 0) * forward_flops_per_sample(layers)
        + work.get("train_samples", 0) * train_flops_per_sample(layers)
    )


def mxu_min_seconds(layers: List[Layer], work: dict, peaks: dict) -> dict:
    """The least time the chip could take for the matrix products of
    ``work`` (convolutions and dense layers): for each product the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    summed. Also says how much of that time is bound by bytes."""
    total = mem_bound = 0.0
    work = {k: work.get(k, 0) for k in
            ("forward_samples", "forward_calls", "train_samples",
             "train_calls")}
    for layer in layers:
        for kind, (flops, nbytes, call_bytes) in passes(layer).items():
            if kind == "forward":
                n = work["forward_samples"] + work["train_samples"]
                calls = work["forward_calls"] + work["train_calls"]
            else:
                n, calls = work["train_samples"], work["train_calls"]
            t_flops = n * flops / peaks["bf16_flops_per_s"]
            t_bytes = (n * nbytes + calls * call_bytes) / peaks[
                "hbm_bytes_per_s"
            ]
            total += max(t_flops, t_bytes)
            if t_bytes > t_flops:
                mem_bound += t_bytes
    return {"seconds": total, "memory_bound_share": mem_bound / total
            if total else 0.0}
