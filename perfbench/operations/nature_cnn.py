"""Matrix products of the Nature-CNN actor-critic, from its shapes alone.

Source of the shapes: Mnih et al. 2015, Nature 518:529, Methods "Model
architecture" — 84x84x4 input, conv 32 of 8x8 stride 4, conv 64 of 4x4
stride 2, conv 64 of 3x3 stride 1, dense 512 — read from the
configuration file's ``model`` group, VALID padding as the program's
``models/networks.py::NatureCNN`` has it, plus the two heads (policy
logits and one value) on the 512 features. The first layer reads the
frames, which are data: it has no input gradient.
"""

from __future__ import annotations

from typing import List

from perfbench.harness.flops import Layer


def layers(config: dict, runner) -> List[Layer]:
    """``config`` is the configuration file; the runner says how many
    actions the cell's env has (``runner.num_actions``)."""
    model, num_actions = config["model"], int(runner.num_actions)
    h, w, c = model["input"]
    out = []
    for i, conv in enumerate(model["conv"]):
        k, s, f = conv["kernel"], conv["stride"], conv["features"]
        oh, ow = (h - k) // s + 1, (w - k) // s + 1
        out.append(Layer(
            name=f"conv{i}", macs=oh * ow * f * k * k * c,
            in_elems=h * w * c, out_elems=oh * ow * f,
            w_elems=k * k * c * f,
            in_bytes=1 if (i == 0 and model["input_dtype"] == "uint8") else 2,
            input_grad=i > 0,
        ))
        h, w, c = oh, ow, f
    flat, d = h * w * c, model["dense"]
    out.append(Layer("dense", flat * d, flat, d, flat * d, 2, True))
    heads = num_actions + 1
    out.append(Layer("heads", d * heads, d, heads, d * heads, 2, True))
    return out
