"""Plain float32 Nature-CNN actor-critic forward pass.

Source: Mnih et al. 2015, "Human-level control through deep
reinforcement learning", Nature 518:529, Methods "Model architecture":
84x84x4 input scaled to [0, 1]; 32 filters of 8x8 stride 4, ReLU; 64
of 4x4 stride 2, ReLU; 64 of 3x3 stride 1, ReLU; 512 rectifier units;
then one linear output per action. The value head beside the policy
head (one more linear output on the same 512 features) is the shared-
torso actor-critic of Mnih et al. 2016 (arXiv:1602.01783, sec. 8),
which OpenAI Baselines ppo2 ``network='cnn'`` and this repo use.

Departures from the source: none in the arithmetic. No padding
(VALID), as the paper's sizes 20x20, 9x9, 7x7 imply. The weights are
the program's own parameter tree (flax names ``NatureCNN_0/Conv_<i>``,
``NatureCNN_0/Dense_0``, ``Dense_0`` policy, ``Dense_1`` value), so
that system and reference evaluate the same function of the same
numbers; everything here runs in float32 at
``default_matmul_precision("highest")``, set by the caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STRIDES = (4, 2, 1)


def forward(params, obs):
    """``obs`` ``[N, 84, 84, 4]`` uint8 -> ``(logits [N, A], value [N])``."""
    p = params["params"]
    x = obs.astype(jnp.float32) / 255.0
    for i, stride in enumerate(STRIDES):
        layer = p["NatureCNN_0"][f"Conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, layer["kernel"].astype(jnp.float32), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + layer["bias"]
        x = jnp.maximum(x, 0.0)
    x = x.reshape(x.shape[0], -1)
    dense = p["NatureCNN_0"]["Dense_0"]
    x = jnp.maximum(x @ dense["kernel"] + dense["bias"], 0.0)
    logits = x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]
    value = (x @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"])[:, 0]
    return logits, value


def log_softmax(logits):
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    return z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))


def categorical(logits, actions):
    """``(log pi(a|s), entropy)`` of the softmax policy."""
    logp = log_softmax(logits)
    taken = jnp.take_along_axis(logp, actions[:, None], axis=-1)[:, 0]
    entropy = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return taken, entropy
