"""The comparisons that decide ``correct``.

Tolerances, and why. The system runs the torso in bfloat16 with float32
parameters, heads, loss and optimizer; the reference runs everything in
float32 at ``default_matmul_precision("highest")``. Both see the same
seeded batch and the parameters the window left (PPO) or a fresh state
(IMPALA, whose gradient is read back from the first Adam step).

* ``ADVANTAGE_RTOL``: GAE runs in float32 on both sides, a ``lax.scan``
  against an unrolled loop; on the chip they agreed exactly.
* ``LOSS_TOL``: bfloat16 keeps 8 bits of mantissa, a relative rounding
  of 2^-9 = 0.2 % per operation, through four layers with float32
  accumulation. Measured on the chip (PERF.md section 6, PR 23): the
  total loss was off by 0.01-0.93 % of its summands' magnitudes
  (``loss_scale``) over some 80 runs of the four cells. 3 % leaves a
  factor of 3 over the worst seen and is a third of what the next step
  down in precision (an 8-bit float torso, 2^-4 per operation) would
  give.
* ``GRAD_COSINE_MIN`` and ``GRAD_NORM_RTOL``: the gradient of the whole
  parameter tree as one vector, compared on the host in float64.
  Measured on the chip: cosine 0.99929 to 0.999998, norms within 3.4 %
  (the worst on four chips after 48 iterations at lr 8e-3; where
  ratios are clipped, a sample's weight jumps with the rounding of its
  log-prob). 0.995 and 10 % are three to seven times the worst seen.

What they catch is shown at a tiny width in ``tests/test_reference.py``:
a dropped entropy term, a doubled value coefficient and weights rounded
to an 8-bit float each fail the comparison. A term that is small beside
the others at the parameters compared (the entropy bonus beside a large
value loss) can hide inside the loss tolerance; the gradient's direction
and norm still hold the rest.
"""

from __future__ import annotations

import math
from typing import Any, Dict

ADVANTAGE_RTOL = 1e-4
LOSS_TOL = 3e-2
GRAD_COSINE_MIN = 0.995
GRAD_NORM_RTOL = 0.10


def seeded_rollout(key, T: int, B: int, obs_shape, num_actions: int) -> dict:
    """The fields every reference check's batch starts from, made from
    the seed: uniform random frames (harder on the arithmetic than
    Pong's mostly black ones), uniform actions, sparse +-1 rewards, 2 %
    episode ends, and ``log_probs``: noise of unit scale around the
    uniform policy's log-probability, for the caller to scale into old
    or behaviour log-probs that straddle the clip."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    return {
        "obs": jax.random.bits(ks[0], (T, B) + tuple(obs_shape), jnp.uint8),
        "actions": jax.random.randint(ks[1], (T, B), 0, num_actions),
        "rewards": jnp.sign(jax.random.normal(ks[2], (T, B)))
        * (jax.random.uniform(ks[3], (T, B)) < 0.1),
        "dones": (jax.random.uniform(ks[4], (T, B)) < 0.02).astype(
            jnp.float32
        ),
        "log_prob_noise": jax.random.normal(ks[5], (T, B)),
        "uniform_log_prob": -jnp.log(float(num_actions)),
    }


def flat_vector(tree):
    """The tree as one float64 vector on the host: a dot product of
    1.7 M terms on the device would itself run at reduced precision."""
    import jax
    import numpy as np

    return np.concatenate([
        np.ravel(np.asarray(x, dtype=np.float64))
        for x in jax.tree_util.tree_leaves(jax.device_get(tree))
    ])


def loss_scale(parts: Dict[str, float], hp: Dict[str, float]) -> float:
    """The magnitudes of the loss's three summands, which the loss error
    is taken against: the total itself can be near zero."""
    return (abs(float(parts["policy_loss"]))
            + hp["vf_coef"] * abs(float(parts["value_loss"]))
            + hp["ent_coef"] * abs(float(parts["entropy"])))


def compare_loss_and_grads(sys_loss: float, ref_loss: float, scale: float,
                           sys_grads, ref_grads) -> Dict[str, Any]:
    """``ok`` and the numbers behind it. Both gradient trees have the
    parameter tree's structure; ``scale`` is ``loss_scale``'s."""
    import numpy as np

    g_sys, g_ref = flat_vector(sys_grads), flat_vector(ref_grads)
    n_sys = float(np.linalg.norm(g_sys))
    n_ref = float(np.linalg.norm(g_ref))
    cosine = float(np.dot(g_sys, g_ref)) / max(n_sys * n_ref, 1e-30)
    loss_err = abs(float(sys_loss) - float(ref_loss)) / max(scale, 1e-30)
    norm_err = abs(n_sys - n_ref) / max(n_ref, 1e-30)
    ok = (
        all(map(math.isfinite, (loss_err, cosine, norm_err)))
        and loss_err <= LOSS_TOL
        and cosine >= GRAD_COSINE_MIN
        and norm_err <= GRAD_NORM_RTOL
    )
    return {"ok": ok, "loss_sys": float(sys_loss), "loss_ref": float(ref_loss),
            "loss_err": loss_err, "grad_cosine": cosine,
            "grad_norm_sys": n_sys, "grad_norm_ref": n_ref,
            "grad_norm_err": norm_err}


LOSS_TERMS_RTOL = 1e-4


def loss_terms_consistent(metrics: Dict[str, float],
                          hp: Dict[str, float]) -> bool:
    """The loss a fused program reports for an iteration is the
    configuration's combination of the terms it reports beside it:
    ``loss = policy_loss + vf_coef * value_loss - ent_coef * entropy``.
    Each is a float32 mean over the iteration's updates and the
    combination is linear, so the identity holds to float32 rounding
    (1e-4 of the summands' magnitudes leaves two digits over it). This
    reads the program under test itself: a coefficient changed or a
    term dropped inside the fused iteration fails here, which the
    reference check of a re-composed loss cannot see."""
    try:
        parts = {k: float(metrics[k]) for k in
                 ("loss", "policy_loss", "value_loss", "entropy")}
    except KeyError:
        return False
    stated = (parts["policy_loss"] + hp["vf_coef"] * parts["value_loss"]
              - hp["ent_coef"] * parts["entropy"])
    err = abs(parts["loss"] - stated)
    return math.isfinite(err) and err <= LOSS_TERMS_RTOL * loss_scale(parts, hp)


def optimizer_count(opt_state) -> int:
    """optax's ``count`` (every transformation that counts, counts the
    same updates; they must agree)."""
    import jax

    counts = {
        int(jax.device_get(leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(opt_state)
        if jax.tree_util.keystr(path).endswith("count")
    }
    if len(counts) != 1:
        raise ValueError(f"optimizer counts disagree or are absent: {counts}")
    return counts.pop()


def updates_consistent(count_before: int, count_after: int, units: int,
                       updates_per_unit: int) -> bool:
    """The optimizer advanced by exactly what the traffic file states
    for ``units`` iterations (PPO) or learner batches (IMPALA): a run
    cannot buy throughput by updating less."""
    return count_after - count_before == units * updates_per_unit


def adam_first_step_grads(opt_state, grad_norm: float, max_grad_norm: float,
                          b1: float = 0.9):
    """The gradient a program applied in its FIRST optimizer step, read
    back from the state it left: from zero moments optax's Adam holds
    ``mu = (1 - b1) * g_clipped``, and ``clip_by_global_norm`` scaled
    ``g`` by ``min(1, max_grad_norm / |g|)``."""
    import jax

    adam = [
        s for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(s, "mu")
    ]
    if len(adam) != 1:
        raise ValueError("expected exactly one Adam state in the chain")
    clip = min(1.0, max_grad_norm / max(grad_norm, 1e-30))
    return jax.tree_util.tree_map(
        lambda m: m / (1.0 - b1) / clip, adam[0].mu
    )


def placement_ok(state, devices) -> bool:
    """``chip_smoke.py`` leg A's assertion: env leaves sharded over
    every device, parameters replicated on every one."""
    import jax

    every = set(devices)
    for leaf in jax.tree_util.tree_leaves((state.obs, state.env_state)):
        if leaf.sharding.device_set != every:
            return False
        if len(every) > 1 and leaf.sharding.is_fully_replicated:
            return False
    return all(
        leaf.sharding.device_set == every
        and leaf.sharding.is_fully_replicated
        for leaf in jax.tree_util.tree_leaves(state.params)
    )
