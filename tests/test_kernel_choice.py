"""The one place a model chooses between a Pallas kernel and its plain
form (ops/kernel_choice.py), and the shell of models/decoder.py that the
sequence cores share: the head parameters under the names the benchmark's
plain references read."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_algs_on_tensorflow_tpu import models  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import (  # noqa: E402
    qwen3_next as qn,
)
from actor_critic_algs_on_tensorflow_tpu.ops import kernel_choice  # noqa: E402
from tests.helpers import chunk_matrices, kernels_interpreted  # noqa: E402

# 128 chunks of 8 x 8, matrices on a whole lane tile, take the kernel; a
# test preset's 6 do not
FITS, NARROW = (16, 8), (2, 3)


@pytest.mark.parametrize("lead, seam, ran", [
    (NARROW, False, False),   # no fit, the CPU's own choice
    (NARROW, True, False),    # no fit, whatever the platform
    (FITS, False, False),     # a fit, but lowered for the CPU
    (FITS, True, True),       # a fit, lowered as for a TPU
])
def test_the_kernel_runs_where_it_fits_on_a_tpu_and_the_counter_agrees(
    lead, seam, ran, monkeypatch
):
    """``kernel_or_plain`` on the unit-lower inverse: the plain form
    where ``fits`` fails, the kernel (its body interpreted) only where
    it fits and the platform choice is the TPU's; both give the
    inverse, and ``kernel_ran`` says which ran."""
    calls = []
    if seam:
        kernels_interpreted(monkeypatch)
    a = chunk_matrices(lead, 8, repeated=False)

    def kernel(ops):
        def inverse(a):
            calls.append("kernel")
            return ops.inverse(a)

        return inverse

    def plain(a):
        calls.append("plain")
        return qn._plain_inverse(a)

    def fits(ops):
        return ops.fits(a)

    got = kernel_choice.kernel_or_plain(
        "pallas_unit_lower_inverse", fits, kernel, plain, a
    )
    share = kernel_choice.kernel_ran("pallas_unit_lower_inverse", fits)
    assert calls == (["kernel"] if ran else ["plain"])
    assert share.dtype == jnp.float32 and float(share) == float(ran)
    eye = jnp.eye(8)
    np.testing.assert_allclose(
        jnp.einsum("...ij,...jk->...ik", eye + a, got,
                   precision=jax.lax.Precision.HIGHEST),
        jnp.broadcast_to(eye, got.shape), atol=1e-5,
    )


def test_under_jit_the_lowering_platform_chooses():
    """Traced once and lowered for the CPU: the plain branch, a counter
    of 0 (the kernel's branch is traced, never lowered)."""
    a = chunk_matrices(FITS, 8, repeated=False)
    fits = lambda ops: ops.fits(a)  # noqa: E731
    share = jax.jit(
        lambda: kernel_choice.kernel_ran("pallas_unit_lower_inverse", fits)
    )()
    assert float(share) == 0.0
    text = jax.jit(qn._unit_lower_inverse).lower(a).as_text()
    assert "tpu_custom_call" not in text and "solve_triangular" in text


# The names every core's head parameters carry: what the plain
# references read off the parameter tree.
HEADS = {
    "qwen3_next": ("embedding", "final_norm", "lm_head", "value_w",
                   "value_b"),
    "kimi_vl": ("embedding", "final_norm", "lm_head", "value_w", "value_b"),
    "sdar": ("embedding", "final_norm", "lm_head", "value_w", "value_b"),
    # the logits read the tied embedding
    "granite_hybrid": ("embedding", "final_norm", "value_w", "value_b"),
    "xing": ("embedding", "final_norm", "lm_head", "value_w", "value_b"),
    "lfm2": ("embedding", "final_norm", "value_w", "value_b"),
}
TINY = {"qwen3_next": "ppo-qwen3next-tiny", "kimi_vl": "ppo-kimivl-tiny",
        "sdar": "ppo-sdar-tiny", "granite_hybrid": "ppo-granite-tiny",
        "xing": "ppo-xing-tiny", "lfm2": "ppo-lfm2-tiny"}


@pytest.mark.parametrize("torso", sorted(models.SEQUENCE_CORES))
def test_each_core_declares_the_heads_the_references_read(torso):
    """The top of the parameter tree is the head parameters and the
    layers ``layer_<i>``, declared in that order (Flax numbers a
    module's draws by it); each head name is one that
    ``perfbench/reference/<core>.py`` reads."""
    cfg = PRESETS[TINY[torso]][1]["seq_model"]
    model_cls, _ = models.sequence_core(torso)
    model = model_cls(cfg=cfg, cache_len=4)
    block = (cfg.block_length,) if torso == "sdar" else ()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2) + block, jnp.int32),
        jnp.zeros((1, 2)), model.initialize_carry(2),
    )["params"]
    layers = [f"layer_{i}" for i in range(cfg.num_hidden_layers)]
    heads = HEADS[torso]
    assert list(params) == [heads[0], *layers, *heads[1:]]
    with open(os.path.join(ROOT, "perfbench", "reference",
                           f"{torso}.py")) as f:
        read = set(re.findall(r'\["([a-z_]+)"\]', f.read()))
    assert set(heads) <= read, set(heads) - read
