"""The expert layer's dispatch buffer (models/moe.py) on the CPU at
small widths: the ladder of row counts a spec yields, the laddered
layer against the single-buffer one in every rung (output, gradients,
counters), the ``switch`` that is not there at ``capacity_factor`` 2.0,
and what its derivative keeps.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS
from actor_critic_algs_on_tensorflow_tpu.models import moe

N, H, I = 64, 32, 16
# 2 of 16 experts held, top-2: 16 local pairs expected of the 128 the
# 64 tokens could send here, so a buffer for every pair is 8.0 x.
SPEC = moe.ExpertSpec(num_experts=16, top_k=2, first_expert=4,
                      experts_held=2, capacity_factor=8.0)
LADDER = (24, 48, 128)
ROUTE = functools.partial(moe.route_softmax_top_k, top_k=2, renormalise=True)
GROUPS = ("router", "w_gate", "w_up", "w_down")


class OneRung(moe.ExpertSpec):
    """The single-buffer layer: ``moe_capacity`` rows whatever lands."""

    def buffer_ladder(self, tokens):
        return (self.moe_capacity(tokens),)


def _one_rung(spec):
    return OneRung(**dataclasses.asdict(spec))


def _inputs(bias, spec=SPEC, seed=0):
    """Parameters and tokens whose router adds ``bias`` to the held
    experts' logits: none of the pairs lands here, or all of them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    held = spec.experts_held
    p = {
        "router": jax.random.normal(keys[0], (H, spec.num_experts)),
        "w_gate": 0.2 * jax.random.normal(keys[1], (held, H, I)),
        "w_up": 0.2 * jax.random.normal(keys[2], (held, H, I)),
        "w_down": 0.2 * jax.random.normal(keys[3], (held, I, H)),
    }
    first = spec.first_expert
    p["router"] = p["router"].at[0, first:first + held].set(bias)
    x = jax.random.normal(keys[4], (N, H)).at[:, 0].set(1.0)
    return p, x


def _layer(spec):
    return lambda p, x: moe.routed_experts(p, x, spec, jnp.float32, ROUTE)


def _primitives(jaxpr, name):
    """The equations of ``jaxpr`` and of every jaxpr inside it whose
    primitive is ``name``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_primitives(sub, name))
    return found


# 1. the ladder -------------------------------------------------------------


@pytest.mark.parametrize("preset,tokens,every_pair,ladder", [
    ("ppo-sdar-turns", 128 * 4, True, (768, 1536, 4096)),
    ("ppo-sdar-turns", 16 * 144 * 4, False, (13824, 36864)),
    ("ppo-qwen3next-recall", 128, False, (160,)),
    ("ppo-qwen3next-recall", 32 * 256, False, (10240,)),
    ("ppo-kimivl-recall", 128, False, (192,)),
    ("ppo-kimivl-recall", 16 * 512, False, (12288,)),
])
def test_the_ladder_of_a_presets_call_sites(preset, tokens, every_pair,
                                            ladder):
    """1.5 x and 3 x the expected local pairs where that is at most
    half the capacity, then the capacity: three rungs in an SDAR
    rollout pass, two in its update, one at ``capacity_factor`` 2.0."""
    cfg = PRESETS[preset][1]["seq_model"]
    spec = getattr(cfg, "expert_spec", None) or moe.ExpertSpec(
        num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
        first_expert=cfg.first_expert, experts_held=cfg.experts_held,
        capacity_factor=cfg.capacity_factor,
    )
    if every_pair:
        spec = dataclasses.replace(
            spec, capacity_factor=spec.num_experts / spec.experts_held
        )
    assert spec.buffer_ladder(tokens) == ladder
    assert ladder[-1] == spec.moe_capacity(tokens)


def test_the_small_specs_ladder():
    assert SPEC.buffer_ladder(N) == LADDER
    assert _one_rung(SPEC).buffer_ladder(N) == LADDER[-1:]
    # a lower rung of more than half the capacity buys too little
    assert dataclasses.replace(SPEC, capacity_factor=4.0).buffer_ladder(
        N
    ) == (24, 64)
    assert dataclasses.replace(SPEC, capacity_factor=2.5).buffer_ladder(
        N
    ) == (40,)


# 2. every rung against the single buffer ------------------------------------


@pytest.mark.parametrize("bias,pairs,rows", [
    (-30.0, (0, 0), 24),        # no local pair
    (0.0, (8, 24), 24),         # about the expected number
    (1.5, (25, 48), 48),        # twice
    (30.0, (128, 128), 128),    # every pair local
])
def test_a_rung_gives_the_single_buffers_output_and_gradients(
    bias, pairs, rows
):
    p, x = _inputs(bias)
    single, laddered = _layer(_one_rung(SPEC)), _layer(SPEC)
    want, want_stats = jax.jit(single)(p, x)
    got, stats = jax.jit(laddered)(p, x)
    n_mine = float(stats["moe_local_pairs_per_token"]) * N
    assert pairs[0] <= n_mine <= pairs[1]
    assert float(stats["moe_buffer_rows_used_share"]) == rows / LADDER[-1]
    assert float(stats["moe_overflow_pairs"]) == 0.0
    np.testing.assert_allclose(got, want, atol=1e-6)
    for name in ("moe_local_pairs_per_token", "moe_overflow_pairs",
                 "moe_expert_load_max_over_mean",
                 "moe_experts_touched_share"):
        assert float(stats[name]) == float(want_stats[name]), name

    def loss(layer, p, x):
        return jnp.sum(jnp.sin(layer(p, x)[0]))

    want = jax.jit(
        jax.grad(functools.partial(loss, single), (0, 1))
    )(p, x)
    for layer in (laddered, jax.checkpoint(laddered)):
        got = jax.jit(
            jax.grad(functools.partial(loss, layer), (0, 1))
        )(p, x)
        np.testing.assert_allclose(got[1], want[1], atol=1e-6)
        for name in GROUPS:
            np.testing.assert_allclose(
                got[0][name], want[0][name], atol=1e-6, err_msg=name
            )
    if n_mine:
        assert all(float(jnp.max(jnp.abs(want[0][n]))) > 0 for n in GROUPS)


@pytest.mark.parametrize("bias", [0.0, 30.0])
def test_a_rung_in_bfloat16_is_the_single_buffer_bit_for_bit(bias):
    """The experts' weights are cast before the ``switch`` and not in
    each branch, and their gradients are cast back inside it: the same
    values through the same products and the same roundings, forward
    and backward."""
    p, x = _inputs(bias)

    def loss(spec, p, x):
        y, _ = moe.routed_experts(p, x, spec, jnp.bfloat16, ROUTE)
        return jnp.sum(jnp.sin(y)), y

    grad = lambda spec: jax.jit(
        jax.grad(functools.partial(loss, spec), (0, 1), has_aux=True)
    )(p, x)
    (want_p, want_x), want = grad(_one_rung(SPEC))
    (got_p, got_x), got = grad(SPEC)
    np.testing.assert_array_equal(got, want)
    for name in ("w_gate", "w_up", "w_down"):
        assert got_p[name].dtype == jnp.float32
        np.testing.assert_array_equal(got_p[name], want_p[name], name)
    np.testing.assert_allclose(got_p["router"], want_p["router"], atol=1e-6)
    np.testing.assert_allclose(got_x, want_x, atol=1e-6)


# 3. no ladder, no switch; and what the derivative keeps -----------------------


def test_capacity_factor_two_traces_no_cond():
    p, x = _inputs(0.0)
    spec = dataclasses.replace(SPEC, capacity_factor=2.0)
    assert len(spec.buffer_ladder(N)) == 1

    def loss(spec, p, x):
        return jnp.sum(jax.checkpoint(_layer(spec))(p, x)[0])

    for fn in (_layer(spec), jax.grad(functools.partial(loss, spec))):
        assert not _primitives(jax.make_jaxpr(fn)(p, x).jaxpr, "cond")
    laddered = jax.make_jaxpr(_layer(SPEC))(p, x).jaxpr
    (cond,) = _primitives(laddered, "cond")
    assert len(cond.params["branches"]) == len(LADDER)
    _, stats = _layer(spec)(p, x)
    assert float(stats["moe_buffer_rows_used_share"]) == 1.0


@pytest.mark.parametrize("remat", [False, True])
def test_the_derivative_keeps_no_rungs_residuals(remat):
    """Forward ``switch`` out: the layer's output and three counters,
    no residual of any rung. Backward: one ``switch`` over each rung's
    own forward and transpose. Under ``jax.checkpoint`` the recomputed
    forward feeds only the counters and is gone: as many grouped
    products as without it."""
    p, x = _inputs(0.0)
    layer = jax.checkpoint(_layer(SPEC)) if remat else _layer(SPEC)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p, x: jnp.sum(jnp.sin(layer(p, x)[0])), (0, 1))
    )(p, x).jaxpr
    forward, backward = _primitives(jaxpr, "cond")
    assert len(forward.outvars) == 4
    products = _primitives(jaxpr, "ragged_dot_general")
    # a rung: 3 forward; 3 forward again, 3 input and 3 weight gradients
    assert len(products) == len(LADDER) * (3 + 9)
    rows = sorted({eqn.invars[0].aval.shape[0] for eqn in products})
    assert rows[:2] == [LADDER[0], LADDER[1]] and LADDER[2] in rows


# 4. the counters ---------------------------------------------------------------


@pytest.mark.parametrize("bias,rows", [(0.0, 24), (1.5, 48), (30.0, 128)])
def test_rows_used_and_fill_are_over_the_rung_that_ran(bias, rows):
    p, x = _inputs(bias)
    _, stats = _layer(SPEC)(p, x)
    kept = float(stats["moe_local_pairs_per_token"]) * N - float(
        stats["moe_overflow_pairs"]
    )
    assert float(stats["moe_buffer_rows_used_share"]) == rows / 128
    assert float(stats["moe_buffer_fill_share"]) == pytest.approx(
        kept / rows, rel=1e-6
    )


def test_the_top_rung_runs_and_counts_what_does_not_fit():
    """A capacity under the pairs that land: the top rung, full, the
    rest counted, as ``tests/test_qwen3_next.py::
    test_the_overflow_counter_counts`` has it for the single buffer."""
    small = dataclasses.replace(SPEC, capacity_factor=6.0)
    assert small.buffer_ladder(N) == (24, 48, 96)
    p, x = _inputs(30.0, small)
    y, stats = _layer(small)(p, x)
    want, want_stats = _layer(_one_rung(small))(p, x)
    rows = small.moe_capacity(N)
    pairs = float(stats["moe_local_pairs_per_token"]) * N
    assert pairs == 128 > rows
    assert float(stats["moe_overflow_pairs"]) == pairs - rows == float(
        want_stats["moe_overflow_pairs"]
    )
    assert float(stats["moe_buffer_rows_used_share"]) == 1.0
    assert float(stats["moe_buffer_fill_share"]) == 1.0
    np.testing.assert_allclose(y, want, atol=1e-6)


def test_the_counters_reduce_by_mean_and_by_phase():
    rows = {
        "moe_local_pairs_per_token": jnp.ones((3,)),
        "moe_expert_load_max_over_mean": jnp.ones((3,)),
        "moe_overflow_pairs": jnp.zeros((3,)),
        "moe_experts_touched_share": jnp.ones((3,)),
        "moe_buffer_fill_share": jnp.asarray([0.5, 0.25, 0.75]),
        "moe_buffer_rows_used_share": jnp.asarray([0.1875, 0.375, 1.0]),
    }
    one = moe.reduce_moe_stats(rows)
    assert float(one["moe_buffer_rows_used_share"]) == pytest.approx(0.5208333)
    update = dict(rows, moe_buffer_rows_used_share=jnp.ones((3,)))
    stats = jax.vmap(
        lambda r, u: moe.iteration_moe_stats(r, u, "data"), axis_name="data"
    )(*jax.tree_util.tree_map(lambda v: v[None], (rows, update)))
    assert float(stats["moe_buffer_rows_used_share_rollout"][0]) == (
        pytest.approx(0.5208333)
    )
    assert float(stats["moe_buffer_rows_used_share_update"][0]) == 1.0
    assert "moe_buffer_rows_used_share" not in stats
