"""The LFM2-24B-A2B sequence-policy core (models/lfm2.py) against its
plain reference (perfbench/reference/lfm2.py) at the tiny preset's
widths on the CPU: the sequence form, the step form through tails and
cache with a reset, the trainer's ``block_grads``, the stated precision
and the steps below it, the expert layer's shares, the tail counter,
the published defaults and the parameter count, and a short run.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (  # noqa: E402
    PPOConfig,
    make_ppo,
)
from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import lfm2  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import moe  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models.decoder import (  # noqa: E402
    rms_norm,
)
from perfbench.reference import lfm2 as ref  # noqa: E402
from perfbench.reference import ppo_loss as ref_ppo  # noqa: E402

TINY = PRESETS["ppo-lfm2-tiny"][1]
CFG = TINY["seq_model"]
# The reference reads the published keys as a dict, and what is held.
MODEL = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
HELD = {"num_hidden_layers": CFG.num_hidden_layers,
        "num_dense_layers": CFG.num_dense_layers,
        "layer_types": CFG.layer_types, "first_expert": CFG.first_expert,
        "experts_held": CFG.experts_held, "vocab_size": CFG.vocab_size}
CONFIG_FILE = os.path.join(ROOT, "perfbench", "configs",
                           "ppo-lfm2-24b-a2b.json")
T, B = 13, 3


def _model(dtype=jnp.float32, cache_len=T, cfg=CFG):
    return lfm2.Lfm2ActorCritic(cfg=cfg, cache_len=cache_len, dtype=dtype)


def _init(model, seed=0, batch=B):
    tokens = jnp.zeros((1, batch), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(seed), tokens, jnp.zeros((1, batch)),
        model.initialize_carry(batch),
    )
    # Norm weights and the value bias start at 1 (or 0): move them, so
    # that a norm that forgot its weight would show.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim <= 1 else x
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def _tokens(T=T, B=B, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (T, B), 0, CFG.vocab_size
    )


def _reference(params, tokens, model=MODEL, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, tokens, model, HELD, **kw)


def _rms(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2)))


# 1. the sequence form against the reference ------------------------------


def test_sequence_forward_equals_reference():
    """Float32 on both sides, the convolution as shifted slices here
    and an explicit sum of taps there, attention over grouped heads here
    and repeated ones there: rounding only, on logits of scale ~0.1 and
    values of scale ~1, so 2e-5."""
    model = _model()
    params, tokens = _init(model), _tokens()
    logits, values, _, stats = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    ref_logits, ref_values = _reference(params, tokens)
    assert logits.shape == (T, B, CFG.vocab_size) and values.shape == (T, B)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(values, ref_values, atol=2e-5)
    assert 0.0 < float(stats[lfm2.TAIL_SHARE]) < 1.5
    assert float(stats["moe_overflow_pairs"]) == 0.0
    # the reference a step down in precision is NOT within that bound
    low, _ = _reference(params, tokens, dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low - ref_logits))) > 2e-5


# 2. the step form through tails and cache ---------------------------------


def _stepwise(model, params, tokens, resets):
    carry = model.initialize_carry(tokens.shape[1])
    step = jax.jit(model.apply)
    logits, values, rows = [], [], []
    for t in range(tokens.shape[0]):
        lg, v, carry, row = step(params, tokens[t:t + 1], resets[t:t + 1],
                                 carry)
        logits.append(lg[0])
        values.append(v[0])
        rows.append(row)
    return jnp.stack(logits), jnp.stack(values), carry, rows


def test_stepping_through_tails_and_cache_equals_the_sequence_pass():
    """The convolutions through their two-token tails and attention
    through its cache, a token at a time, are the sequence pass (float32,
    2e-5); a reset in the middle is a fresh start for that env alone."""
    model = _model()
    params, tokens = _init(model), _tokens()
    cut = 6
    resets = jnp.zeros((T, B)).at[cut, 1].set(1.0)
    logits, values, carry, _ = _stepwise(model, params, tokens, resets)
    seq = lambda tok: model.apply(params, tok, jnp.zeros(tok.shape), None)
    whole_logits, whole_values = seq(tokens)[:2]
    for env in (0, 2):
        np.testing.assert_allclose(logits[:, env], whole_logits[:, env],
                                   atol=2e-5)
        np.testing.assert_allclose(values[:, env], whole_values[:, env],
                                   atol=2e-5)
    np.testing.assert_allclose(logits[:cut, 1], whole_logits[:cut, 1],
                               atol=2e-5)
    fresh_logits, fresh_values = seq(tokens[cut:, 1:2])[:2]
    np.testing.assert_allclose(logits[cut:, 1], fresh_logits[:, 0], atol=2e-5)
    np.testing.assert_allclose(values[cut:, 1], fresh_values[:, 0], atol=2e-5)
    assert float(jnp.max(jnp.abs(logits[cut:, 1] - whole_logits[cut:, 1]))) > (
        1e-3
    )
    assert np.asarray(carry["pos"]).tolist() == [T, T - cut, T]
    n_conv = CFG.layer_types.count(lfm2.CONV)
    assert carry["conv"].shape == (B, n_conv, 2, CFG.hidden_size)
    assert carry["conv"].dtype == jnp.float32
    assert carry["k"].shape == carry["v"].shape == (B, 1, T, 2, 16)


def test_a_step_form_that_drops_its_tail_is_another_function():
    """The convolution's carried taps are not a rounding: with every
    tail emptied before each step, the stepped logits leave the sequence
    pass by far more than the tolerance above."""
    model = _model()
    params, tokens = _init(model), _tokens()
    carry = model.initialize_carry(B)
    step = jax.jit(model.apply)
    dropped = []
    for t in range(T):
        carry = dict(carry, conv=jnp.zeros_like(carry["conv"]))
        lg, _, carry, _ = step(params, tokens[t:t + 1], jnp.zeros((1, B)),
                               carry)
        dropped.append(lg[0])
    whole = model.apply(params, tokens, jnp.zeros((T, B)), None)[0]
    assert float(jnp.max(jnp.abs(jnp.stack(dropped) - whole))) > 1e-3


# 3. the tail counter -------------------------------------------------------


def test_the_tail_share_is_zero_on_an_empty_tail_and_positive_after():
    """``shortconv_tail_share`` reads 0 at the first step (every tail
    empty), and strictly more after one and after two steps; the step
    form reads, token by token, what the sequence form reads over the
    same tokens."""
    model = _model()
    params, tokens = _init(model), _tokens()
    _, _, _, rows = _stepwise(model, params, tokens, jnp.zeros((T, B)))
    shares = [float(r[lfm2.TAIL_SHARE]) for r in rows]
    assert shares[0] == 0.0
    assert shares[1] > 0.05 and shares[2] > 0.05, shares[:3]
    seq = model.apply(params, tokens, jnp.zeros((T, B)), None)[3]
    np.testing.assert_allclose(float(seq[lfm2.TAIL_SHARE]), np.mean(shares),
                               rtol=1e-5)
    # a reset empties the tail again
    resets = jnp.zeros((T, B)).at[4].set(1.0)
    _, _, _, rows = _stepwise(model, params, tokens, resets)
    assert float(rows[4][lfm2.TAIL_SHARE]) == 0.0
    assert float(rows[5][lfm2.TAIL_SHARE]) > 0.05


# 4. the trainer's gradients against the reference ---------------------------


@pytest.fixture(scope="module")
def trainer():
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    return cfg, fns, fns.init(jax.random.PRNGKey(4))


def test_block_grads_equal_the_reference_loss_and_gradients(trainer):
    cfg, fns, state = trainer
    traj, _ = fns.collect(state)
    T_, B_ = traj.obs.shape
    assert (T_, B_) == (cfg.rollout_length, cfg.num_envs)
    # what collect stored is what the reference computes on the tokens
    logits, values = _reference(state.params, traj.obs)
    log_probs, _ = ref.categorical(logits, traj.actions)
    np.testing.assert_allclose(traj.log_probs, log_probs, atol=2e-5)
    np.testing.assert_allclose(traj.values, values, atol=2e-5)
    noise = jax.random.normal(jax.random.PRNGKey(8), (3, T_, B_))
    adv, ret = ref_ppo.gae(
        traj.rewards + 0.3 * noise[0], traj.values, traj.dones,
        jnp.zeros((B_,)), cfg.gamma, cfg.gae_lambda,
    )
    block = {
        "obs": traj.obs, "actions": traj.actions,
        # old log-probs scattered so that a share of the ratios clips
        "old_log_probs": traj.log_probs + 0.15 * noise[1],
        "old_values": traj.values + 0.1 * noise[2],
        "advantages": adv, "returns": ret,
        "resets": jnp.zeros((T_, B_)), "core": None,
    }
    loss, parts, grads = fns.block_grads(state.params, block)
    hp = {"clip_eps": cfg.clip_eps, "vf_coef": cfg.vf_coef,
          "ent_coef": cfg.ent_coef}
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref_parts), ref_grads = jax.value_and_grad(
            ref.ppo_loss, has_aux=True
        )(state.params, block, hp, MODEL, HELD)
    np.testing.assert_allclose(loss, ref_loss, atol=1e-5)
    for k in ref_parts:
        np.testing.assert_allclose(parts[k], ref_parts[k], atol=1e-5)
    assert jax.tree_util.tree_structure(grads) == (
        jax.tree_util.tree_structure(ref_grads)
    )
    flat = lambda t: np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(t)]
    )
    g, g_ref = flat(grads), flat(ref_grads)
    assert np.linalg.norm(g_ref) > 1e-3
    np.testing.assert_allclose(g, g_ref, atol=2e-5 * np.abs(g_ref).max())
    # the convolution's taps, the per-head norms and the router train;
    # the selection bias has no gradient
    layers = grads["params"]
    assert float(jnp.max(jnp.abs(layers["layer_2"]["conv"]))) > 0.0
    assert float(jnp.max(jnp.abs(layers["layer_1"]["k_norm"]))) > 0.0
    assert float(jnp.max(jnp.abs(layers["layer_1"]["router"]))) > 0.0
    assert float(jnp.max(jnp.abs(layers["layer_1"]["expert_bias"]))) == 0.0


# 5. bfloat16 products, and the steps below the stated precision ------------


def test_at_bfloat16_products_the_system_stays_with_the_stated_reference():
    """bfloat16 inputs to the matrix products, float32 sums and all else
    float32: the program stands well inside what rounding the products
    costs, and a reference whose tails are bfloat16 (or whose norms or
    router run in it) stands further from the stated one than the
    program does."""
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), _tokens()
    logits, values, _, _ = model.apply(params, tokens, jnp.zeros((T, B)),
                                       None)
    stated = _reference(params, tokens, products=jnp.bfloat16)
    plain = _reference(params, tokens)
    tolerance = _rms(stated[0], plain[0])  # what bf16 products cost
    program = _rms(logits, stated[0])
    assert program < 0.5 * tolerance, (program, tolerance)
    assert _rms(values, stated[1]) < _rms(stated[1], plain[1])
    same = _reference(params, tokens, products=jnp.float32)
    np.testing.assert_allclose(same[0], plain[0], atol=1e-6)
    for lower in ("tails", "norms", "router"):
        lowered, _ = _reference(params, tokens, products=jnp.bfloat16,
                                lower=(lower,))
        control = _rms(lowered, stated[0])
        assert control > 2 * program, (lower, control, program)


# 6. the expert layer's shares -----------------------------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Over the tiny model's shares of its 8 experts (4 held each), the
    routed terms each share computes add up to the uncut reference's
    expert layer; the router, which every chip computes alike, counts
    once (there is no shared expert)."""
    full = dataclasses.replace(CFG, first_expert=0,
                               experts_held=CFG.num_experts)
    params = _init(_model(cfg=full))["params"]["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.hidden_size))
    h = rms_norm(x, params["post_norm"], CFG.norm_eps)
    held = CFG.experts_held
    total = jnp.zeros_like(h)
    for first in range(0, CFG.num_experts, held):
        share = dataclasses.replace(CFG, first_expert=first)
        mine = dict(params, **{n: params[n][first:first + held]
                               for n in ("w_gate", "w_up", "w_down")})
        y, stats = lfm2.routed_experts(mine, h, share, jnp.float32)
        assert float(stats["moe_overflow_pairs"]) == 0.0
        total = total + y
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_block(params, h, MODEL, 0, CFG.num_experts)
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    # and the routing is the reference's gate, epsilon and all
    experts, weights = lfm2.route(params, h, CFG)
    ref_e, ref_w = ref.gate(params, h, MODEL)
    np.testing.assert_array_equal(experts, ref_e)
    np.testing.assert_allclose(weights, ref_w, rtol=1e-6)


def test_the_sigmoid_routing_takes_its_epsilon():
    """``moe.route_sigmoid_top_k`` with LFM2's 1e-6 and with Kimi-VL's
    1e-20: the same experts, weights apart by the epsilon alone."""
    params = _init(_model())["params"]["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (16, CFG.hidden_size))
    args = (params, x, params["expert_bias"], CFG.num_experts_per_tok, True,
            1.0)
    e6, w6 = moe.route_sigmoid_top_k(*args, 1e-6)
    e20, w20 = moe.route_sigmoid_top_k(*args, 1e-20)
    np.testing.assert_array_equal(e6, e20)
    np.testing.assert_allclose(jnp.sum(w20, -1), 1.0, rtol=1e-6)
    assert float(jnp.max(jnp.abs(w6 - w20))) > 0.0
    np.testing.assert_allclose(w6, w20, rtol=2e-6)


# 7. the published defaults and the held share ------------------------------


HELD_SHARE = ("first_expert", "experts_held", "capacity_factor")


def test_defaults_are_the_published_row_and_the_share_counts():
    """The dataclass's defaults are the published ``config.json`` as the
    configuration file holds it (``model.published``, the catalog's row),
    the preset is its held share, and the parameter count the file
    states is the built layout's."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    published = config["model"]["published"]
    cfg = lfm2.Lfm2Config()
    fields = {f.name for f in dataclasses.fields(cfg)} - set(HELD_SHARE)
    assert fields == set(published)
    for key, value in published.items():
        got = getattr(cfg, key)
        if key == "rope_parameters":
            got = dict(got)
        elif key == "layer_types":
            got = list(got)
        assert got == value, key
    held = PRESETS["ppo-lfm2-recall"][1]["seq_model"]
    # published layer 0, then layers 2-5: one whole period
    assert held == dataclasses.replace(
        cfg, num_hidden_layers=5, num_dense_layers=1,
        layer_types=cfg.layer_types[:1] + cfg.layer_types[2:6],
        vocab_size=8_192, experts_held=8,
    )
    model = lfm2.Lfm2ActorCritic(cfg=held, cache_len=512)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 2)), model.initialize_carry(2),
        )
    )["params"]
    size = lambda t: sum(int(np.prod(x.shape))
                         for x in jax.tree_util.tree_leaves(t))
    stated = config["model"]["parameters"]
    assert int(stated.split()[0].replace(",", "")) == size(shapes)
    assert size(shapes) == 469_287_297
    assert size(shapes["layer_0"]) == 89_139_200
    assert size(shapes["layer_1"]) == 86_118_592
    assert size(shapes["layer_2"]) == 92_416_064


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("rope_parameters", (("rope_theta", 1e6), ("rope_type", "yarn"))),
    ("layer_types", ("conv", "sliding_attention", "conv", "conv", "conv")),
    ("num_hidden_layers", 4), ("conv_L_cache", 1), ("num_dense_layers", 5),
])
def test_the_config_refuses_what_is_not_built(key, value):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **{key: value})


# 8. a short run ------------------------------------------------------------------


def test_a_short_run_trains_and_counts():
    """Two iterations through ``make_ppo``: the expert layer's counters
    and the convolutions' tail share."""
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(1))
    p0 = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
    state, first = fns.iteration(state)
    state, metrics = fns.iteration(state)
    # the update's sequence pass reproduces what the rollout's steps
    # through tails and cache stored
    assert float(first["approx_kl"]) < 1e-5
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["health_finite"]) == 1.0
    assert 0.0 < float(metrics[lfm2.TAIL_SHARE]) < 1.5
    assert float(metrics["moe_overflow_pairs"]) == 0.0
    assert float(metrics["episodes"]) == cfg.num_envs
    assert int(state.step) == 2
    assert fns.steps_per_iteration == cfg.num_envs * cfg.rollout_length
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, state.params
    )
    for path, v in jax.tree_util.tree_leaves_with_path(moved):
        if "expert_bias" in jax.tree_util.keystr(path):
            assert v == 0.0  # no gradient reaches it, nothing updates it
        else:
            assert v > 0.0, jax.tree_util.keystr(path)
