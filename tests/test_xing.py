"""The Xing4.0-29B-A4B sequence-policy core (models/xing.py) against its
plain reference (perfbench/reference/xing.py) at the tiny preset's
widths on the CPU: the expanded sequence form, the absorbed step form
through the cache of latents, the trainer's ``block_grads``, the stated
precision and the steps below it, the expert layer's shares under the
hyper-connections, YaRN, the published defaults and the parameter
count, and a short run. The hyper-connections alone are
tests/test_hyper_connections.py's.
"""

import dataclasses
import json
import math
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
from actor_critic_algs_on_tensorflow_tpu.models import (  # noqa: E402
    hyper_connections as hc,
)
from actor_critic_algs_on_tensorflow_tpu.models import kimi_vl as kv  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import xing  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models.decoder import (  # noqa: E402
    rms_norm,
)
from perfbench.reference import ppo_loss as ref_ppo  # noqa: E402
from perfbench.reference import xing as ref  # noqa: E402

TINY = PRESETS["ppo-xing-tiny"][1]
CFG = TINY["seq_model"]
# The reference reads the published keys as a dict, and what is held:
# as in the cell, the published leading dense layers (2) are not the
# held ones (1).
MODEL = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
MODEL["first_k_dense_replace"] = xing.XingConfig().first_k_dense_replace
HELD = {"num_hidden_layers": CFG.num_hidden_layers,
        "first_k_dense_replace": CFG.first_k_dense_replace,
        "first_expert": CFG.first_expert,
        "experts_held": CFG.experts_held, "vocab_size": CFG.vocab_size}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
T, B = 13, 3


def _model(dtype=jnp.float32, cache_len=T, cfg=CFG):
    return xing.XingActorCritic(cfg=cfg, cache_len=cache_len, dtype=dtype)


def _init(model, seed=0, batch=B):
    tokens = jnp.zeros((1, batch), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(seed), tokens, jnp.zeros((1, batch)),
        model.initialize_carry(batch),
    )
    # Norm weights, mHC gains and biases start at 1, 0.01 or 0: move
    # them, so that a norm that forgot its weight or a mix that forgot a
    # bias would show.
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


# (c) the sequence form against the reference -------------------------------


def test_sequence_forward_equals_reference():
    """Float32 on both sides, the same equations written twice (the
    mixes elementwise here, ``einsum``s there; the Sinkhorn on ``[n, n,
    tokens]`` here, ``[..., n, n]`` there): rounding only, on logits of
    scale ~0.1 and values of scale ~1, so 2e-5; and the counters say the
    residual mix is far from the identity."""
    model = _model()
    params, tokens = _init(model), _tokens()
    logits, values, _, stats = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    ref_logits, ref_values = _reference(params, tokens)
    assert logits.shape == (T, B, CFG.vocab_size) and values.shape == (T, B)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(values, ref_values, atol=2e-5)
    assert 0.3 < float(stats[hc.OFFDIAG_MASS]) < 0.95
    # the reference a step down in precision is NOT within that bound
    low, _ = _reference(params, tokens, dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low - ref_logits))) > 2e-5


# (d) the step form through the cache -----------------------------------------


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


def test_stepping_through_the_cache_equals_the_sequence_pass():
    """The absorbed form over the cache of latents, a token at a time,
    with the four streams carried through every layer, is the expanded
    causal pass (float32, 2e-5); a reset in the middle is a fresh start
    for that env alone."""
    model = _model()
    params, tokens = _init(model), _tokens()
    cut = 6
    resets = jnp.zeros((T, B)).at[cut, 1].set(1.0)
    logits, values, carry, rows = _stepwise(model, params, tokens, resets)
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
    assert carry["layers"].shape == (B, CFG.num_hidden_layers, T,
                                     CFG.cache_width)
    for row in rows:
        assert 0.3 < float(row[hc.OFFDIAG_MASS]) < 0.95
        assert float(row[kv.CACHE_ROWS_READ]) == 1.0


# (e) the trainer's gradients against the reference ----------------------------


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
    # the mHC coefficients are trained: Phi, the gains and the biases
    layer = grads["params"]["layer_1"]
    for name in ("phi", "alpha", "b_pre", "b_post", "b_res"):
        for sub in ("attn_hc_", "ffn_hc_"):
            assert float(jnp.max(jnp.abs(layer[sub + name]))) > 0.0, sub + name


# (f) bfloat16 products, and the steps below the stated precision ------------


def test_at_bfloat16_products_the_system_stays_with_the_stated_reference():
    """bfloat16 inputs to the matrix products, float32 sums and all else
    float32, the residual path included: the program stands well inside
    what rounding the products costs, and a reference whose streams are
    held in bfloat16 (or whose norms run in it) stands further from the
    stated one than the program does."""
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
    for lower in ("streams", "norms"):
        lowered, _ = _reference(params, tokens, products=jnp.bfloat16,
                                lower=(lower,))
        control = _rms(lowered, stated[0])
        assert control > 2 * program, (lower, control, program)


# (g) the expert layer's shares under the hyper-connections --------------------


def test_the_shares_add_up_through_the_residual_mix():
    """Over the 4 shares of the tiny model's 8 experts, 2 held each:
    the parts each share's sublayer adds to the streams (its routed
    terms through ``h_post``), with the shared expert's once, are the
    uncut reference's expert sublayer: the mix is linear in ``F``'s
    output, so the chips' parts still add up."""
    full = dataclasses.replace(CFG, first_expert=0,
                               experts_held=CFG.n_routed_experts)
    model = _model(cfg=full)
    params = _init(model)["params"]["layer_1"]
    X = jax.random.normal(jax.random.PRNGKey(3), (5, 2, CFG.hc_mult,
                                                  CFG.hidden_size))
    streams = X.reshape(5, 2, -1)
    held = CFG.experts_held

    def sublayer(F):
        return hc.sublayer(params, "ffn_hc_", streams, F, full)[0].reshape(
            X.shape)

    def normed(h):
        return rms_norm(h, params["post_norm"], CFG.rms_norm_eps).reshape(
            -1, CFG.hidden_size)

    base = sublayer(lambda h: (jnp.zeros_like(h), None))        # H_res X
    total = sublayer(lambda h: (
        kv.shared_experts(params, normed(h), jnp.float32).reshape(h.shape),
        None,
    ))
    for first in range(0, CFG.n_routed_experts, held):
        share = dataclasses.replace(CFG, first_expert=first)
        mine = dict(params, **{n: params[n][first:first + held]
                               for n in ("w_gate", "w_up", "w_down")})

        def routed(h, share=share, mine=mine):
            y, stats = kv.routed_experts(mine, normed(h), share, jnp.float32)
            assert float(stats["moe_overflow_pairs"]) == 0.0
            return y.reshape(h.shape), None

        total = total + sublayer(routed) - base
    with jax.default_matmul_precision("highest"):
        uncut = ref.mhc(params, "ffn_hc_", X, lambda h: ref.expert_block(
            params, ref._norm(h, params["post_norm"], CFG.rms_norm_eps,
                              ref.Precision()).reshape(-1, CFG.hidden_size),
            MODEL, 0, CFG.n_routed_experts,
        ).reshape(h.shape), MODEL)
    np.testing.assert_allclose(total, uncut, atol=5e-5)


# (h) YaRN ------------------------------------------------------------------------


def test_yarn_at_the_published_numbers_and_at_factor_one():
    """At the published numbers (d 64, theta 1e4, factor 64, original
    4,096, beta 32 / 1): the correction range is [10, 23], the first 10
    frequencies are theta^(-2i/d), from 23 on they are those over 64,
    the ramp is linear between, and the softmax scale is 192^(-1/2)
    (0.1 ln 64 + 1)^2; program and reference agree. With no scaling the
    angles are Kimi-VL's plain rotary."""
    cfg = xing.XingConfig()
    d, theta = 64, 1e4
    plain = theta ** (-np.arange(0, d, 2) / d)
    assert math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(theta))) == 10
    assert math.ceil(d * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(theta))) == 23
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = plain / 64 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(kv.yarn_inv_freq(cfg), want, rtol=1e-12)
    np.testing.assert_allclose(kv.yarn_inv_freq(cfg)[:10], plain[:10])
    np.testing.assert_allclose(kv.yarn_inv_freq(cfg)[23:], plain[23:] / 64)
    np.testing.assert_allclose(ref.inv_freq(MODEL | {"rope_scaling":
                                                     cfg.rope_scaling,
                                                     "qk_rope_head_dim": 64}),
                               want, rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.41589) < 1e-5
    assert kv._softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert ref.softmax_scale(MODEL | {"qk_nope_head_dim": 128,
                                      "qk_rope_head_dim": 64}) == (
        pytest.approx(192 ** -0.5 * m * m, rel=1e-12))
    # factor 1: no range is stretched, the angles are the plain ones
    one = dataclasses.replace(cfg, rope_scaling=tuple(
        (k, 1 if k == "factor" else v) for k, v in cfg.rope_scaling
    ))
    np.testing.assert_allclose(kv.yarn_inv_freq(one), plain, rtol=1e-12)
    assert kv._softmax_scale(one) == 192 ** -0.5
    kimi = kv.KimiVLConfig(rope_theta=theta)
    positions = jnp.arange(7)
    np.testing.assert_allclose(kv._angles(positions, one),
                               kv._angles(positions, kimi), rtol=1e-6)


# (i) the published defaults and the held share ----------------------------------


HELD_SHARE = ("first_expert", "experts_held", "capacity_factor")


def test_defaults_are_the_catalog_row_and_the_share_counts():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    cfg = xing.XingConfig()
    fields = {f.name for f in dataclasses.fields(cfg)} - set(HELD_SHARE)
    assert fields == set(row["config"])
    for key, value in row["config"].items():
        got = getattr(cfg, key)
        assert (dict(got) if key == "rope_scaling" else got) == value, key
    held = PRESETS["ppo-xing-recall"][1]["seq_model"]
    assert held == dataclasses.replace(
        cfg, num_hidden_layers=5, first_k_dense_replace=1,
        vocab_size=16_384, experts_held=8,
    )
    model = xing.XingActorCritic(cfg=held, cache_len=256)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 2)), model.initialize_carry(2),
        )
    )["params"]
    size = lambda t: sum(int(np.prod(x.shape))
                         for x in jax.tree_util.tree_leaves(t))
    assert size(shapes) == 759_350_031
    assert size(shapes["layer_0"]) == 128_196_918
    assert size(shapes["layer_1"]) == 128_426_358
    mhc = {k: v for k, v in shapes["layer_1"].items() if "_hc_" in k}
    assert size(mhc) == 2 * 344_091


@pytest.mark.parametrize("key,value", [
    ("topk_method", "greedy"), ("n_group", 8), ("scoring_func", "softmax"),
    ("num_key_value_heads", 8), ("tie_word_embeddings", True),
    ("rope_scaling", (("type", "yarn"), ("mscale", 1),
                      ("mscale_all_dim", 0.707))),
    ("hc_mult", 0), ("first_k_dense_replace", 3),
])
def test_the_config_refuses_what_is_not_built(key, value):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **{key: value})


# (j) a short run ------------------------------------------------------------------


def test_a_short_run_trains_and_counts():
    """Two iterations through ``make_ppo``: the expert layer's counters,
    the cache's, and both of the hyper-connections'."""
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(1))
    p0 = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
    state, first = fns.iteration(state)
    state, metrics = fns.iteration(state)
    # the update's expanded pass reproduces what the rollout's absorbed
    # steps stored, streams and all
    assert float(first["approx_kl"]) < 1e-5
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["health_finite"]) == 1.0
    assert 0.05 < float(metrics[hc.OFFDIAG_MASS]) < 0.95
    # 3 iterations at the tiny preset: far from converged, but bounded
    assert 0.0 < float(metrics[hc.SINKHORN_RESIDUAL]) < 1.0
    assert float(metrics["moe_overflow_pairs"]) == 0.0
    assert float(metrics[kv.CACHE_ROWS_READ]) == 1.0
    assert float(metrics["episodes"]) == cfg.num_envs
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, state.params
    )
    # Unmoved: the routing bias (no gradient reaches it), and the B_res
    # of the first and the last sublayer: the first's four streams are
    # the same embedding, which a doubly stochastic H_res maps to itself
    # whatever it is, and the last's are summed at the top, which its
    # columns' sums of 1 keep whatever it is. Their gradients are 0 to
    # the iterations' accuracy, and Adam's step rounds away.
    last = f"['layer_{CFG.num_hidden_layers - 1}']['ffn_hc_b_res']"
    still = ("e_score_correction_bias", "['layer_0']['attn_hc_b_res']", last)
    for (path, change) in jax.tree_util.tree_leaves_with_path(moved):
        leaf = jax.tree_util.keystr(path)
        assert (change == 0.0) == any(s in leaf for s in still), leaf
