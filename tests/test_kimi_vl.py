"""The Kimi-VL sequence-policy core (models/kimi_vl.py) against its
plain reference (perfbench/reference/kimi_vl.py) at the tiny preset's
widths on the CPU: the expanded sequence form, the absorbed step form
through the cache of latents, the gate with its selection bias, the
expert layer's shares (models/moe.py, shared with Qwen3-Next), the layer
pattern, and the trainer's two entry points (``collect``,
``block_grads``).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from actor_critic_algs_on_tensorflow_tpu import envs, models  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.algos import (  # noqa: E402
    common,
    evaluation,
)
from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (  # noqa: E402
    PPOConfig,
    make_ppo,
)
from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import kimi_vl as kv  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.ops import pallas_mla_step  # noqa: E402
from perfbench.reference import kimi_vl as ref  # noqa: E402
from perfbench.reference import ppo_loss as ref_ppo  # noqa: E402
from tests.helpers import kernels_interpreted  # noqa: E402

TINY = PRESETS["ppo-kimivl-tiny"][1]
CFG = TINY["seq_model"]
# The reference reads the published keys as a dict, and what is held.
MODEL = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
HELD = {"num_hidden_layers": CFG.num_hidden_layers,
        "first_expert": CFG.first_expert,
        "experts_held": CFG.experts_held, "vocab_size": CFG.vocab_size}
T, B = 19, 3


def _model(dtype=jnp.float32, cache_len=T, cfg=CFG):
    return kv.KimiVLActorCritic(cfg=cfg, cache_len=cache_len, dtype=dtype)


def _init(model, seed=0, batch=B):
    tokens = jnp.zeros((1, batch), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(seed), tokens, jnp.zeros((1, batch)),
        model.initialize_carry(batch),
    )
    # Norm weights start at 1, the value bias at 0, and the selection
    # bias is small: move them, so that a norm that forgot its weight
    # or a gate that forgot its bias would show.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim <= 1 else x
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def _tokens(T=T, B=B, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (T, B), 0, CFG.vocab_size
    )


def _reference(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, tokens, MODEL, HELD, **kw)


# 1. the sequence form against the reference ------------------------------


@pytest.mark.parametrize("dtype,atol", [
    # float32 products: rounding only. bfloat16 products through 3
    # layers: values of scale ~1 agree to a few 1e-2 (the worst is a
    # token whose second and third expert trade places).
    ("float32", 2e-5), ("bfloat16", 5e-2),
])
def test_sequence_forward_equals_reference(dtype, atol):
    model = _model(jnp.dtype(dtype))
    params, tokens = _init(model), _tokens()
    logits, values, _, stats = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    ref_logits, ref_values = _reference(params, tokens)
    assert logits.shape == (T, B, CFG.vocab_size) and values.shape == (T, B)
    np.testing.assert_allclose(logits, ref_logits, atol=atol)
    np.testing.assert_allclose(values, ref_values, atol=atol)
    assert float(stats["moe_overflow_pairs"]) == 0.0
    low_logits, _ = _reference(params, tokens, dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low_logits - ref_logits))) > 2e-5


def test_the_reference_at_the_stated_precision_is_the_programs():
    """bfloat16 inputs to the matrix products, float32 sums and all
    else float32: the reference written from that statement agrees with
    the bfloat16 program far closer than the float32 reference does."""
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), _tokens()
    logits, values, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    ref_logits, ref_values = _reference(params, tokens, products=jnp.bfloat16)
    np.testing.assert_allclose(logits, ref_logits, atol=5e-3)
    np.testing.assert_allclose(values, ref_values, atol=5e-3)
    plain = _reference(params, tokens)
    same = _reference(params, tokens, products=jnp.float32)
    np.testing.assert_allclose(same[0], plain[0], atol=1e-6)


@pytest.mark.parametrize("lower", ["cache", "router", "softmax", "norms"])
def test_each_step_below_the_stated_precision_is_another_function(lower):
    """The cache of latents in 8 bits, and the gate, the softmax and the
    norms each alone in bfloat16, move the reference's outputs by more
    than the program stands from it: the comparison has something to
    see."""
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), _tokens()
    logits, _, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    stated, _ = _reference(params, tokens, products=jnp.bfloat16)
    lowered, _ = _reference(
        params, tokens, products=jnp.bfloat16, lower=(lower,)
    )
    program = float(jnp.sqrt(jnp.mean((logits - stated) ** 2)))
    control = float(jnp.sqrt(jnp.mean((lowered - stated) ** 2)))
    assert control > 2 * program, (control, program)


# 2. the step form through the cache of latents -----------------------------


# The kernel's rows a grid step and envs a block in these tests, and a
# cache of whole chunks that holds the T rows.
CHUNK, BLOCK, CACHE_LEN = 8, 2, 24


@pytest.fixture(params=["plain", "kernel"])
def attention(request, monkeypatch):
    """The step form's scores, softmax and weighted sum as the CPU runs
    them, and once more through the TPU's one-pass kernel in the Pallas
    interpreter (what ``kv._latent_attention`` picks where the program
    is lowered for a TPU), at a latent of 128, a lane tile, and the
    kernel's blocks of ``BLOCK`` envs and ``CHUNK`` rows. Gives the
    model's config and cache length."""
    if request.param == "plain":
        return {"cfg": CFG, "cache_len": T}
    kernels_interpreted(monkeypatch, block_envs=BLOCK, chunk=CHUNK)
    cfg = dataclasses.replace(CFG, kv_lora_rank=128)
    caches = jnp.zeros((B, cfg.num_hidden_layers, CACHE_LEN, cfg.cache_width))
    assert pallas_mla_step.fits(caches, cfg.kv_lora_rank, chunk=CHUNK)
    return {"cfg": cfg, "cache_len": CACHE_LEN}


def _stepwise(model, params, tokens, resets, stats=None):
    carry = model.initialize_carry(tokens.shape[1])
    step = jax.jit(model.apply)
    logits, values = [], []
    for t in range(tokens.shape[0]):
        lg, v, carry, row = step(
            params, tokens[t:t + 1], resets[t:t + 1], carry
        )
        logits.append(lg[0])
        values.append(v[0])
        if stats is not None:
            stats.append(row)
    return jnp.stack(logits), jnp.stack(values), carry


def test_stepping_through_the_cache_equals_the_sequence_pass(attention):
    """The absorbed form over the cache of latents, a token at a time,
    is the expanded causal pass: float32, tight."""
    model = _model(**attention)
    params, tokens = _init(model), _tokens()
    seq_logits, seq_values, _, _ = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    logits, values, carry = _stepwise(
        model, params, tokens, jnp.zeros((T, B))
    )
    np.testing.assert_allclose(logits, seq_logits, atol=2e-5)
    np.testing.assert_allclose(values, seq_values, atol=2e-5)
    assert np.asarray(carry["pos"]).tolist() == [T] * B
    # a layer's cache: rows of latent + rope key an env, no more; the
    # layers' caches are one array
    # array, the envs leading as the trainer shards a carry
    cfg = attention["cfg"]
    assert carry["layers"].shape == (
        B, cfg.num_hidden_layers, attention["cache_len"],
        cfg.kv_lora_rank + cfg.qk_rope_head_dim,
    )


def test_a_reset_mid_way_is_a_fresh_start(attention):
    cut = 8
    model = _model(**attention)
    params, tokens = _init(model), _tokens()
    resets = jnp.zeros((T, B)).at[cut, 1].set(1.0)
    logits, values, _ = _stepwise(model, params, tokens, resets)
    fresh_logits, fresh_values, _, _ = model.apply(
        params, tokens[cut:], jnp.zeros((T - cut, B)), None
    )
    whole_logits, _, _, _ = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    # env 1 starts over at the reset (rows past its position are
    # masked, stale as they are), envs 0 and 2 run on
    np.testing.assert_allclose(logits[cut:, 1], fresh_logits[:, 1], atol=2e-5)
    np.testing.assert_allclose(values[cut:, 1], fresh_values[:, 1], atol=2e-5)
    np.testing.assert_allclose(
        logits[:, [0, 2]], whole_logits[:, [0, 2]], atol=2e-5
    )
    assert float(jnp.max(jnp.abs(logits[cut:, 1] - whole_logits[cut:, 1]))) > 1e-3


def test_the_step_form_counts_the_rows_it_read(attention):
    """``mla_cache_rows_read_share`` of a step's counters: 1 where the
    plain form ran (it reads every row), and through the kernel what its
    index map fetches, at lock-step positions the chunks up to the one
    that holds ``pos`` over the cache's length; a reset env reads one
    chunk again, but its block still fetches what its neighbour needs."""
    model = _model(**attention)
    params, tokens = _init(model, batch=4), _tokens(B=4)
    resets = jnp.zeros((T, 4)).at[10, 1].set(1.0).at[17, 2:].set(1.0)
    stats = []
    _stepwise(model, params, tokens, resets, stats)
    shares = [float(row[kv.CACHE_ROWS_READ]) for row in stats]
    assert all(0.0 < share <= 1.0 for share in shares)
    assert all(set(row) >= {kv.CACHE_ROWS_READ, "moe_overflow_pairs"}
               for row in stats)
    if attention["cache_len"] == T:
        assert shares == [1.0] * T
        return
    chunks = [t // CHUNK + 1 for t in range(T)]
    # env 1 starts over at step 10 but shares its block with env 0;
    # envs 2 and 3, a block of their own, start over at step 17
    chunks[17:] = [(t // CHUNK + 1 + (t - 17) // CHUNK + 1) / 2
                   for t in range(17, T)]
    np.testing.assert_allclose(
        shares, np.asarray(chunks) * CHUNK / CACHE_LEN, rtol=1e-6
    )
    # the sequence form has no cache and no such counter
    _, _, _, seq_stats = model.apply(params, tokens, jnp.zeros((T, 4)), None)
    assert kv.CACHE_ROWS_READ not in seq_stats


def _shapes(jaxpr):
    """Every intermediate's shape, through nested jaxprs."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("form", ["step", "sequence"])
def test_the_step_form_never_rebuilds_a_key_or_a_value(form):
    """Nothing the step form computes has a per-head key or value of
    the cache in it: no intermediate but the layers' caches themselves
    (one array) is larger than a layer's cache of latents, where a
    rebuilt key ``[B, L, heads, d_nope]`` would be ``heads * d_nope /
    (rank + d_rope)`` = 1.6 times it (3.6 times at the published
    widths). The expanded form, for comparison, does hold them."""
    L, batch = 32, 5
    model = _model(cache_len=L)
    params = _init(model, batch=batch)
    nh, dn = CFG.num_attention_heads, CFG.qk_nope_head_dim
    cache = batch * L * (CFG.kv_lora_rank + CFG.qk_rope_head_dim)
    if form == "step":
        args = (_tokens(1, batch), jnp.zeros((1, batch)),
                model.initialize_carry(batch))
    else:
        args = (_tokens(L, batch), jnp.zeros((L, batch)), None)
    shapes = list(_shapes(jax.make_jaxpr(model.apply)(params, *args).jaxpr))
    caches = (batch, CFG.num_hidden_layers, L, CFG.cache_width)
    largest = max(int(np.prod(s)) for s in shapes if s != caches)
    per_head = [s for s in shapes if len(s) == 4 and set(s[:3]) == {batch, L, nh}]
    if form == "step":
        assert largest <= cache, largest
        assert not per_head, per_head
    else:
        assert (batch, L, nh, dn + CFG.v_head_dim) in per_head


# 3. the gate --------------------------------------------------------------


def _expert_params(cfg, seed=0):
    spec = kv.layer_param_spec(cfg, cfg.first_k_dense_replace)
    names = ("router", "e_score_correction_bias", "shared_w_gate",
             "shared_w_up", "shared_w_down", "w_gate", "w_up", "w_down")
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    return {n: 0.2 * jax.random.normal(k, spec[n][0])
            for n, k in zip(names, keys)}


@pytest.mark.parametrize("case", [
    "the bias chooses and does not weigh",
    "the weights renormalise and scale",
    "no gradient reaches the bias",
])
def test_the_gate(case):
    p = _expert_params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.hidden_size))
    experts, weights = kv.route(p, x, CFG)
    k = CFG.num_experts_per_tok
    assert experts.shape == weights.shape == (40, k)
    scores = jax.nn.sigmoid(
        jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    )
    if case == "the bias chooses and does not weigh":
        pushed = dict(p, e_score_correction_bias=(
            p["e_score_correction_bias"].at[5].add(10.0)
        ))
        chosen, pushed_w = kv.route(pushed, x, CFG)
        # every token now takes expert 5 first, which most did not take
        assert (np.asarray(chosen[:, 0]) == 5).all()
        assert (np.asarray(experts) == 5).any(1).mean() < 0.9
        # and weighs it by its score, which the bias has not entered
        picked = jnp.take_along_axis(scores, chosen, -1)
        want = picked / picked.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            pushed_w, want * CFG.routed_scaling_factor, rtol=1e-6
        )
        # without a bias the choice is the plain top-k of the scores
        none = dict(p, e_score_correction_bias=jnp.zeros_like(
            p["e_score_correction_bias"]
        ))
        assert (np.asarray(kv.route(none, x, CFG)[0])
                == np.asarray(jax.lax.top_k(scores, k)[1])).all()
    elif case == "the weights renormalise and scale":
        np.testing.assert_allclose(
            weights.sum(-1), CFG.routed_scaling_factor, rtol=1e-6
        )
        assert CFG.routed_scaling_factor == 2.446
        loose = dataclasses.replace(CFG, norm_topk_prob=False)
        np.testing.assert_allclose(
            kv.route(p, x, loose)[1],
            jnp.take_along_axis(scores, experts, -1) * 2.446, rtol=1e-6,
        )
    else:
        def loss(p):
            y, _ = kv.moe_block(p, x, CFG, jnp.float32)
            return jnp.sum(y * y)

        grads = jax.grad(loss)(p)
        assert float(jnp.max(jnp.abs(grads["e_score_correction_bias"]))) == 0.0
        assert float(jnp.max(jnp.abs(grads["router"]))) > 0.0


def test_the_shares_add_up():
    """The routed parts of all ``n_routed_experts / held`` shares plus
    the shared experts once = the uncut expert block of the reference."""
    p = _expert_params(dataclasses.replace(
        CFG, first_expert=0, experts_held=CFG.n_routed_experts
    ))
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.hidden_size))
    held = CFG.experts_held
    total = kv.shared_experts(p, x, jnp.float32)
    for first in range(0, CFG.n_routed_experts, held):
        share = dataclasses.replace(CFG, first_expert=first)
        mine = dict(p, **{n: p[n][first:first + held]
                          for n in ("w_gate", "w_up", "w_down")})
        routed, stats = kv.routed_experts(mine, x, share, jnp.float32)
        assert float(stats["moe_overflow_pairs"]) == 0.0
        total = total + routed
        # one share alone is the reference given the same share
        with jax.default_matmul_precision("highest"):
            want = ref.expert_block(mine, x, MODEL, first, held)
        np.testing.assert_allclose(
            routed + kv.shared_experts(p, x, jnp.float32), want, atol=2e-5
        )
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_block(p, x, MODEL, 0, CFG.n_routed_experts)
    np.testing.assert_allclose(total, uncut, atol=5e-5)


# 4. the layer pattern --------------------------------------------------------


def test_the_first_layer_is_dense_and_the_rest_are_expert_layers():
    params = _init(_model())["params"]
    assert [CFG.is_expert_layer(i) for i in range(3)] == [False, True, True]
    dense, expert = set(params["layer_0"]), set(params["layer_1"])
    assert {"mlp_gate", "mlp_up", "mlp_down"} <= dense
    assert not {"router", "w_gate", "shared_w_gate"} & dense
    assert {"router", "e_score_correction_bias", "w_gate",
            "shared_w_down"} <= expert and "mlp_gate" not in expert
    assert set(params["layer_2"]) == expert
    # both kinds hold the whole latent attention
    mla = {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}
    assert mla <= dense and mla <= expert
    I = CFG.moe_intermediate_size
    assert params["layer_0"]["mlp_gate"].shape == (64, CFG.intermediate_size)
    assert params["layer_1"]["w_gate"].shape == (CFG.experts_held, 64, I)
    # the two shared experts: one SwiGLU of twice the routed width
    assert params["layer_1"]["shared_w_gate"].shape == (64, 2 * I)
    # the published pattern: one dense layer, then 26 expert layers
    whole = kv.KimiVLConfig()
    assert sum(map(whole.is_expert_layer, range(27))) == 26
    assert not whole.is_expert_layer(0)
    two = dataclasses.replace(CFG, first_k_dense_replace=2)
    assert [two.is_expert_layer(i) for i in range(3)] == [False, False, True]


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("topk_method", "greedy"), ("n_group", 8),
    ("topk_group", 4), ("scoring_func", "softmax"),
    ("num_hidden_layers", 1),
])
def test_the_config_refuses_what_is_not_built(key, value):
    with pytest.raises(ValueError, match=key if key != "num_hidden_layers"
                       else "no expert layer"):
        dataclasses.replace(CFG, **{key: value})


# 5. the trainer's entry points against the reference ------------------------


@pytest.fixture(scope="module")
def trainer():
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    return cfg, fns, fns.init(jax.random.PRNGKey(4))


def test_collect_stores_the_reference_log_probs_and_values(trainer):
    cfg, fns, state = trainer
    traj, carry0 = fns.collect(state)
    assert traj.obs.shape == (cfg.rollout_length, cfg.num_envs)
    assert traj.obs.dtype == jnp.int32
    assert float(jnp.max(carry0["core"]["pos"])) == 0.0
    logits, values = _reference(state.params, traj.obs)
    log_probs, _ = ref.categorical(logits, traj.actions)
    np.testing.assert_allclose(traj.log_probs, log_probs, atol=2e-5)
    np.testing.assert_allclose(traj.values, values, atol=2e-5)
    assert np.asarray(traj.dones[-1]).all() and not np.asarray(
        traj.dones[:-1]
    ).any()


def test_block_grads_equal_the_reference_loss_and_gradients(trainer):
    cfg, fns, state = trainer
    traj, _ = fns.collect(state)
    T, B = traj.obs.shape
    noise = jax.random.normal(jax.random.PRNGKey(8), (3, T, B))
    adv, ret = ref_ppo.gae(
        traj.rewards + 0.3 * noise[0], traj.values, traj.dones,
        jnp.zeros((B,)), cfg.gamma, cfg.gae_lambda,
    )
    block = {
        "obs": traj.obs, "actions": traj.actions,
        # old log-probs scattered so that a share of the ratios clips
        "old_log_probs": traj.log_probs + 0.15 * noise[1],
        "old_values": traj.values + 0.1 * noise[2],
        "advantages": adv, "returns": ret,
        "resets": jnp.zeros((T, B)), "core": None,
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
    flat = lambda t: np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(t)]
    )
    g, g_ref = flat(grads), flat(ref_grads)
    assert np.linalg.norm(g_ref) > 1e-3
    np.testing.assert_allclose(g, g_ref, atol=2e-5 * np.abs(g_ref).max())
    bias = [grads["params"][f"layer_{i}"]["e_score_correction_bias"]
            for i in (1, 2)]
    assert all(float(jnp.max(jnp.abs(b))) == 0.0 for b in bias)


def test_a_short_run_trains_and_counts():
    cfg = PPOConfig(**dict(TINY, lr=1e-3))
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(1))
    p0 = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
    for _ in range(2):
        state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["health_finite"]) == 1.0
    assert float(metrics["moe_overflow_pairs"]) == 0.0
    assert 0.0 < float(metrics["moe_local_pairs_per_token"]) < 2.0
    assert 0.0 < float(metrics["moe_experts_touched_share"]) <= 1.0
    for phase in ("rollout", "update"):
        assert 0.0 < float(metrics[f"moe_buffer_fill_share_{phase}"]) <= 1.0
        used = float(metrics[f"moe_buffer_rows_used_share_{phase}"])
        assert 0.0 < used <= 1.0
    # the plain form ran (the CPU, a narrow cache): every row, every step
    assert float(metrics[kv.CACHE_ROWS_READ]) == 1.0
    assert float(metrics["episodes"]) == cfg.num_envs
    assert fns.steps_per_iteration == cfg.num_envs * cfg.rollout_length
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, state.params
    )["params"]
    # everything trains but the selection bias, which nothing updates
    for name, layer in moved.items():
        for leaf, change in (layer.items() if isinstance(layer, dict)
                             else [(name, layer)]):
            assert (change == 0.0) == (leaf == "e_score_correction_bias"), (
                name, leaf, change
            )


def test_the_carry_is_sharded_by_env_on_two_devices():
    """The trainer shards every leaf of a carry on its leading axis as
    the env axis (``common.state_specs``): on two devices each holds
    its half of the envs, ALL layers of their caches, and ``init`` and
    an iteration run (the tiny preset pins one device, and the
    benchmark's cell has one chip: nothing else sees a mesh)."""
    cfg = PPOConfig(**dict(TINY, num_devices=2))
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(2))
    model = cfg.seq_model
    whole = (cfg.num_envs, model.num_hidden_layers, cfg.rollout_length,
             model.cache_width)
    for _ in range(2):
        core = state.carry["core"]
        assert core["layers"].shape == whole
        assert core["layers"].sharding.shard_shape(whole) == (
            cfg.num_envs // 2,) + whole[1:]
        assert core["pos"].sharding.shard_shape(core["pos"].shape) == (
            cfg.num_envs // 2,)
        state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["episodes"]) == cfg.num_envs
    assert float(metrics[kv.CACHE_ROWS_READ]) == 1.0


# 6. dispatch by capability --------------------------------------------------


def test_the_sequence_cores_are_a_table():
    assert set(models.SEQUENCE_CORES) == {
        "qwen3_next", "kimi_vl", "sdar", "granite_hybrid", "xing", "lfm2"
    }
    for torso in models.SEQUENCE_CORES:
        core, config = models.sequence_core(torso)
        assert core.replays_from_empty_carry is True
        assert callable(core.iteration_stats)
        fields = {f.name for f in dataclasses.fields(config)}
        assert "vocab_size" in fields
        # a share of an expert layer is asked only of a core that has one
        expert_share = {"first_expert", "experts_held", "capacity_factor"}
        if torso == "granite_hybrid":
            assert not expert_share & fields
        else:
            assert expert_share <= fields


@pytest.mark.parametrize("torso", sorted(models.SEQUENCE_CORES))
def test_refusals_name_the_table_and_not_a_model(torso):
    presets = {"qwen3_next": "ppo-qwen3next-tiny",
               "kimi_vl": "ppo-kimivl-tiny", "sdar": "ppo-sdar-tiny",
               "granite_hybrid": "ppo-granite-tiny", "xing": "ppo-xing-tiny",
               "lfm2": "ppo-lfm2-tiny"}
    preset = presets[torso]
    tiny = PRESETS[preset][1]
    with pytest.raises(ValueError, match="episode_length"):
        make_ppo(PPOConfig(**dict(tiny, rollout_length=8)))
    with pytest.raises(ValueError, match="SEQUENCE_CORES.*recurrent=True"):
        make_ppo(PPOConfig(**dict(tiny, recurrent=False)))
    # the other core's config is not this core's
    other = next(PRESETS[p][1]["seq_model"]
                 for p in presets.values() if p != preset)
    env, env_params = envs.make(tiny["env"], num_envs=1,
                                params=tiny["env_params"])
    with pytest.raises(ValueError, match="SEQUENCE_CORES"):
        common.make_recurrent_policy_head(
            env.action_space(env_params), torso=torso, hidden_sizes=(),
            lstm_size=0, compute_dtype="float32", seq_model=other,
        )
    with pytest.raises(NotImplementedError, match="SEQUENCE_CORES"):
        evaluation._act_fn("ppo", PPOConfig(**tiny), None, None, False)
