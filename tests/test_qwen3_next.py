"""The Qwen3-Next sequence-policy core (models/qwen3_next.py) against
its plain reference (perfbench/reference/qwen3_next.py) at the tiny
preset's widths on the CPU: the sequence form, the step form through
the carry, the chunked Gated DeltaNet against the recurrence, the
expert layer's shares and its dropless dispatch, and the trainer's two
entry points (``collect``, ``block_grads``).
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

from actor_critic_algs_on_tensorflow_tpu import envs  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (  # noqa: E402
    PPOConfig,
    make_ppo,
)
from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import qwen3_next as qn  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.ops import pallas_delta_step  # noqa: E402
from perfbench.reference import ppo_loss as ref_ppo  # noqa: E402
from perfbench.reference import qwen3_next as ref  # noqa: E402
from tests.helpers import chunk_matrices, kernels_interpreted  # noqa: E402

TINY = PRESETS["ppo-qwen3next-tiny"][1]
CFG = TINY["seq_model"]
CHUNK = CFG.chunk_size
# The reference reads the published keys as a dict, and what is held.
MODEL = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
HELD = {"num_hidden_layers": CFG.num_hidden_layers,
        "first_expert": CFG.first_expert,
        "experts_held": CFG.experts_held, "vocab_size": CFG.vocab_size}


def _model(dtype=jnp.float32, cache_len=2 * CHUNK + 3, cfg=CFG):
    return qn.Qwen3NextActorCritic(cfg=cfg, cache_len=cache_len, dtype=dtype)


def _init(model, seed=0, batch=3):
    tokens = jnp.zeros((1, batch), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(seed), tokens, jnp.zeros((1, batch)),
        model.initialize_carry(batch),
    )
    # Norm weights and the value bias start at 0 (or 1): move them, so
    # that a norm that forgot its weight would show.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim <= 1 else x
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def _tokens(T, B, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (T, B), 0, CFG.vocab_size
    )


def _reference(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, tokens, MODEL, HELD, **kw)


# 1. the sequence form against the reference ------------------------------


@pytest.mark.parametrize("dtype,atol", [
    # float32 products: rounding only. bfloat16 products (8 bits of
    # mantissa, float32 accumulation) through 4 layers: values of
    # scale ~1 agree to a few 1e-2 (the worst is a token whose tenth
    # and eleventh expert trade places under the rounding).
    ("float32", 2e-5), ("bfloat16", 5e-2),
])
def test_sequence_forward_equals_reference(dtype, atol):
    T, B = 2 * CHUNK + 3, 3
    model = _model(jnp.dtype(dtype))
    params, tokens = _init(model), _tokens(T, B)
    logits, values, _, stats = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    ref_logits, ref_values = _reference(params, tokens)
    assert logits.shape == (T, B, CFG.vocab_size) and values.shape == (T, B)
    np.testing.assert_allclose(logits, ref_logits, atol=atol)
    np.testing.assert_allclose(values, ref_values, atol=atol)
    assert float(stats["moe_overflow_pairs"]) == 0.0
    # The reference a step down in precision is NOT within the float32
    # bound: the bound tells the two apart.
    low_logits, _ = _reference(params, tokens, dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low_logits - ref_logits))) > 2e-5


def test_the_reference_at_the_stated_precision_is_the_programs():
    """bfloat16 inputs to the matrix products, float32 sums and all
    else float32: written down from that statement, the reference
    agrees with the bfloat16 program ten times closer than the float32
    reference does, and is no other function where the products are
    float32."""
    T, B = 2 * CHUNK + 3, 3
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), _tokens(T, B)
    logits, values, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    ref_logits, ref_values = _reference(params, tokens, products=jnp.bfloat16)
    np.testing.assert_allclose(logits, ref_logits, atol=5e-3)
    np.testing.assert_allclose(values, ref_values, atol=5e-3)
    plain = _reference(params, tokens)
    same = _reference(params, tokens, products=jnp.float32)
    np.testing.assert_allclose(same[0], plain[0], atol=1e-6)
    np.testing.assert_allclose(same[1], plain[1], atol=1e-6)


@pytest.mark.parametrize("lower", ["state", "router", "norms"])
def test_each_step_below_the_stated_precision_is_another_function(lower):
    """The DeltaNet's state, the router's softmax and the norms, each
    alone in bfloat16, move the reference's outputs by more than the
    program stands from it: the comparison has something to see."""
    T, B = 2 * CHUNK + 3, 3
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), _tokens(T, B)
    logits, _, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    stated, _ = _reference(params, tokens, products=jnp.bfloat16)
    lowered, _ = _reference(
        params, tokens, products=jnp.bfloat16, lower=(lower,)
    )
    program = float(jnp.sqrt(jnp.mean((logits - stated) ** 2)))
    control = float(jnp.sqrt(jnp.mean((lowered - stated) ** 2)))
    assert control > 2 * program, (control, program)


# 2. the step form through the carry ---------------------------------------


@pytest.fixture(params=["plain", "kernel"])
def state_step(request, monkeypatch):
    """The config to build the model with: the step form's state update
    as the CPU runs it, and once more through the TPU's one-pass kernel
    in the Pallas interpreter (what ``qn._state_step`` picks where the
    program is lowered for a TPU), at value heads of 128, a state that
    tiles the vector unit."""
    if request.param == "plain":
        return CFG
    kernels_interpreted(monkeypatch)
    cfg = dataclasses.replace(CFG, linear_value_head_dim=128)
    assert pallas_delta_step.fits(jnp.zeros((
        1, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim,
    )))
    return cfg


def _stepwise(model, params, tokens, resets):
    carry = model.initialize_carry(tokens.shape[1])
    step = jax.jit(model.apply)
    logits, values = [], []
    for t in range(tokens.shape[0]):
        lg, v, carry, _ = step(
            params, tokens[t:t + 1], resets[t:t + 1], carry
        )
        logits.append(lg[0])
        values.append(v[0])
    return jnp.stack(logits), jnp.stack(values), carry


def test_stepping_through_the_carry_equals_the_sequence_pass(state_step):
    """DeltaNet state, convolution tail, key/value cache and rotary
    position, over two chunks and a ragged tail."""
    T, B = 2 * CHUNK + 3, 3
    model = _model(cfg=state_step)
    params, tokens = _init(model), _tokens(T, B)
    seq_logits, seq_values, _, _ = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    logits, values, carry = _stepwise(
        model, params, tokens, jnp.zeros((T, B))
    )
    np.testing.assert_allclose(logits, seq_logits, atol=2e-5)
    np.testing.assert_allclose(values, seq_values, atol=2e-5)
    assert np.asarray(carry["pos"]).tolist() == [T] * B


def test_a_reset_mid_way_is_a_fresh_start(state_step):
    T, B, cut = 2 * CHUNK + 3, 3, CHUNK + 2
    model = _model(cfg=state_step)
    params, tokens = _init(model), _tokens(T, B)
    resets = jnp.zeros((T, B)).at[cut, 1].set(1.0)
    logits, values, _ = _stepwise(model, params, tokens, resets)
    fresh_logits, fresh_values, _, _ = model.apply(
        params, tokens[cut:], jnp.zeros((T - cut, B)), None
    )
    whole_logits, _, _, _ = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    # env 1 starts over at the reset, envs 0 and 2 run on
    np.testing.assert_allclose(logits[cut:, 1], fresh_logits[:, 1], atol=2e-5)
    np.testing.assert_allclose(values[cut:, 1], fresh_values[:, 1], atol=2e-5)
    np.testing.assert_allclose(
        logits[:, [0, 2]], whole_logits[:, [0, 2]], atol=2e-5
    )
    assert float(jnp.max(jnp.abs(logits[cut:, 1] - whole_logits[cut:, 1]))) > 1e-3


# 3. chunked DeltaNet against the recurrence --------------------------------


def _delta_inputs(T, b=2, h=3, dk=8, dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, h, T, dk))
    k = jax.random.normal(ks[1], (b, h, T, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, h, T, dv))
    g = -jax.random.uniform(ks[3], (b, h, T), minval=0.01, maxval=2.0)
    beta = jax.random.uniform(ks[4], (b, h, T))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    def step(S, xs):
        S, o = qn.gated_delta_step(S, *xs)
        return S, o

    t_first = lambda x: jnp.moveaxis(x, 2, 0)
    S0 = jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]))
    S, o = jax.lax.scan(step, S0, tuple(map(t_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2), S


@pytest.mark.parametrize("T", [2 * 8 + 3, 8, 5])
def test_chunked_deltanet_equals_the_recurrence(T):
    """Forward, final state and gradient, at lengths that are not a
    multiple of the chunk (8), one chunk exactly, and less than one."""
    args = _delta_inputs(T)
    o, S = qn.chunk_gated_delta_rule(*args, chunk=8)
    o_ref, S_ref = _recurrence(*args)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, atol=2e-5)

    weight = jax.random.normal(jax.random.PRNGKey(9), o.shape)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0] * weight)
    grads = jax.grad(
        loss(lambda *a: qn.chunk_gated_delta_rule(*a, chunk=8)),
        argnums=(0, 1, 2, 3, 4),
    )(*args)
    grads_ref = jax.grad(loss(_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(grads, grads_ref):
        np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("c", [8, 64])
@pytest.mark.parametrize("repeated", [False, True])
def test_the_solve_through_the_inverse_equals_the_triangular_solve(
    c, repeated
):
    """``solve_unit_lower`` (the inverse kept, its derivative two products
    with it) against ``solve_triangular`` and JAX's own derivative of
    it: the value and both gradients, with random keys and with keys
    that repeat inside a chunk (entries of ``a`` near 1)."""
    a = chunk_matrices((2, 3), c, repeated)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    rhs = jax.random.normal(ks[0], (2, 3, c, 12))
    dX = jax.random.normal(ks[1], rhs.shape)

    def triangular(a, rhs):
        return jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c), rhs, lower=True, unit_diagonal=True
        )

    got, got_vjp = jax.vjp(qn.solve_unit_lower, a, rhs)
    want, want_vjp = jax.vjp(triangular, a, rhs)
    for x, y in zip((got, *got_vjp(dX)), (want, *want_vjp(dX))):
        scale = float(jnp.max(jnp.abs(y)))
        assert scale > 0.1
        np.testing.assert_allclose(x, y, atol=1e-5 * scale)


def test_the_chunked_deltanet_through_the_kernel_equals_the_recurrence(
    monkeypatch
):
    """The chunked rule with its inverse taken by the TPU's kernel (in the
    Pallas interpreter): 2 chunks x 2 envs x 64 heads are two lane tiles
    of matrices. Forward, final state and gradient."""
    kernels_interpreted(monkeypatch)
    args = _delta_inputs(16, b=2, h=64)
    _, _, share = qn._chunk_rule(*args, chunk=8)
    assert float(share) == 1.0
    o, S = qn.chunk_gated_delta_rule(*args, chunk=8)
    o_ref, S_ref = _recurrence(*args)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, atol=2e-5)
    weight = jax.random.normal(jax.random.PRNGKey(9), o.shape)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0] * weight)
    grads = jax.grad(
        loss(lambda *a: qn.chunk_gated_delta_rule(*a, chunk=8)),
        argnums=(0, 1, 2, 3, 4),
    )(*args)
    grads_ref = jax.grad(loss(_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(grads, grads_ref):
        np.testing.assert_allclose(got, want, atol=1e-4)


# 4. and 5. the expert layer ---------------------------------------------


def _expert_params(cfg, seed=0):
    spec = qn.layer_param_spec(cfg, 0)
    names = ("router", "shared_gate", "shared_w_gate", "shared_w_up",
             "shared_w_down", "w_gate", "w_up", "w_down")
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    return {n: 0.2 * jax.random.normal(k, spec[n][0])
            for n, k in zip(names, keys)}


def test_the_shares_add_up():
    """The routed parts of all ``num_experts / held`` shares plus the
    shared expert once = the uncut expert block of the reference."""
    whole = dataclasses.replace(CFG, first_expert=0,
                                experts_held=CFG.num_experts)
    p = _expert_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.hidden_size))
    held = CFG.experts_held
    total = qn.shared_expert(p, x, jnp.float32)
    for first in range(0, CFG.num_experts, held):
        share = dataclasses.replace(CFG, first_expert=first)
        mine = dict(p, **{n: p[n][first:first + held]
                          for n in ("w_gate", "w_up", "w_down")})
        routed, stats = qn.routed_experts(mine, x, share, jnp.float32)
        assert float(stats["moe_overflow_pairs"]) == 0.0
        total = total + routed
        # one share alone is the reference given the same share
        with jax.default_matmul_precision("highest"):
            want = ref.expert_block(mine, x, MODEL, first, held)
        np.testing.assert_allclose(
            routed + qn.shared_expert(p, x, jnp.float32), want, atol=2e-5
        )
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_block(p, x, MODEL, 0, CFG.num_experts)
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def _biased(cfg, seed=0):
    """Every token's first choice is expert ``first_expert``: the
    inputs share a component that the router reads for that expert."""
    p = _expert_params(cfg, seed)
    p["router"] = p["router"].at[0, cfg.first_expert].set(20.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.hidden_size))
    return p, x.at[:, 0].set(1.0)


def test_dispatch_is_dropless_under_a_skewed_router():
    p, x = _biased(CFG)
    y, stats = qn.moe_block(p, x, CFG, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_block(p, x, MODEL, CFG.first_expert,
                                CFG.experts_held)
    assert float(stats["moe_local_pairs_per_token"]) >= 1.0
    assert float(stats["moe_expert_load_max_over_mean"]) > 1.5
    assert float(stats["moe_overflow_pairs"]) == 0.0
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_the_overflow_counter_counts():
    small = dataclasses.replace(CFG, capacity_factor=0.5)
    p, x = _biased(small)
    _, stats = qn.moe_block(p, x, small, jnp.float32)
    rows = small.moe_capacity(x.shape[0])
    pairs = float(stats["moe_local_pairs_per_token"]) * x.shape[0]
    assert pairs > rows
    assert float(stats["moe_overflow_pairs"]) == pairs - rows


@pytest.mark.parametrize("capacity_factor,full", [
    (4.0, False), (2.5, False), (2.0, True), (0.5, True),
])
def test_the_buffer_fill_is_the_kept_pairs_over_the_rows(
    capacity_factor, full
):
    """``moe_buffer_fill_share``: the dispatch buffer's rows that hold
    a pair, over its rows. Below 1 while the buffer holds every local
    pair, exactly 1 once pairs overflow."""
    cfg = dataclasses.replace(CFG, capacity_factor=capacity_factor)
    p, x = _biased(cfg)
    _, stats = qn.moe_block(p, x, cfg, jnp.float32)
    rows = cfg.moe_capacity(x.shape[0])
    pairs = float(stats["moe_local_pairs_per_token"]) * x.shape[0]
    kept = pairs - float(stats["moe_overflow_pairs"])
    fill = float(stats["moe_buffer_fill_share"])
    assert fill == pytest.approx(kept / rows, rel=1e-6)
    assert 0.0 < fill <= 1.0
    assert (fill == 1.0) == full == (pairs > rows)


@pytest.mark.parametrize("core", ["qwen3_next", "kimi_vl", "sdar"])
def test_an_iteration_reports_the_buffer_fill_of_both_phases(core):
    """Every core's ``iteration_stats`` carries the fill twice: the
    mean over the rollout's steps and the mean over the update's
    blocks, whose buffers differ in rows by orders of magnitude."""
    from actor_critic_algs_on_tensorflow_tpu import models

    model, _ = models.sequence_core(core)
    module = sys.modules[model.__module__]
    steps, blocks = 6, 2
    counters = ("moe_local_pairs_per_token", "moe_expert_load_max_over_mean",
                "moe_overflow_pairs", "moe_experts_touched_share")
    rollout = {k: jnp.zeros((steps,)) for k in counters}
    update = {k: jnp.zeros((1, blocks)) for k in counters}
    rollout["moe_buffer_fill_share"] = jnp.linspace(0.1, 0.2, steps)
    update["moe_buffer_fill_share"] = jnp.asarray([[0.25, 0.75]])
    rollout["moe_buffer_rows_used_share"] = jnp.linspace(0.2, 0.4, steps)
    update["moe_buffer_rows_used_share"] = jnp.asarray([[0.375, 1.0]])
    # what else a core's reduction reads: its own counters, one row a
    # step or a block
    for name in ("CACHE_ROWS_READ", "COMMITTED_TOKENS"):
        if hasattr(module, name):
            rollout[getattr(module, name)] = jnp.ones((steps,))
    for name in ("REVEALED_POSITIONS", "DENOISE_PASSES", "POSITIONS",
                 "SCORE_TILES_COMPUTED", "SOLVE_KERNEL_SHARE"):
        if hasattr(module, name):
            update[getattr(module, name)] = jnp.ones((1, blocks))
    stats = jax.vmap(
        lambda r, u: model.iteration_stats(r, u, "data"),
        axis_name="data",
    )(*jax.tree_util.tree_map(lambda x: x[None], (rollout, update)))
    np.testing.assert_allclose(
        stats["moe_buffer_fill_share_rollout"], [0.15], rtol=1e-6
    )
    np.testing.assert_allclose(
        stats["moe_buffer_fill_share_update"], [0.5], rtol=1e-6
    )
    assert "moe_buffer_fill_share" not in stats
    np.testing.assert_allclose(
        stats["moe_buffer_rows_used_share_rollout"], [0.3], rtol=1e-6
    )
    np.testing.assert_allclose(
        stats["moe_buffer_rows_used_share_update"], [0.6875], rtol=1e-6
    )


@pytest.mark.parametrize("tokens,touched", [(64, 1.0), (1, 0.5), (0, 0.0)])
def test_the_touched_experts_are_counted(tokens, touched):
    """The held experts a call gave a row, as a share of the two held:
    both with 64 tokens (each sends a pair to the favoured expert and
    most of them one to the other), one with a single token whose
    second choice lies elsewhere, none where no token has a pair
    here."""
    p, x = _biased(CFG)
    if tokens == 1:
        far, _ = qn.route(p, x, CFG)
        far = np.flatnonzero((np.asarray(far) != CFG.first_expert + 1).all(1))
        x = x[far[:1]]
    elif tokens == 0:
        x = x.at[:, 0].set(-1.0)  # the favoured expert is now the last choice
        far, _ = qn.route(p, x, CFG)
        held = np.isin(np.asarray(far), [CFG.first_expert,
                                        CFG.first_expert + 1])
        x = x[np.flatnonzero(~held.any(1))[:8]]
    _, stats = qn.moe_block(p, x, CFG, jnp.float32)
    assert float(stats["moe_experts_touched_share"]) == touched


# 6. the trainer's entry points against the reference ------------------------


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
    # every env's episode is the rollout
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


# 7. refusals and the token env ------------------------------------------------


def test_make_ppo_refuses_an_episode_that_is_not_the_rollout():
    with pytest.raises(ValueError, match="episode_length"):
        make_ppo(PPOConfig(**dict(TINY, rollout_length=8)))
    with pytest.raises(ValueError, match="recurrent=True"):
        make_ppo(PPOConfig(**dict(TINY, recurrent=False)))


def test_token_recall_reward_and_reset_against_a_numpy_loop():
    params = envs.TokenRecallParams(vocab_size=5, delay=3, episode_length=7)
    env, params = envs.make("TokenRecallTPU-v0", num_envs=4, params=params)
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (4,) and obs.dtype == jnp.int32
    step = jax.jit(lambda s, a, k: env.step(k, s, a, params))
    shown = [[int(o)] for o in obs]  # this episode's tokens, an env
    rng = np.random.default_rng(0)
    earned = 0
    for t in range(20):
        # env 0 always recalls, env 1 never does, the others guess
        actions = np.array([
            shown[e][-1 - 3] if len(shown[e]) > 3 else 0 for e in range(4)
        ])
        actions[1] = (actions[1] + 1) % 5
        actions[2:] = rng.integers(0, 5, 2)
        want = np.array([
            float(len(shown[e]) > 3 and actions[e] == shown[e][-1 - 3])
            for e in range(4)
        ])
        state, obs, reward, done, info = step(
            state, jnp.asarray(actions), jax.random.PRNGKey(100 + t)
        )
        np.testing.assert_array_equal(reward, want)
        earned += reward[0]
        ends = (t + 1) % 7 == 0
        assert np.asarray(done).tolist() == [float(ends)] * 4
        assert np.asarray(info["terminated"]).tolist() == [0.0] * 4
        for e in range(4):
            shown[e] = [int(obs[e])] if ends else shown[e] + [int(obs[e])]
        assert int(np.max(obs)) < 5
    assert earned == 2 * 4 + 3  # steps 3..6 of two episodes, 3..5 of a third


# 8. a short run ----------------------------------------------------------------


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
    assert float(metrics["episodes"]) == cfg.num_envs
    # the tiny widths' chunks do not tile: the triangular solve ran
    assert float(metrics[qn.SOLVE_KERNEL_SHARE]) == 0.0
    assert int(state.step) == 2
    assert fns.steps_per_iteration == cfg.num_envs * cfg.rollout_length
    counts = [int(x) for path, x in
              jax.tree_util.tree_leaves_with_path(state.opt_state)
              if jax.tree_util.keystr(path).endswith("count")]
    assert counts and set(counts) == {
        2 * cfg.num_epochs * cfg.num_minibatches
    }
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, state.params
    )
    assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
