"""The SDAR sequence-policy core (models/sdar.py), a language model that
generates by diffusion over blocks, against its plain reference
(perfbench/reference/sdar.py) at the tiny preset's widths on the CPU,
on trajectories the env produced (envs/block_turns.py through
``make_ppo``'s ``collect``): the sequence form under the block-diffusion
mask, the step form through the key/value cache that only a commit pass
extends, the mask itself, the expert layer's shares (models/moe.py,
shared with the other cores), and the trainer's two entry points.
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

from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (  # noqa: E402
    PPOConfig,
    make_ppo,
)
from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.models import sdar  # noqa: E402
from actor_critic_algs_on_tensorflow_tpu.ops import BlockReveal  # noqa: E402
from perfbench.reference import ppo_loss as ref_ppo  # noqa: E402
from perfbench.reference import sdar as ref  # noqa: E402
from tests.helpers import kernels_interpreted  # noqa: E402

TINY = PRESETS["ppo-sdar-tiny"][1]
CFG = TINY["seq_model"]
ENV = TINY["env_params"]
MASK, L = CFG.mask_token_id, CFG.block_length
# The reference reads the published keys as a dict, and what is held.
MODEL = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
HELD = {"num_hidden_layers": CFG.num_hidden_layers,
        "first_expert": CFG.first_expert,
        "experts_held": CFG.experts_held, "vocab_size": CFG.vocab_size,
        "mask_token_id": MASK}
T, B = TINY["rollout_length"], 3
CACHE_LEN = ENV.tokens_per_episode


def _model(dtype=jnp.float32, cfg=CFG):
    return sdar.SDARActorCritic(cfg=cfg, cache_len=CACHE_LEN, dtype=dtype)


def _init(model, seed=0, batch=B):
    tokens = jnp.zeros((1, batch, L), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(seed), tokens, jnp.zeros((1, batch)),
        model.initialize_carry(batch),
    )
    # Norm weights start at 1 and the value bias at 0: move them, so
    # that a norm that forgot its weight would show.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim <= 1 else x
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(scope="module")
def trainer():
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    return cfg, fns, fns.init(jax.random.PRNGKey(4))


@pytest.fixture(scope="module")
def trajectory(trainer):
    """Passes the env and the sampler produced, ``[T, B, L]``: of every
    turn's six the first and the last show a clean block."""
    _, fns, state = trainer
    traj, _ = fns.collect(state)
    tokens = traj.obs[:, :B]
    commit = np.asarray(sdar.is_commit(tokens, CFG))
    assert commit.reshape(-1, 6, B).all(-1).tolist() == [
        [True, False, False, False, False, True]
    ] * ENV.turns
    return tokens


def _reference(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, tokens, MODEL, HELD, **kw)


def _masked(tokens):
    return np.asarray(tokens == MASK)


# 1. the sequence form against the reference -------------------------------


@pytest.mark.parametrize("dtype,atol", [
    # float32 products: rounding only. bfloat16 products through 2
    # layers: logits of scale ~1 agree to a few 1e-2 (the worst is a
    # position whose second and third expert trade places).
    ("float32", 2e-5), ("bfloat16", 5e-2),
])
def test_sequence_forward_equals_reference(trajectory, dtype, atol):
    model = _model(jnp.dtype(dtype))
    params, tokens = _init(model), trajectory
    logits, values, _, stats = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    ref_logits, ref_values = _reference(params, tokens)
    assert logits.shape == (T, B, L, CFG.vocab_size)
    assert values.shape == (T, B)
    # a mask is never an action, on either side
    assert np.isneginf(np.asarray(logits[..., MASK])).all()
    assert np.isneginf(np.asarray(ref_logits[..., MASK])).all()
    np.testing.assert_allclose(
        logits[..., :MASK], ref_logits[..., :MASK], atol=atol
    )
    np.testing.assert_allclose(values, ref_values, atol=atol)
    assert float(stats["moe_overflow_pairs"]) == 0.0
    low_logits, _ = _reference(params, tokens, dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(
        low_logits[..., :MASK] - ref_logits[..., :MASK]
    ))) > 2e-5


def test_the_reference_at_the_stated_precision_is_the_programs(trajectory):
    """bfloat16 inputs to the matrix products, float32 sums and all
    else float32: the reference written from that statement agrees with
    the bfloat16 program far closer than the float32 reference does."""
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), trajectory
    logits, values, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    ref_logits, ref_values = _reference(params, tokens, products=jnp.bfloat16)
    np.testing.assert_allclose(
        logits[..., :MASK], ref_logits[..., :MASK], atol=5e-3
    )
    np.testing.assert_allclose(values, ref_values, atol=5e-3)
    plain = _reference(params, tokens)
    same = _reference(params, tokens, products=jnp.float32)
    np.testing.assert_allclose(
        same[0][..., :MASK], plain[0][..., :MASK], atol=1e-6
    )


@pytest.mark.parametrize("lower", ["cache", "router", "softmax", "norms"])
def test_each_step_below_the_stated_precision_is_another_function(
    trajectory, lower
):
    """Keys and values in 8 bits, and the router, the softmax and the
    norms each alone in bfloat16, move the reference's outputs by more
    than the program stands from it: the comparison has something to
    see."""
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), trajectory
    logits, _, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    stated, _ = _reference(params, tokens, products=jnp.bfloat16)
    lowered, _ = _reference(
        params, tokens, products=jnp.bfloat16, lower=(lower,)
    )
    rms = lambda a, b: float(jnp.sqrt(jnp.mean(  # noqa: E731
        (a[..., :MASK] - b[..., :MASK]) ** 2
    )))
    assert rms(lowered, stated) > 2 * rms(logits, stated)


# 2. the step form: a cache that only a commit pass extends ----------------


def _stepwise(model, params, tokens, resets=None, stats=None):
    carry = model.initialize_carry(tokens.shape[1])
    step = jax.jit(model.apply)
    logits, values, positions = [], [], []
    for t in range(tokens.shape[0]):
        reset = (jnp.zeros((1, tokens.shape[1])) if resets is None
                 else resets[t:t + 1])
        positions.append(np.asarray(carry["pos"]))
        lg, v, carry, row = step(params, tokens[t:t + 1], reset, carry)
        logits.append(lg[0])
        values.append(v[0])
        if stats is not None:
            stats.append(row)
    return jnp.stack(logits), jnp.stack(values), carry, np.stack(positions)


def test_the_rollout_through_the_cache_is_the_sequence_pass(trajectory):
    """A pass at a time through the cache — a denoising pass's rows
    overwritten by the next pass over its block, a commit's kept — is
    the pass over ``T * L`` positions under the block-diffusion mask,
    and both are the reference: float32, tight."""
    model = _model()
    params, tokens = _init(model), trajectory
    seq_logits, seq_values, _, _ = model.apply(
        params, tokens, jnp.zeros((T, B)), None
    )
    stats = []
    logits, values, carry, positions = _stepwise(
        model, params, tokens, stats=stats
    )
    np.testing.assert_allclose(
        logits[..., :MASK], seq_logits[..., :MASK], atol=2e-5
    )
    np.testing.assert_allclose(values, seq_values, atol=2e-5)
    ref_logits, ref_values = _reference(params, tokens)
    masked = _masked(tokens)
    np.testing.assert_allclose(
        np.asarray(logits[..., :MASK])[masked],
        np.asarray(ref_logits[..., :MASK])[masked], atol=2e-5,
    )
    np.testing.assert_allclose(values, ref_values, atol=2e-5)
    # the position advances by a block on a commit pass and stays on a
    # denoising pass: two commits a turn of six passes
    commits = np.asarray(sdar.is_commit(tokens, CFG))
    np.testing.assert_array_equal(
        positions, L * (np.cumsum(commits, 0) - commits)
    )
    assert np.asarray(carry["pos"]).tolist() == [CACHE_LEN] * B
    # one array for all layers, the envs leading, a row a committed token
    assert carry["layers"].shape == (
        B, CFG.num_hidden_layers, CACHE_LEN,
        2 * CFG.num_key_value_heads * CFG.head_dim,
    )
    # the step form's counter: the tokens a pass committed
    assert [float(row[sdar.COMMITTED_TOKENS]) for row in stats] == [
        float(L * c) for c in commits[:, 0]
    ]


def test_block_reveal_scores_what_the_reference_scores(trainer):
    """The log-probabilities and values the rollout stored, pass by
    pass through the cache, are the reference's over the whole
    trajectory; a commit pass's log-probability is 0."""
    cfg, fns, state = trainer
    traj, carry0 = fns.collect(state)
    assert traj.obs.shape == traj.actions.shape == (
        cfg.rollout_length, cfg.num_envs, L
    )
    assert traj.obs.dtype == jnp.int32
    assert float(jnp.max(carry0["core"]["pos"])) == 0.0
    logits, values = _reference(state.params, traj.obs)
    log_probs, _ = ref.block_reveal(logits, traj.obs, traj.actions, MASK)
    np.testing.assert_allclose(traj.log_probs, log_probs, atol=2e-5)
    np.testing.assert_allclose(traj.values, values, atol=2e-5)
    commits = np.asarray(sdar.is_commit(traj.obs, CFG))
    assert (np.asarray(traj.log_probs)[commits] == 0.0).all()
    assert (np.asarray(traj.log_probs)[~commits] < 0.0).all()
    assert np.asarray(traj.dones[-1]).all() and not np.asarray(
        traj.dones[:-1]
    ).any()


def test_a_reset_mid_way_is_a_fresh_start(trajectory):
    cut = 12  # a turn's first pass
    model = _model()
    params, tokens = _init(model), trajectory
    resets = jnp.zeros((T, B)).at[cut, 1].set(1.0)
    logits, _, _, positions = _stepwise(model, params, tokens, resets)
    fresh, _, _, _ = model.apply(
        params, tokens[cut:], jnp.zeros((T - cut, B)), None
    )
    whole, _, _, _ = model.apply(params, tokens, jnp.zeros((T, B)), None)
    # env 1 starts over at the reset (rows past its position are never
    # read); its neighbours go on
    np.testing.assert_allclose(
        logits[cut:, 1, :, :MASK], fresh[:, 1, :, :MASK], atol=2e-5
    )
    np.testing.assert_allclose(
        logits[:, 0, :, :MASK], whole[:, 0, :, :MASK], atol=2e-5
    )
    assert positions[cut + 1, 1] == L and positions[cut + 1, 0] > L


# 3. the mask --------------------------------------------------------------


def _logits(model, params, tokens):
    return np.asarray(model.apply(
        params, tokens, jnp.zeros(tokens.shape[:2]), None
    )[0][..., :MASK])


def _other(token):
    return (token + 1) % MASK


def test_a_denoising_pass_is_seen_by_no_later_pass(trajectory):
    """A denoising pass's observation changed — here a masked position
    shown another way — leaves every later pass's logits bit-equal: its
    keys and values are never in a later pass's view, in either form."""
    model = _model()
    params, tokens = _init(model), trajectory
    t = 8  # turn 1's second denoising pass
    assert _masked(tokens)[t, 0, 3]
    changed = tokens.at[t, 0, 3].set(5)  # still a denoising pass
    assert not bool(sdar.is_commit(changed[t, 0], CFG))
    a, b = _logits(model, params, tokens), _logits(model, params, changed)
    assert np.abs(a[t, 0] - b[t, 0]).max() > 1e-4
    np.testing.assert_array_equal(a[t + 1:], b[t + 1:])
    np.testing.assert_array_equal(a[:t], b[:t])
    np.testing.assert_array_equal(a[:, 1:], b[:, 1:])
    step_a = np.asarray(_stepwise(model, params, tokens)[0][..., :MASK])
    step_b = np.asarray(_stepwise(model, params, changed)[0][..., :MASK])
    np.testing.assert_array_equal(step_a[t + 1:], step_b[t + 1:])


def test_attention_is_bidirectional_inside_a_block(trajectory):
    """Position 3 of a block moves position 0 of the same pass."""
    model = _model()
    params, tokens = _init(model), trajectory
    for t in (6, 9):  # a commit pass (an env block), a denoising pass
        keep_kind = _other(tokens[t, 0, 3]) if t == 6 else 7
        changed = tokens.at[t, 0, 3].set(keep_kind)
        a, b = _logits(model, params, tokens), _logits(model, params, changed)
        assert np.abs(a[t, 0, 0] - b[t, 0, 0]).max() > 1e-4, t


def test_a_committed_block_is_seen_by_every_later_pass(trajectory):
    model = _model()
    params, tokens = _init(model), trajectory
    t = 6  # turn 1's env block
    changed = tokens.at[t, 0, 1].set(_other(tokens[t, 0, 1]))
    a, b = _logits(model, params, tokens), _logits(model, params, changed)
    np.testing.assert_array_equal(a[:t], b[:t])
    later = np.abs(a[t + 1:, 0] - b[t + 1:, 0]).max((1, 2))
    assert (later > 1e-5).all(), later


def test_the_trajectory_mask_by_hand():
    """Three passes of two positions: a commit, a denoising pass, the
    commit of its block."""
    commit = jnp.asarray([[True], [False], [True]])
    positions, visible = sdar.trajectory_mask(commit, 2)
    assert positions.tolist() == [[0, 1, 2, 3, 2, 3]]
    c0, d, c1 = [1, 1], [0, 0], [1, 1]  # keys of pass 0, 1, 2
    assert visible[0].astype(int).tolist() == [
        c0 + [0, 0] + [0, 0], c0 + [0, 0] + [0, 0],   # pass 0 sees itself
        c0 + [1, 1] + [0, 0], c0 + [1, 1] + [0, 0],   # pass 1: 0 and itself
        c0 + d + c1, c0 + d + c1,                     # pass 2: not pass 1
    ]
    with jax.default_matmul_precision("highest"):
        ref_positions, ref_visible = ref.trajectory(
            jnp.asarray([[3, 4], [MASK, 9], [8, 9]]), MASK
        )
    assert ref_positions.tolist() == positions[0].tolist()
    np.testing.assert_array_equal(ref_visible, visible[0])


# 4. the expert layer's shares ---------------------------------------------


def _expert_params(cfg, seed=0):
    spec = sdar.layer_param_spec(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return {name: 0.2 * jax.random.normal(k, shape)
            for k, (name, (shape, _)) in zip(keys, spec.items())}


def test_the_shares_add_up():
    """The routed outputs of all ``num_experts / held`` shares sum to
    the uncut layer's (there is no shared expert), and each share alone
    is the reference given the same share."""
    p = _expert_params(dataclasses.replace(
        CFG, first_expert=0, experts_held=CFG.num_experts
    ))
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.hidden_size))
    held, total = CFG.experts_held, 0.0
    for first in range(0, CFG.num_experts, held):
        share = dataclasses.replace(CFG, first_expert=first)
        mine = dict(p, **{n: p[n][first:first + held]
                          for n in ("w_gate", "w_up", "w_down")})
        routed, stats = sdar.routed_experts(mine, x, share, jnp.float32)
        assert float(stats["moe_overflow_pairs"]) == 0.0
        total = total + routed
        with jax.default_matmul_precision("highest"):
            want = ref.expert_block(mine, x, MODEL, first, held)
        np.testing.assert_allclose(routed, want, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_block(p, x, MODEL, 0, CFG.num_experts)
    np.testing.assert_allclose(total, uncut, atol=5e-5)


# 5. the trainer's entry points --------------------------------------------


def _block(cfg, traj):
    T, B = traj.rewards.shape
    noise = jax.random.normal(jax.random.PRNGKey(8), (3, T, B))
    adv, ret = ref_ppo.gae(
        traj.rewards + 0.3 * noise[0], traj.values, traj.dones,
        jnp.zeros((B,)), cfg.gamma, cfg.gae_lambda,
    )
    return {
        "obs": traj.obs, "actions": traj.actions,
        # old log-probs scattered so that a share of the ratios clips
        "old_log_probs": traj.log_probs + 0.15 * noise[1],
        "old_values": traj.values + 0.1 * noise[2],
        "advantages": adv, "returns": ret,
        "resets": jnp.zeros((T, B)), "core": None,
    }


def test_block_grads_equal_the_reference_loss_and_gradients(trainer):
    cfg, fns, state = trainer
    traj, _ = fns.collect(state)
    block = _block(cfg, traj)
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
    # a third of the passes are commits: entropy 0 there
    assert 0.0 < float(parts["entropy"]) < np.log(CFG.vocab_size) * 2 / 3
    flat = lambda t: np.concatenate(  # noqa: E731
        [np.ravel(x) for x in jax.tree_util.tree_leaves(t)]
    )
    g, g_ref = flat(grads), flat(ref_grads)
    assert np.linalg.norm(g_ref) > 1e-3 and np.isfinite(g).all()
    np.testing.assert_allclose(g, g_ref, atol=2e-5 * np.abs(g_ref).max())


def test_the_sequence_form_through_the_kernels_is_the_plain_form(
        trajectory, monkeypatch):
    """``SDARActorCritic``'s sequence form at a head of 128 (what the
    kernels take; the preset's 16 goes to the plain form) through
    ``ops/pallas_block_attention.py`` in the Pallas interpreter, as
    the model picks it where the program is lowered for a TPU: the
    plain form's logits and values, its gradients under the layers'
    ``jax.checkpoint``, and a counter that says what was skipped."""
    model = _model(cfg=dataclasses.replace(CFG, head_dim=128))
    params, tokens = _init(model), trajectory
    weights = jax.random.normal(
        jax.random.PRNGKey(5), (T, B, L, CFG.vocab_size - 1)
    )

    def run(params):
        logits, values, _, stats = model.apply(
            params, tokens, jnp.zeros((T, B)), None
        )
        loss = jnp.sum(logits[..., :MASK] * weights) + jnp.sum(values ** 2)
        return loss, (logits, values, stats)

    run = jax.value_and_grad(run, has_aux=True)
    (_, (logits, values, stats)), grads = run(params)
    assert float(stats[sdar.SCORE_TILES_COMPUTED]) == 1.0

    # 3 x 3 tiles over 96 positions
    kernels_interpreted(monkeypatch, tile_q=32, chunk=32)
    (_, (k_logits, k_values, k_stats)), k_grads = run(params)
    assert float(k_stats[sdar.SCORE_TILES_COMPUTED]) == pytest.approx(6 / 9)
    np.testing.assert_allclose(
        k_logits[..., :MASK], logits[..., :MASK], atol=2e-5
    )
    np.testing.assert_allclose(k_values, values, atol=2e-5)
    flat = lambda t: np.concatenate(  # noqa: E731
        [np.ravel(x) for x in jax.tree_util.tree_leaves(t)]
    )
    g, g_plain = flat(k_grads), flat(grads)
    assert np.linalg.norm(g_plain) > 1e-3 and np.isfinite(g).all()
    np.testing.assert_allclose(g, g_plain, atol=2e-5 * np.abs(g_plain).max())


def test_a_commit_pass_has_no_policy_gradient(trajectory):
    """Every commit pass: log-probability 0 whatever the parameters
    (ratio 1, no policy gradient; the value is still trained), and the
    entropy's gradient is finite though the mask's column is -inf."""
    model = _model()
    params, tokens = _init(model), trajectory
    commits = sdar.is_commit(tokens, CFG)

    def dist(p):
        logits, values, _, _ = model.apply(p, tokens, jnp.zeros((T, B)), None)
        return BlockReveal(logits, tokens, CFG.reveal, MASK), values

    def commit_log_prob(p):
        # the rollout's actions: a commit pass hands its block back
        return jnp.sum(jnp.where(commits, dist(p)[0].log_prob(tokens), 0.0))

    grads = jax.grad(commit_log_prob)(params)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0
               for g in jax.tree_util.tree_leaves(grads))
    value_grads = jax.grad(
        lambda p: jnp.sum(jnp.where(commits, dist(p)[1], 0.0))
    )(params)
    assert float(jnp.max(jnp.abs(value_grads["params"]["value_w"]))) > 0.0
    entropy_grads = jax.grad(lambda p: jnp.sum(dist(p)[0].entropy()))(params)
    flat = np.concatenate([np.ravel(g) for g in
                           jax.tree_util.tree_leaves(entropy_grads)])
    assert np.isfinite(flat).all() and np.abs(flat).max() > 0.0


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
    # 6 passes a turn commit 8 tokens; a denoising pass reveals 1 of 4
    # positions; of a turn's 24 positions the log-prob reads 4
    np.testing.assert_allclose(
        float(metrics["diffusion_passes_per_committed_token"]), 0.75
    )
    assert float(metrics["diffusion_revealed_per_denoise_pass"]) == 1.0
    np.testing.assert_allclose(
        float(metrics["diffusion_scored_position_share"]), 4 / 24
    )
    # the first iteration's update sees the parameters the rollout saw
    assert float(metrics["episodes"]) == cfg.num_envs
    assert fns.steps_per_iteration == cfg.num_envs * cfg.rollout_length
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, state.params
    )
    assert all(change > 0.0 for change in jax.tree_util.tree_leaves(moved))


def test_the_update_reproduces_what_the_rollout_stored():
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    _, metrics = fns.iteration(fns.init(jax.random.PRNGKey(3)))
    assert float(metrics["approx_kl"]) < 1e-5
    assert float(metrics["clip_fraction"]) == 0.0


def test_the_carry_is_sharded_by_env_on_two_devices():
    """The trainer shards every leaf of a carry on its leading axis as
    the env axis (``common.state_specs``): on two devices each holds
    its half of the envs, ALL layers of their caches, and ``init`` and
    two iterations run."""
    cfg = PPOConfig(**dict(TINY, num_devices=2))
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(2))
    whole = (cfg.num_envs, CFG.num_hidden_layers, CACHE_LEN, CFG.cache_width)
    for _ in range(2):
        core = state.carry["core"]
        assert core["layers"].shape == whole
        assert core["layers"].sharding.shard_shape(whole) == (
            cfg.num_envs // 2,) + whole[1:]
        assert core["pos"].sharding.shard_shape(core["pos"].shape) == (
            cfg.num_envs // 2,)
        assert state.obs.sharding.shard_shape(state.obs.shape) == (
            cfg.num_envs // 2, L)
        state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["episodes"]) == cfg.num_envs
    np.testing.assert_allclose(
        float(metrics["diffusion_passes_per_committed_token"]), 0.75
    )


# 6. what the config refuses -----------------------------------------------


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("mlp_only_layers", (0,)), ("decoder_sparse_step", 2),
    ("attention_bias", True), ("denoising_steps", 3),
    ("mask_token_id", 64), ("num_key_value_heads", 3),
])
def test_the_config_refuses_what_is_not_built(key, value):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **{key: value})


def test_the_head_refuses_a_block_that_is_not_the_envs():
    """The model's vocabulary, block length and mask id are the action
    space's: another block length, or a one-token env, is refused."""
    with pytest.raises(ValueError, match="SEQUENCE_CORES.*block"):
        make_ppo(PPOConfig(**dict(
            TINY, env_params=dataclasses.replace(ENV, block_length=2,
                                                 denoise_steps=2, turns=6),
        )))
    recall = PRESETS["ppo-kimivl-tiny"][1]
    with pytest.raises(ValueError, match="SEQUENCE_CORES"):
        make_ppo(PPOConfig(**dict(TINY, env=recall["env"],
                                  env_params=recall["env_params"],
                                  rollout_length=16)))
