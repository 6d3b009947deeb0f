"""The Granite-4.0-H-Micro sequence-policy core
(models/granite_hybrid.py) against its plain reference
(perfbench/reference/granite_hybrid.py) at the tiny preset's widths on
the CPU: the sequence form, the step form through the carry, the
chunked state-space scan against the recurrence and the unrolled sum,
the trainer's ``block_grads``, the vocabulary slice, the published
defaults and the parameter count, and a short run.
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
from actor_critic_algs_on_tensorflow_tpu.models import (  # noqa: E402
    granite_hybrid as gh,
)
from perfbench.reference import granite_hybrid as ref  # noqa: E402
from perfbench.reference import ppo_loss as ref_ppo  # noqa: E402

TINY = PRESETS["ppo-granite-tiny"][1]
CFG = TINY["seq_model"]
CHUNK = CFG.mamba_chunk_size
T_SEQ, B_SEQ = 2 * CHUNK + 3, 3  # two whole chunks and a padded one
# The reference reads the published keys as a dict, and what is held.
MODEL = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
HELD = {"num_hidden_layers": CFG.num_hidden_layers,
        "layer_types": CFG.layer_types, "vocab_size": CFG.vocab_size}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _model(dtype=jnp.float32, cache_len=T_SEQ, cfg=CFG):
    return gh.GraniteHybridActorCritic(cfg=cfg, cache_len=cache_len,
                                       dtype=dtype)


def _init(model, seed=0, batch=B_SEQ):
    tokens = jnp.zeros((1, batch), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(seed), tokens, jnp.zeros((1, batch)),
        model.initialize_carry(batch),
    )
    # Norm weights, D and the value bias start at 1 (or 0): move them,
    # so that a norm that forgot its weight would show.
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim <= 1 else x
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def _tokens(T, B, seed=1, vocab=CFG.vocab_size):
    return jax.random.randint(jax.random.PRNGKey(seed), (T, B), 0, vocab)


def _reference(params, tokens, model=MODEL, held=HELD, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, tokens, model, held, **kw)


# 1. the sequence form against the reference ------------------------------


def test_sequence_forward_equals_reference():
    """Float32 on both sides, and two formulations of one sum (chunks
    and a recurrence over them here, one ``[T, T]`` product there):
    rounding only, on logits of scale ~0.1 and values of scale ~1."""
    model = _model()
    params, tokens = _init(model), _tokens(T_SEQ, B_SEQ)
    logits, values, _, stats = model.apply(
        params, tokens, jnp.zeros((T_SEQ, B_SEQ)), None
    )
    ref_logits, ref_values = _reference(params, tokens)
    assert logits.shape == (T_SEQ, B_SEQ, CFG.vocab_size)
    assert values.shape == (T_SEQ, B_SEQ)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(values, ref_values, atol=2e-5)
    assert 0.0 < float(stats[gh.STATE_RETENTION]) < 1.0
    # The reference a step down in precision is NOT within that bound.
    low_logits, _ = _reference(params, tokens, dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low_logits - ref_logits))) > 2e-5


# 2. the step form through the carry ---------------------------------------


def _stepwise(model, params, tokens, resets):
    carry = model.initialize_carry(tokens.shape[1])
    step = jax.jit(model.apply)
    logits, values = [], []
    for t in range(tokens.shape[0]):
        lg, v, carry, _ = step(params, tokens[t:t + 1], resets[t:t + 1], carry)
        logits.append(lg[0])
        values.append(v[0])
    return jnp.stack(logits), jnp.stack(values), carry


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """The model's choice (``gh._state_step``) pointed at the TPU's
    branch, and that at the Pallas interpreter: the CPU then runs the
    kernel's body where a chip would run the kernel. Yields the list of
    the Mamba layers it was traced for."""
    from actor_critic_algs_on_tensorflow_tpu.ops import pallas_mamba_step

    kernel, traced = pallas_mamba_step.mamba_step, []

    def interpreted(state, layer, *args):
        traced.append(layer)
        return kernel(state, layer, *args, interpret=True)

    monkeypatch.setattr(pallas_mamba_step, "mamba_step", interpreted)
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args),
    )
    return traced


@pytest.mark.parametrize("kernel", [False, True])
def test_stepping_through_the_carry_equals_the_sequence_pass(
    kernel, request
):
    """State, convolution tail and key/value cache, a token at a time,
    against the chunked scan and causal attention over the sequence
    (and, the sequence form being held to it above, the reference's
    unrolled sum); and a reset in the middle is a fresh start for that
    env alone. Once at the tiny preset's widths, where the plain step
    runs; once at a state that tiles the vector unit (``n`` 128, heads
    of 16 rows), with the one-pass kernel as the mixer's update: tails,
    the reset folded into the decay and the layer index with it."""
    cfg = CFG
    if kernel:
        cfg = dataclasses.replace(CFG, mamba_d_state=128)
        traced = request.getfixturevalue("interpreted_kernel")
    model = _model(cfg=cfg)
    params, tokens = _init(model), _tokens(T_SEQ, B_SEQ)
    cut = CHUNK + 2
    resets = jnp.zeros((T_SEQ, B_SEQ)).at[cut, 1].set(1.0)
    logits, values, carry = _stepwise(model, params, tokens, resets)
    if kernel:
        # (the parameters' initialisation traces the step form too)
        assert traced[-2:] == [0, 1], traced
        ref_logits, ref_values = _reference(
            params, tokens, model=dict(MODEL, mamba_d_state=128)
        )
        np.testing.assert_allclose(logits[:, 0], ref_logits[:, 0], atol=2e-5)
        np.testing.assert_allclose(values[:, 0], ref_values[:, 0], atol=2e-5)
    seq = lambda tok: model.apply(
        params, tok, jnp.zeros(tok.shape), None
    )[:2]
    whole_logits, whole_values = seq(tokens)
    for env in (0, 2):
        np.testing.assert_allclose(
            logits[:, env], whole_logits[:, env], atol=2e-5
        )
        np.testing.assert_allclose(
            values[:, env], whole_values[:, env], atol=2e-5
        )
    np.testing.assert_allclose(
        logits[:cut, 1], whole_logits[:cut, 1], atol=2e-5
    )
    after_logits, after_values = seq(tokens[cut:, 1:2])
    np.testing.assert_allclose(logits[cut:, 1], after_logits[:, 0], atol=2e-5)
    np.testing.assert_allclose(values[cut:, 1], after_values[:, 0], atol=2e-5)
    assert float(jnp.max(jnp.abs(logits[cut:, 1] - whole_logits[cut:, 1]))) > (
        1e-3
    )
    assert carry["pos"].tolist() == [T_SEQ, T_SEQ - cut, T_SEQ]
    assert carry["state"].shape == (
        B_SEQ, 2, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    )
    assert carry["conv"].shape == (B_SEQ, 2, 3, cfg.conv_channels)
    assert carry["k"].shape == carry["v"].shape == (B_SEQ, 1, T_SEQ, 2, 16)


# 3. the chunked scan, the recurrence, the unrolled sum ---------------------


def _scan_inputs(T, b=2, h=3, p=4, n=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, T, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, h)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=-1.0, maxval=1.5))
    B, C = (jax.random.normal(k, (b, T, n)) for k in ks[3:])
    return x, dt, A, B, C


def _recurrence(x, dt, A, B, C):
    S = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:])
    out = []
    for t in range(x.shape[1]):
        S, y = gh.mamba_step(
            S, x[:, t], dt[:, t], jnp.exp(dt[:, t] * A), B[:, t], C[:, t]
        )
        out.append(y)
    return jnp.stack(out, 1), S


@pytest.mark.parametrize("chunk", [4, 8, None])
@pytest.mark.parametrize("T", [16, 19])
def test_chunked_scan_equals_recurrence_equals_unrolled_sum(T, chunk):
    """Three formulations, none derived from another: chunks with a
    recurrence over their states, ``mamba_step`` a token at a time, and
    the reference's one ``[T, T]`` product; a length that is and one
    that is no multiple of the chunk; chunks of 4, 8 and the whole."""
    x, dt, A, B, C = _scan_inputs(T)
    with jax.default_matmul_precision("highest"):
        y, S = gh.chunk_state_space_scan(x, dt, A, B, C, chunk or T)
        y_rec, S_rec = _recurrence(x, dt, A, B, C)
        tm = lambda a: jnp.swapaxes(a, 0, 1)  # the reference is time-major
        y_sum = tm(ref.unrolled_sum(tm(x), tm(dt), A, tm(B), tm(C)))
    np.testing.assert_allclose(y, y_rec, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(S, S_rec, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(y_sum, y_rec, atol=2e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(y_rec))) > 1.0


# 4. the trainer's gradients against the reference ---------------------------


@pytest.fixture(scope="module")
def trainer():
    cfg = PPOConfig(**TINY)
    fns = make_ppo(cfg)
    return cfg, fns, fns.init(jax.random.PRNGKey(4))


def test_block_grads_equal_the_reference_loss_and_gradients(trainer):
    cfg, fns, state = trainer
    traj, carry0 = fns.collect(state)
    T, B = traj.obs.shape
    assert (T, B) == (cfg.rollout_length, cfg.num_envs)
    # what collect stored is what the reference computes on the tokens
    logits, values = _reference(state.params, traj.obs)
    log_probs, _ = ref.categorical(logits, traj.actions)
    np.testing.assert_allclose(traj.log_probs, log_probs, atol=2e-5)
    np.testing.assert_allclose(traj.values, values, atol=2e-5)
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
    assert jax.tree_util.tree_structure(grads) == (
        jax.tree_util.tree_structure(ref_grads)
    )
    flat = lambda t: np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(t)]
    )
    g, g_ref = flat(grads), flat(ref_grads)
    assert np.linalg.norm(g_ref) > 1e-3
    np.testing.assert_allclose(g, g_ref, atol=2e-5 * np.abs(g_ref).max())


# 5. bfloat16 products ----------------------------------------------------------


def test_at_bfloat16_products_the_system_stays_with_the_stated_reference():
    """bfloat16 inputs to the matrix products, float32 sums and all
    else float32: the reference written from that statement stands as
    far from the float32 one as rounding the products puts it, the
    program stands several times closer to it than that, and each step
    below the stated precision is another function by more than the
    program's distance."""
    model = _model(jnp.bfloat16)
    params, tokens = _init(model), _tokens(T_SEQ, B_SEQ)
    logits, values, _, _ = model.apply(
        params, tokens, jnp.zeros((T_SEQ, B_SEQ)), None
    )
    stated = _reference(params, tokens, products=jnp.bfloat16)
    plain = _reference(params, tokens)
    rms = lambda a, b: float(jnp.sqrt(jnp.mean((a - b) ** 2)))
    tolerance = rms(stated[0], plain[0])  # what bf16 products cost
    program = rms(logits, stated[0])
    assert program < 0.5 * tolerance, (program, tolerance)
    assert rms(values, stated[1]) < rms(stated[1], plain[1])
    same = _reference(params, tokens, products=jnp.float32)
    np.testing.assert_allclose(same[0], plain[0], atol=1e-6)
    for lower in ("state", "scan", "norms"):
        lowered, _ = _reference(
            params, tokens, products=jnp.bfloat16, lower=(lower,)
        )
        control = rms(lowered, stated[0])
        assert control > 2 * program, (lower, control, program)


# 6. the vocabulary slice -------------------------------------------------------


def test_the_vocabulary_slice_is_a_smaller_vocabulary():
    """With ``E`` the first 16 rows of the 64-row tied embedding, the
    logits are the whole model's first 16 columns for ids inside the
    slice: nothing couples the rows."""
    model = _model()
    params = _init(model)
    small_cfg = dataclasses.replace(CFG, vocab_size=16)
    small = _model(cfg=small_cfg)
    sliced = jax.tree_util.tree_map(lambda x: x, params)
    sliced["params"] = dict(
        params["params"], embedding=params["params"]["embedding"][:16]
    )
    tokens = _tokens(T_SEQ, B_SEQ, vocab=16)
    zeros = jnp.zeros((T_SEQ, B_SEQ))
    logits, values, _, _ = model.apply(params, tokens, zeros, None)
    s_logits, s_values, _, _ = small.apply(sliced, tokens, zeros, None)
    assert s_logits.shape[-1] == 16
    np.testing.assert_allclose(s_logits, logits[..., :16], atol=1e-6)
    np.testing.assert_allclose(s_values, values, atol=1e-6)


# 7. the published defaults and the held share ------------------------------------


def test_defaults_are_the_catalog_row_and_the_share_counts():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    cfg = gh.GraniteHybridConfig()
    fields = {f.name for f in dataclasses.fields(cfg)}
    assert fields == set(row["config"])
    for key, value in row["config"].items():
        got = getattr(cfg, key)
        assert got == (tuple(value) if isinstance(value, list) else value), key
    held = PRESETS["ppo-granite-recall"][1]["seq_model"]
    assert held == dataclasses.replace(
        cfg, num_hidden_layers=10, layer_types=cfg.layer_types[:10],
        vocab_size=12_544,
    )
    model = gh.GraniteHybridActorCritic(cfg=held, cache_len=512)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 2)), model.initialize_carry(2),
        )
    )
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert count == 772_162_497
    layers = shapes["params"]
    size = lambda t: sum(int(np.prod(x.shape)) for x in
                         jax.tree_util.tree_leaves(t))
    assert size(layers["layer_0"]) == 76_182_976
    assert size(layers["layer_5"]) == 60_821_504


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("mamba_n_groups", 2), ("layer_types", ("mamba", "conv", "mamba")),
    ("num_hidden_layers", 4),
])
def test_the_config_refuses_what_is_not_built(key, value):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **{key: value})


# 8. a short run ----------------------------------------------------------------


def test_a_short_run_trains_and_counts():
    """Two iterations through ``make_ppo``: a core with no expert
    layer, of which no ``moe_*`` key is demanded and which reports
    none."""
    cfg = PPOConfig(**dict(TINY, lr=1e-3))
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(1))
    p0 = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
    for _ in range(2):
        state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["health_finite"]) == 1.0
    assert 0.0 < float(metrics["mamba_state_retention"]) < 1.0
    assert not [k for k in metrics if k.startswith("moe_")]
    assert float(metrics["approx_kl"]) < 1e-3
    assert float(metrics["episodes"]) == cfg.num_envs
    assert int(state.step) == 2
    assert fns.steps_per_iteration == cfg.num_envs * cfg.rollout_length
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, state.params
    )
    assert all(v > 0 for v in jax.tree_util.tree_leaves(moved))
