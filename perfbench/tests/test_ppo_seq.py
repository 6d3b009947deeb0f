"""CPU rehearsal of the ``ppo-qwen3next-recall`` cell: the ``ppo_seq``
runner on the tiny preset (the cell's own files with the model and the
traffic cut: counts and correctness, never a time), the operations
function at the published widths, and the ``scope_roofline`` rule on
numbers worked out by hand.

``helpers.tiny_cell`` cuts a cell's TRAFFIC and keeps its preset; this
family's model is too large for the CPU at any traffic, so the helper
here swaps the configuration's preset for the tiny one as well.
"""

import copy
import dataclasses
import types

import pytest

from perfbench.harness import checks, driver, flops, peaks, rows, spec
from perfbench.operations import qwen3_next as operations
from perfbench.rules import scope_roofline
from perfbench.tests.helpers import no_span, nothing

CELL = "ppo-qwen3next-recall"


def tiny_seq_cell(**program):
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    cell = spec.load_cell(CELL)
    config, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    tiny = PRESETS["ppo-qwen3next-tiny"][1]
    model = tiny["seq_model"]
    config["preset"] = "ppo-qwen3next-tiny"
    dtype = program.pop("compute_dtype", "float32")
    config["program"].update(
        rollout_length=tiny["rollout_length"], compute_dtype=dtype,
    )
    published = {
        k: getattr(model, k, v)
        for k, v in config["model"]["published"].items()
    }
    config["model"]["published"] = published
    config["model"]["held"].update(
        num_hidden_layers=model.num_hidden_layers,
        experts_held=model.experts_held, first_expert=model.first_expert,
        vocab_size=model.vocab_size, router_width=model.num_experts,
        capacity_factor=model.capacity_factor,
    )
    envs = program.get("num_envs", tiny["num_envs"])
    traffic["program"].update(program, num_envs=envs, compute_dtype=dtype)
    traffic["expect"]["env_steps_per_iteration"] = (
        envs * tiny["rollout_length"]
    )
    config["reference_check"].update(
        rollout=tiny["rollout_length"], envs=envs, rollout_block_envs=4,
        block_envs=envs // traffic["program"]["num_minibatches"],
        grad_part_envs=1,
    )
    return dataclasses.replace(cell, config=config, traffic=traffic)


def test_the_cell_loads_with_its_metrics():
    cell = spec.load_cell(CELL)
    assert cell.family == "ppo_seq" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {
        "env_steps_per_s_per_chip", "peak_hbm_gib", "setup_s"
    }
    names = {m.name for m in cell.per_layer}
    assert {"gdn_time_share", "gated_attn_time_share", "moe_time_share",
            "moe_dispatch_time_share", "lm_head_time_share",
            "moe_expert_load_imbalance", "gdn_roofline",
            "moe_experts_roofline", "model_flops_util",
            "optimizer_time_share"} <= names
    assert not {n for n in names if n.startswith(("conv_", "allreduce"))}


def test_ppo_seq_runner_tiny():
    cell = tiny_seq_cell()
    runner = driver.load_runner("ppo_seq")(cell, seed=3)
    assert runner.setup() == {"placement": True}
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["attempted"] == window["iterations"] == 2
    assert window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    assert set(window["checks"]) >= {
        "optimizer_updates", "env_steps", "fused_loss_terms",
        "moe_dispatch_dropless",
    }
    steps = runner.cfg.num_envs * runner.cfg.rollout_length
    assert window["end_to_end"]["env_steps_per_s_per_chip"] == (
        rows.steady_rate(window["row_times_s"], steps)
    )
    # one log row an iteration, with the program's counters
    assert len(window["log_rows"]) == 2 and window["log_window_s"] > 0
    assert all(r["moe_overflow_pairs"] == 0.0 for r in window["log_rows"])
    assert 0.0 < runner.moe_pairs_per_token < 2.0
    per_it = cell.traffic["expect"]["optimizer_updates_per_iteration"]
    assert checks.optimizer_count(runner.state.opt_state) == 3 * per_it
    assert 0.0 < runner.moe_experts_touched_share <= 1.0
    verdict = runner.verify()
    assert verdict == {"reference_rollout": True,
                       "reference_block_grads": True}, runner.report
    # float32 products on the CPU: the program IS the reference, at the
    # stated precision and in float32 alike.
    rollout = runner.report["rollout"]
    assert rollout["log_prob"]["max"] < 1e-4
    assert rollout["against_float32"]["value"]["max"] < 1e-4
    assert runner.report["grad_cosine"] > 0.9999


def test_the_check_block_leaves_the_clip_on_both_sides():
    """A third of the tokens each at ratio 1, exp(-0.45) and
    exp(+0.45), old values likewise: both clipped branches carry
    tokens, and the same seed gives the same block."""
    import numpy as np

    runner = driver.load_runner("ppo_seq")(tiny_seq_cell(), seed=5)
    runner.setup()
    traj = runner.collected()[1]
    block = runner.check_block(traj)
    mb = block["obs"].shape[1]
    assert mb == runner.cfg.num_envs // runner.cfg.num_minibatches
    for key, stored in (("old_log_probs", traj.log_probs),
                        ("old_values", traj.values)):
        moved = np.asarray(block[key] - stored[:, :mb])
        assert sorted({round(float(m), 4) for m in moved.ravel()}) == [
            -0.45, 0.0, 0.45
        ]
    ratio = np.exp(np.asarray(traj.log_probs[:, :mb] - block["old_log_probs"]))
    eps = runner.cfg.clip_eps
    assert (ratio > 1 + eps).any() and (ratio < 1 - eps).any()
    again = runner.check_block(traj)
    assert (np.asarray(again["old_values"])
            == np.asarray(block["old_values"])).all()


def sound_errors(seed=0, T=256, B=128, scale=0.005):
    import numpy as np

    rng = np.random.default_rng(seed)
    return (rng.normal(0, scale, (T, B)), rng.normal(0, scale, (T, B)))


@pytest.mark.parametrize("fault", [
    "none", "a wrong token in one env of 128", "a stale last chunk",
    "one env a little off", "not a number",
])
def test_judge_rollout_fails_a_fault_in_a_minority(fault):
    """Errors of the sound program's size pass; a fault that touches
    one env or a quarter of the steps fails at least one limit, though
    none of them moves the median of all tokens by much."""
    import numpy as np

    from perfbench.runners import ppo_seq

    lp, v = sound_errors()
    if fault == "a wrong token in one env of 128":
        lp[100:, 7] += np.random.default_rng(1).normal(0, 0.5, 156)
    elif fault == "a stale last chunk":
        v[192:] += np.random.default_rng(2).normal(0, 0.05, (64, 128))
    elif fault == "one env a little off":
        lp[:, 77] += 0.03
    elif fault == "not a number":
        v[3, 3] = np.nan
    report = ppo_seq.judge_rollout(lp, v)
    assert report["ok"] == (fault == "none"), report
    if fault == "one env a little off":
        # only the per-env median sees it
        limits = ppo_seq.ROLLOUT_LIMITS
        assert all(report["log_prob"][k] < limits[k] for k in ("p90", "p99"))


def test_precision_controls_on_the_tiny_cell():
    """The controls' tool end to end on the CPU: at the tiny widths
    with bfloat16 products the program passes its own comparison, and
    the tool says which rows it could tell apart (the limits are set
    at the published widths; here only the plumbing is held)."""
    import json

    from perfbench.tools import precision_controls

    runner = driver.load_runner("ppo_seq")(
        tiny_seq_cell(compute_dtype="bfloat16"), seed=11
    )
    out = precision_controls.run(runner, grads=["all_bfloat16"])
    assert out in (0, 1)


def test_an_overflowing_dispatch_fails_the_window():
    """With the buffer forced too small the counter is not 0: the
    iteration counts as failed and the check fails."""
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    cell = tiny_seq_cell()
    runner = driver.load_runner("ppo_seq")(cell, seed=0)
    base = PRESETS["ppo-qwen3next-tiny"][1]["seq_model"]
    runner.cfg = dataclasses.replace(
        runner.cfg,
        seq_model=dataclasses.replace(base, capacity_factor=0.25),
    )
    cell.config["model"]["held"]["capacity_factor"] = 0.25
    runner.setup()
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["failed"] == window["attempted"] == 2
    assert not window["checks"]["moe_dispatch_dropless"]


def test_operations_at_the_published_widths():
    cell = spec.load_cell(CELL)
    chunk = types.SimpleNamespace(chunk_size=64)
    runner = types.SimpleNamespace(
        cfg=types.SimpleNamespace(rollout_length=256, seq_model=chunk,
                                  num_envs=128),
        moe_pairs_per_token=None, moe_experts_touched_share=None,
    )
    layers = {l.name: l for l in operations.layers(cell.config, runner)}
    H = 2048
    assert layers["gdn_in_proj"].macs == 3 * H * (12288 + 64)
    assert layers["gdn_out_proj"].w_elems == 3 * 4096 * H
    assert layers["attn_in_proj"].macs == H * (8192 + 1024)
    assert layers["moe_router"].macs == 4 * H * 512
    # 10 x 32 / 512 pairs a token expected, three products each
    assert layers["moe_routed"].macs == round(4 * 0.625 * 3 * H * 512)
    # every held expert's weights, until the program has counted the
    # ones a call touches
    assert layers["moe_routed"].w_elems == 4 * 32 * 3 * H * 512
    assert layers["lm_head"].macs == H * 18993
    # the delta rule: 3 d_k d_v + c (2 d_k + d_v) / 2 + c (d_k + d_v) / 2
    assert layers["gdn_delta_rule"].macs == 3 * 32 * (
        3 * 128 * 128 + 64 * 384 // 2 + 64 * 256 // 2
    )
    assert layers["gdn_delta_rule"].w_elems == 0
    assert layers["attn_scores_values"].macs == 16 * 256 * 257
    # the float32 state read once and written once a token
    state = layers["gdn_state"]
    assert (state.macs, state.w_elems, state.out_elems) == (0, 0, 0)
    assert state.in_elems * state.in_bytes == 3 * 32 * 128 * 128 * 8
    total = flops.forward_flops_per_sample(list(layers.values())) / 2
    assert 185e6 < total < 200e6  # ISSUE 27's 192 M multiply-adds a token
    # the counted pairs move the routed experts' row and nothing else
    runner.moe_pairs_per_token = 1.25
    runner.moe_experts_touched_share = 0.5
    more = {l.name: l for l in operations.layers(cell.config, runner)}
    assert more["moe_routed"].macs == 2 * layers["moe_routed"].macs
    assert 2 * more["moe_routed"].w_elems == layers["moe_routed"].w_elems
    assert more["moe_shared"] == layers["moe_shared"]


def roofline_ctx(self_s_by_phases, work, layers, why=None, kernels_s=0.0):
    joined = {"why": why, "self_s_by_phases": self_s_by_phases}
    reduced = types.SimpleNamespace(
        chips=1, family_self_s=lambda family: kernels_s
    )
    return types.SimpleNamespace(
        reduced=reduced, work_per_chip=work,
        layers=layers, peaks=peaks.PEAKS["TPU v5 lite"],
        notes={"scope_join": joined},
    )


def test_scope_roofline_by_hand():
    """One compute-bound row under one scope: 1e9 multiply-adds a
    sample, 1,000 acting passes and 1,000 training samples, so 2e12 +
    6e12 operations = 8e12 / 197e12 s; a state row counted for the
    acting passes alone: 1,000 x (4 + 2) MB / 819e9 s."""
    dense = flops.Layer("dense", 10**9, 1, 1, 1, 2, True)
    state = flops.Layer("state", 0, 10**6, 10**6, 0, 4, True)
    work = {"forward_samples": 1000, "forward_calls": 10,
            "train_samples": 1000, "train_calls": 1}
    phases = {"rollout/policy_act/mix": 0.05, "update/loss_grad/mix": 0.03,
              "update/optimizer": 1.0}
    ctx = roofline_ctx(phases, work, [dense, state])
    value = scope_roofline.read(ctx, scope="mix", layers=["dense"])
    assert value == pytest.approx(100 * (8e12 / 197e12) / 0.08, rel=1e-6)
    both = scope_roofline.read(
        ctx, scope="mix", layers=["dense"], rollout_only=["state"]
    )
    assert both == pytest.approx(
        100 * (8e12 / 197e12 + 6e9 / 819e9) / 0.08, rel=1e-6
    )
    assert ctx.notes["scope_roofline"]["mix"]["measured_s"] == (
        pytest.approx(0.08)
    )
    # kernels the compiler names itself are found by name, whatever
    # scope their consumers gave them
    ctx = roofline_ctx(phases, work, [dense, state], kernels_s=0.1)
    family = {"category": [], "name": ["^%ragged-dot-"]}
    by_name = scope_roofline.read(ctx, family=family, layers=["dense"])
    assert by_name == pytest.approx(100 * (8e12 / 197e12) / 0.1, rel=1e-6)
    assert scope_roofline.read(
        roofline_ctx(phases, work, [dense]), family=family, layers=["dense"]
    ) is None


@pytest.mark.parametrize("case", [
    "no such scope in the trace", "the join gave up", "a row is missing",
    "no operations function", "no trace",
])
def test_scope_roofline_reads_nothing_where_there_is_nothing(case):
    dense = flops.Layer("dense", 10**9, 1, 1, 1, 2, True)
    work = {"forward_samples": 10, "train_samples": 10}
    ctx = roofline_ctx({"update/loss_grad/mix": 0.03}, work, [dense])
    args = dict(scope="mix", layers=["dense"])
    if case == "no such scope in the trace":
        args["scope"] = "gdn"  # a program from before the scope
    elif case == "the join gave up":
        ctx.notes["scope_join"]["why"] = "coverage"
    elif case == "a row is missing":
        args["layers"] = ["dense", "absent"]
    elif case == "no operations function":
        ctx.layers = None
    else:
        ctx.reduced = None
    assert scope_roofline.read(ctx, **args) is None
