"""CPU rehearsal of the ``ppo-xing-recall`` cell: the ``ppo_seq_hc``
runner on the tiny preset (the cell's own files with the model and the
traffic cut: counts and correctness, never a time), its two checks of
the hyper-connections, the operations function at the published widths
against a hand count, and the controls' tool end to end.

As in ``test_ppo_seq_ssm.py``, the helper swaps the configuration's
preset for the tiny one as well as cutting the traffic; it runs the
tiny model at the published 20 Sinkhorn iterations (the preset's 3 are
for the unit tests' speed, and fail ``mhc_sinkhorn_converged``, below).
"""

import copy
import dataclasses
import json
import os
import types

import pytest

from perfbench.harness import checks, driver, flops, spec
from perfbench.operations import xing as operations
from perfbench.tests.helpers import no_span, nothing

CELL = "ppo-xing-recall"
TINY = "ppo-xing-tiny"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_hc_cell(iters=20, **program):
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    cell = spec.load_cell(CELL)
    config, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    tiny = PRESETS[TINY][1]
    model = dataclasses.replace(tiny["seq_model"], hc_sinkhorn_iters=iters)
    config["preset"] = TINY
    dtype = program.pop("compute_dtype", "float32")
    config["program"].update(
        rollout_length=tiny["rollout_length"], compute_dtype=dtype,
    )
    config["model"]["published"] = {
        k: getattr(model, k, v)
        for k, v in config["model"]["published"].items()
    }
    config["model"]["published"]["rope_scaling"] = dict(model.rope_scaling)
    config["model"]["held"].update(
        num_hidden_layers=model.num_hidden_layers,
        first_k_dense_replace=model.first_k_dense_replace,
        vocab_size=model.vocab_size, experts_held=model.experts_held,
        router_width=model.n_routed_experts,
        capacity_factor=model.capacity_factor,
    )
    envs = program.get("num_envs", tiny["num_envs"])
    minibatches = program.setdefault("num_minibatches", 4)
    traffic["program"].update(program, num_envs=envs, compute_dtype=dtype,
                              seq_model=model)
    traffic["expect"].update(
        env_steps_per_iteration=envs * tiny["rollout_length"],
        optimizer_updates_per_iteration=minibatches,
    )
    config["reference_check"].update(
        rollout=tiny["rollout_length"], envs=envs, rollout_block_envs=4,
        block_envs=envs // minibatches, grad_part_envs=1,
    )
    return dataclasses.replace(cell, config=config, traffic=traffic)


def test_the_cell_loads_with_its_metrics():
    cell = spec.load_cell(CELL)
    assert cell.family == "ppo_seq_hc" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {
        "env_steps_per_s_per_chip", "peak_hbm_gib", "setup_s"
    }
    names = {m.name for m in cell.per_layer}
    assert {"mhc_time_share", "mhc_sinkhorn_time_share", "mhc_roofline",
            "mla_time_share", "mla_roofline", "moe_experts_roofline",
            "dense_mlp_time_share", "mixer_core_time_share",
            "model_flops_util"} <= names
    # Kimi-VL's cell reports the same metrics but the three of mHC
    kimi = {m.name for m in spec.load_cell("ppo-kimivl-recall").per_layer}
    assert names - kimi == {"mhc_time_share", "mhc_sinkhorn_time_share",
                            "mhc_roofline"}
    assert kimi <= names
    published, cut = cell.config["model"]["published"], cell.config["reduced"]
    assert sorted(cut) == ["first_k_dense_replace", "n_routed_experts",
                           "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        assert (cell.config[key] == value) == (key not in cut), key
    held = cell.config["model"]["held"]
    for key, value in (("num_hidden_layers", 5), ("first_k_dense_replace", 1),
                       ("vocab_size", 16_384)):
        assert cell.config[key] == held[key] == value
    assert cell.config["n_routed_experts"] == held["experts_held"] == 8
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Xing4.0-29B-A4B"]
        assert published == row["config"]
        assert cell.config["source"] == row["source_url"]


def test_the_preset_is_the_configuration():
    """``_check_model`` at the published widths: the preset's model
    holds every published key its dataclass has (YaRN's group as the
    dict it is published as), and a key that differs is refused."""
    runner = driver.load_runner("ppo_seq_hc")(spec.load_cell(CELL), seed=0)
    runner._check_model(runner.cfg)
    for wrong in ({"hc_sinkhorn_iters": 10}, {"q_lora_rank": 1536},
                  {"first_k_dense_replace": 2},
                  {"rope_scaling": (("type", "yarn"), ("factor", 40),
                                    ("mscale", 1), ("mscale_all_dim", 1))}):
        cfg = dataclasses.replace(
            runner.cfg,
            seq_model=dataclasses.replace(runner.cfg.seq_model, **wrong),
        )
        with pytest.raises(spec.SpecError, match=next(iter(wrong))):
            runner._check_model(cfg)


def test_ppo_seq_hc_runner_tiny():
    cell = tiny_hc_cell()
    runner = driver.load_runner("ppo_seq_hc")(cell, seed=3)
    assert runner.setup() == {"placement": True}
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["attempted"] == window["iterations"] == 2
    assert window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    assert set(window["checks"]) >= {
        "optimizer_updates", "env_steps", "fused_loss_terms",
        "moe_dispatch_dropless", "mhc_sinkhorn_converged", "mhc_res_mixed",
    }
    assert {"mhc_res_offdiag_mass", "mhc_sinkhorn_residual",
            "moe_overflow_pairs", "loss"} <= set(window["log_rows"][0])
    assert 0.05 < window["mhc_res_offdiag_mass"] < 0.95
    assert window["mhc_sinkhorn_residual"] <= 1e-3
    per_it = cell.traffic["expect"]["optimizer_updates_per_iteration"]
    assert checks.optimizer_count(runner.state.opt_state) == 3 * per_it
    # the limits are set at the published widths; at float32 on the CPU
    # the program IS the reference, whatever they are
    verdict = runner.verify()
    assert verdict == {"reference_rollout": True,
                       "reference_block_grads": True}, runner.report
    rollout = runner.report["rollout"]
    assert rollout["log_prob"]["max"] < 1e-4
    assert rollout["against_float32"]["value"]["max"] < 1e-4
    assert runner.report["grad_cosine"] > 0.9999


def test_a_projection_left_short_fails_the_window():
    """At 3 Sinkhorn iterations ``H_res`` is not doubly stochastic to
    1e-3: ``mhc_sinkhorn_converged`` fails, and an identity-like mix
    fails ``mhc_res_mixed``."""
    cell = tiny_hc_cell(iters=3)
    runner = driver.load_runner("ppo_seq_hc")(cell, seed=0)
    runner.setup()
    iteration = runner.fns.iteration

    def unmixed(state):
        state, metrics = iteration(state)  # (keeps what it reports)
        runner._reported[-1] = dict(metrics, mhc_res_offdiag_mass=0.01)
        return state, metrics

    window = runner.measure(0.0, nothing, nothing, no_span)
    assert not window["checks"]["mhc_sinkhorn_converged"]
    assert window["checks"]["mhc_res_mixed"]
    runner.fns = runner.fns._replace(iteration=unmixed)
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert not window["checks"]["mhc_res_mixed"]


def test_precision_controls_on_the_tiny_cell(tmp_path):
    """The controls' tool end to end on the CPU with bfloat16 products:
    every control of the family is computed and judged (the limits are
    set at the published widths; here only the plumbing is held)."""
    from perfbench.runners import ppo_seq_hc
    from perfbench.tools import precision_controls_hc

    runner = driver.load_runner("ppo_seq_hc")(
        tiny_hc_cell(compute_dtype="bfloat16"), seed=11
    )
    out = tmp_path / "controls.jsonl"
    code = precision_controls_hc.run(
        runner, grads=["all_bfloat16", "streams_bfloat16"], out=str(out)
    )
    assert code in (0, 1)
    rows_ = [json.loads(l) for l in out.read_text().splitlines()]
    final = {r["row"]: r for r in rows_ if "ok" in r}
    controls = {**ppo_seq_hc.CONTROLS, **ppo_seq_hc.REPORTED}
    assert set(final) == {"program", *controls}
    assert {"streams_bfloat16", "sinkhorn_bfloat16"} <= set(controls)
    assert "grads" in final["program"] and "grads" in final["all_bfloat16"]
    assert "grads" in final["streams_bfloat16"]
    for name in controls:
        assert final[name]["rollout"]["log_prob"]["max"] > 0.0, name


def test_operations_at_the_published_widths():
    """Every row of the hyper-connections and the low-rank query against
    a count by hand (ISSUE 39's arithmetic); the rest is Kimi-VL's rows
    at this configuration's widths."""
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=256, num_envs=128, compute_dtype="bfloat16"
    ))
    layers = {l.name: l for l in operations.layers(cell.config, runner)}
    H, C4 = 3584, 4 * 3584
    assert layers["mla_q_proj"].macs == 5 * (H * 768 + 768 * 32 * 192)
    assert layers["mla_kv_a_proj"].macs == 5 * H * 576
    assert layers["mla_kv_b_proj"].macs == 5 * 512 * 32 * 256
    assert layers["mla_out_proj"].macs == 5 * 4096 * H
    # one dense layer (the held pattern, not the published 2), 4 expert
    assert layers["dense_mlp"].macs == 1 * 3 * H * 9216
    assert layers["moe_router"].macs == 4 * H * 64
    assert layers["moe_shared"].macs == 4 * 3 * H * 1024
    assert layers["lm_head"].macs == H * 16_385
    # ten sublayers: the coefficient product, 14,336 x 24 a token
    coeffs = layers["mhc_coeffs"]
    assert coeffs.macs == coeffs.w_elems == 10 * C4 * 24 == 10 * 344_064
    assert coeffs.in_elems * coeffs.in_bytes == 10 * C4 * 4
    # the mixes: (3n + 1) C float32 numbers a token a sublayer
    mix = layers["mhc_mix"]
    assert (mix.w_elems, mix.out_elems) == (0, 0)
    assert mix.in_elems * mix.in_bytes == 10 * 13 * H * 4
    sink = layers["mhc_sinkhorn"]
    assert sink.macs == 10 * 20 * 2 * 16 and sink.w_elems == 0
    total = flops.forward_flops_per_sample(list(layers.values())) / 2
    # MLA 142 M, the dense layer 99 M, the head 59 M, the shared experts
    # 44 M, the routed ones at 0.5 pairs a token 22 M, mHC 3.4 M
    assert 370e6 < total < 385e6  # ~378 M multiply-adds a token


def test_the_new_metrics_name_rows_the_operations_function_has():
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=256, compute_dtype="bfloat16"
    ))
    rows_ = {l.name for l in operations.layers(cell.config, runner)}
    for name in ("mhc_roofline", "mla_roofline"):
        with open(os.path.join(spec.BENCH_DIR, "metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args["layers"]) | set(args.get("rollout_only", ())) <= rows_
    for name, scope in (("mhc_time_share", "mhc"),
                        ("mhc_sinkhorn_time_share", "mhc_sinkhorn")):
        with open(os.path.join(spec.BENCH_DIR, "metrics", name + ".json")) as f:
            decl = json.load(f)
        assert decl["rule"] == "scope_time_share"
        assert decl["args"] == {"scope": scope}


def test_the_tiny_cells_program_feeds_the_new_metrics(monkeypatch):
    """One event of 1 us for every instruction the tiny cell's lowered
    program holds (``test_layer_map.py``'s rehearsal): the three new
    metrics read a number, the Sinkhorn inside the hyper-connections,
    and ``mla``'s three parts still add up to it beside them."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        scope_table,
    )
    from perfbench.harness import peaks, trace_reduce as tr
    from perfbench.rules import scope_lowering as sl
    from perfbench.rules import scope_roofline, scope_time_share

    cell = tiny_hc_cell()
    runner = driver.load_runner(cell.family)(cell, seed=3)
    runner.setup()
    runner.close()
    texts = sl._LOWER[cell.family](runner)
    monkeypatch.setitem(sl._LOWER, cell.family, lambda runner: texts)
    events, at = [], 0.0
    for text in texts:
        for key in scope_table(text):
            events.append((tr.Event(key, at, 1000.0), 1000.0))
            at += 1000.0
    ctx = types.SimpleNamespace(
        reduced=types.SimpleNamespace(
            op_events=events, busy_s=at / 1e9, chips=1,
            modules={"jit_local_iteration_recurrent(123)": [1.0]},
        ),
        notes={}, runner=runner, cell=cell,
        work_per_chip={"forward_samples": 8 * 17, "forward_calls": 17,
                       "train_samples": 8 * 16},
        layers=operations.layers(cell.config, runner),
        peaks=peaks.peaks_for("TPU v5 lite"),
    )
    values = {
        m.name: scope_time_share.read(ctx, **m.args)
        for m in cell.per_layer if m.rule == "scope_time_share"
    }
    assert ctx.notes["scope_join"]["why"] is None
    for name in ("mhc_time_share", "mhc_sinkhorn_time_share",
                 "mla_time_share"):
        assert values[name] is not None and 0 < values[name] < 100, values
    assert values["mhc_sinkhorn_time_share"] < values["mhc_time_share"]
    parts = sum(values[m] for m in ("mixer_proj_time_share",
                                    "mixer_pointwise_time_share",
                                    "mixer_core_time_share"))
    assert parts == pytest.approx(values["mla_time_share"], abs=1e-9)
    roofline = next(m for m in cell.per_layer if m.name == "mhc_roofline")
    share = scope_roofline.read(ctx, **roofline.args)
    assert share is not None and share > 0
    assert ctx.notes["scope_roofline"]["mhc"]["least_s"] > 0
