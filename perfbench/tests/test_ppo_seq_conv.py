"""CPU rehearsal of the ``ppo-lfm2-recall`` cell: the ``ppo_seq_conv``
runner on the tiny preset (the cell's own files with the model and the
traffic cut: counts and correctness, never a time), its check of the
convolutions' tails, the operations function at the published widths
against a hand count, the new metrics on the tiny cell's program, and
the controls' tool end to end.

As in ``test_ppo_seq_hc.py``, the helper swaps the configuration's
preset for the tiny one as well as cutting the traffic.
"""

import copy
import dataclasses
import json
import os
import types

import pytest

from perfbench.harness import checks, driver, flops, spec
from perfbench.operations import lfm2 as operations
from perfbench.tests.helpers import no_span, nothing

CELL = "ppo-lfm2-recall"
TINY = "ppo-lfm2-tiny"


def tiny_conv_cell(**program):
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    cell = spec.load_cell(CELL)
    config, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    tiny = PRESETS[TINY][1]
    model = tiny["seq_model"]
    config["preset"] = TINY
    dtype = program.pop("compute_dtype", "float32")
    config["program"].update(
        rollout_length=tiny["rollout_length"], compute_dtype=dtype,
    )
    config["model"]["published"] = {
        k: getattr(model, k, v)
        for k, v in config["model"]["published"].items()
    }
    config["model"]["published"]["rope_parameters"] = dict(
        model.rope_parameters
    )
    config["model"]["published"]["layer_types"] = list(model.layer_types)
    config["model"]["held"].update(
        num_hidden_layers=model.num_hidden_layers,
        num_dense_layers=model.num_dense_layers,
        layer_types=list(model.layer_types),
        vocab_size=model.vocab_size, experts_held=model.experts_held,
        router_width=model.num_experts,
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
    assert cell.family == "ppo_seq_conv" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {
        "env_steps_per_s_per_chip", "peak_hbm_gib", "setup_s"
    }
    names = {m.name for m in cell.per_layer}
    assert {"shortconv_time_share", "shortconv_roofline", "gqa_time_share",
            "moe_experts_roofline", "dense_mlp_time_share",
            "mixer_core_time_share", "model_flops_util",
            "rollout_moe_dispatch_time_share", "gqa_roofline"} <= names
    assert not {"mamba_time_share", "mla_time_share"} & names
    published, cut = cell.config["model"]["published"], cell.config["reduced"]
    assert sorted(cut) == ["layer_types", "num_dense_layers", "num_experts",
                           "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        assert (cell.config[key] == value) == (key not in cut), key
    held = cell.config["model"]["held"]
    for key, value in (("num_hidden_layers", 5), ("num_dense_layers", 1),
                       ("vocab_size", 8_192)):
        assert cell.config[key] == held[key] == value
    assert cell.config["num_experts"] == held["experts_held"] == 8
    assert held["layer_types"] == (
        published["layer_types"][:1] + published["layer_types"][2:6]
    ) == ["conv", "full_attention", "conv", "conv", "conv"]


def test_the_preset_is_the_configuration():
    """``_check_model`` at the published widths: the preset's model
    holds every published key its dataclass has, and a key that differs
    is refused."""
    runner = driver.load_runner("ppo_seq_conv")(spec.load_cell(CELL), seed=0)
    runner._check_model(runner.cfg)
    for wrong in ({"conv_L_cache": 4}, {"num_dense_layers": 2},
                  {"num_key_value_heads": 4},
                  {"rope_parameters": (("rope_theta", 10000),
                                       ("rope_type", "default"))}):
        cfg = dataclasses.replace(
            runner.cfg,
            seq_model=dataclasses.replace(runner.cfg.seq_model, **wrong),
        )
        with pytest.raises(spec.SpecError, match=next(iter(wrong))):
            runner._check_model(cfg)


def test_ppo_seq_conv_runner_tiny():
    cell = tiny_conv_cell()
    runner = driver.load_runner("ppo_seq_conv")(cell, seed=3)
    assert runner.setup() == {"placement": True}
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["attempted"] == window["iterations"] == 2
    assert window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    assert set(window["checks"]) >= {
        "optimizer_updates", "env_steps", "fused_loss_terms",
        "moe_dispatch_dropless", "shortconv_tail_used",
    }
    assert {"shortconv_tail_share", "moe_overflow_pairs",
            "loss"} <= set(window["log_rows"][0])
    assert window["shortconv_tail_share"] > 0.05
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


def test_an_unread_tail_fails_the_window():
    """An iteration whose tail share reads 0 (the step form read nothing
    from its tails) or NaN fails ``shortconv_tail_used``."""
    runner = driver.load_runner("ppo_seq_conv")(tiny_conv_cell(), seed=0)
    runner.setup()
    iteration = runner.fns.iteration
    for bad in (0.0, float("nan")):
        def unread(state, bad=bad):
            state, metrics = iteration(state)  # (keeps what it reports)
            runner._reported[-1] = dict(metrics, shortconv_tail_share=bad)
            return state, metrics

        runner.fns = runner.fns._replace(iteration=unread)
        window = runner.measure(0.0, nothing, nothing, no_span)
        assert not window["checks"]["shortconv_tail_used"], bad


def test_precision_controls_on_the_tiny_cell(tmp_path):
    """The controls' tool end to end on the CPU with bfloat16 products:
    every control of the family is computed and judged (the limits are
    set at the published widths; here only the plumbing is held)."""
    from perfbench.runners import ppo_seq_conv
    from perfbench.tools import precision_controls_conv

    runner = driver.load_runner("ppo_seq_conv")(
        tiny_conv_cell(compute_dtype="bfloat16"), seed=11
    )
    out = tmp_path / "controls.jsonl"
    code = precision_controls_conv.run(
        runner, grads=["all_bfloat16", "tails_bfloat16"], out=str(out)
    )
    assert code in (0, 1)
    rows_ = [json.loads(l) for l in out.read_text().splitlines()]
    final = {r["row"]: r for r in rows_ if "ok" in r}
    controls = {**ppo_seq_conv.CONTROLS, **ppo_seq_conv.REPORTED}
    assert set(final) == {"program", *controls}
    assert {"tails_bfloat16", "router_bfloat16", "norms_bfloat16",
            "all_bfloat16"} <= set(controls)
    assert "grads" in final["program"] and "grads" in final["all_bfloat16"]
    assert "grads" in final["tails_bfloat16"]
    for name in controls:
        assert final[name]["rollout"]["log_prob"]["max"] > 0.0, name


def test_operations_at_the_published_widths():
    """Every row against a count by hand (the configuration's held
    share: 4 convolutions, 1 attention, 1 dense and 4 expert layers)."""
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=512, num_envs=128, compute_dtype="bfloat16"
    ))
    layers = {l.name: l for l in operations.layers(cell.config, runner)}
    H = 2048
    assert layers["shortconv_in_proj"].macs == 4 * H * 3 * H
    assert layers["shortconv_out_proj"].macs == 4 * H * H
    point = layers["shortconv_pointwise"]
    assert point.macs == 4 * 5 * H and point.w_elems == 0
    assert point.in_elems * point.in_bytes == 4 * 3 * H * 4
    tails = layers["shortconv_tails"]
    assert tails.macs == tails.w_elems == tails.out_elems == 0
    # two float32 values a channel a layer, read once and written once
    assert tails.in_elems * tails.in_bytes == 4 * 2 * 2 * H * 4
    assert layers["gqa_q_proj"].macs == H * H
    assert layers["gqa_kv_proj"].macs == H * 2 * 8 * 64
    assert layers["gqa_out_proj"].macs == H * H
    assert layers["gqa_scores_values"].macs == 32 * 64 * 513
    assert layers["dense_mlp"].macs == 3 * H * 11_776
    assert layers["moe_router"].macs == 4 * H * 64
    # the expected half a pair a token where no window has run
    assert layers["moe_routed"].macs == 4 * 0.5 * 3 * H * 1536
    assert layers["moe_routed"].w_elems == 4 * 8 * 3 * H * 1536
    assert layers["lm_head"].macs == H * 8_193
    total = flops.forward_flops_per_sample(list(layers.values())) / 2
    # conv 67 M, dense 72 M, experts 19 M, attention 11 M, head 17 M
    assert 185e6 < total < 190e6


def test_the_new_metrics_name_rows_the_operations_function_has():
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=512, compute_dtype="bfloat16"
    ))
    rows_ = {l.name for l in operations.layers(cell.config, runner)}
    with open(os.path.join(spec.BENCH_DIR, "metrics",
                           "shortconv_roofline.json")) as f:
        args = json.load(f)["args"]
    assert args["scope"] == "shortconv"
    assert set(args["layers"]) | set(args["rollout_only"]) <= rows_
    assert {"moe_routed"} <= rows_
    with open(os.path.join(spec.BENCH_DIR, "metrics",
                           "shortconv_time_share.json")) as f:
        decl = json.load(f)
    assert decl["rule"] == "scope_time_share"
    assert decl["args"] == {"scope": "shortconv"}
    # the accepted attention roofline, appended to this cell, finds its
    # rows here too
    with open(os.path.join(spec.BENCH_DIR, "metrics",
                           "gqa_roofline.json")) as f:
        args = json.load(f)["args"]
    assert set(args["layers"]) | set(args["rollout_only"]) <= rows_


def test_the_gradient_limits_are_the_familys():
    """``block_grads`` is judged under ``GRAD_LIMITS``, not the
    harness's looser ones: a gradient 1e-3 off in direction (what the
    all-bfloat16 control reads on the chip) passes the harness's 0.995
    and fails here; one as close as the program reads passes both."""
    import numpy as np

    from perfbench.runners import ppo_seq_conv

    ref = {"w": np.ones(1000), "b": np.zeros(3)}

    def turned(one_minus_cosine):
        # unit-norm component orthogonal to ``ref["w"]``, angle set so
        # that cosine = 1 - one_minus_cosine, norm kept
        ortho = np.zeros(1000)
        ortho[0::2], ortho[1::2] = 1.0, -1.0
        theta = np.arccos(1.0 - one_minus_cosine)
        w = ref["w"] * np.cos(theta) + ortho * np.sin(theta)
        return {"w": w, "b": np.zeros(3)}

    for off, ok in ((1e-4, True), (1e-3, False)):
        report = ppo_seq_conv.compare_loss_and_grads(
            (1.0, turned(off)), (1.0, ref), 1.0
        )
        assert report["grad_cosine"] == pytest.approx(1.0 - off, rel=1e-9)
        assert report["grad_norm_err"] < 1e-9
        assert report["ok"] is ok, (off, report)
        assert report["grad_cosine"] >= checks.GRAD_COSINE_MIN
    limits = ppo_seq_conv.GRAD_LIMITS
    longer = {"w": ref["w"] * (1.0 + 2 * limits["grad_norm_err"]),
              "b": ref["b"]}
    assert not ppo_seq_conv.compare_loss_and_grads(
        (1.0, longer), (1.0, ref), 1.0)["ok"]
    loss_off = 1.0 + 2 * limits["loss_err"]
    assert not ppo_seq_conv.compare_loss_and_grads(
        (loss_off, ref), (1.0, ref), 1.0)["ok"]


def test_the_tiny_cells_program_feeds_the_new_metrics(monkeypatch):
    """One event of 1 us for every instruction the tiny cell's lowered
    program holds (``test_layer_map.py``'s rehearsal): the two new
    metrics read a number, and the mixers' three parts add up to
    ``shortconv`` and ``gqa`` together."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        scope_table,
    )
    from perfbench.harness import peaks, trace_reduce as tr
    from perfbench.rules import scope_lowering as sl
    from perfbench.rules import scope_roofline, scope_time_share

    cell = tiny_conv_cell()
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
    for name in ("shortconv_time_share", "gqa_time_share",
                 "dense_mlp_time_share", "moe_time_share"):
        assert values[name] is not None and 0 < values[name] < 100, values
    parts = sum(values[m] for m in ("mixer_proj_time_share",
                                    "mixer_pointwise_time_share",
                                    "mixer_core_time_share"))
    assert parts == pytest.approx(
        values["shortconv_time_share"] + values["gqa_time_share"], abs=1e-9
    )
    roofline = next(m for m in cell.per_layer
                    if m.name == "shortconv_roofline")
    share = scope_roofline.read(ctx, **roofline.args)
    assert share is not None and share > 0
    assert ctx.notes["scope_roofline"]["shortconv"]["least_s"] > 0
    gqa = next(m for m in cell.per_layer if m.name == "gqa_roofline")
    share = scope_roofline.read(ctx, **gqa.args)
    assert share is not None and share > 0
    assert ctx.notes["scope_roofline"]["gqa"]["least_s"] > 0
