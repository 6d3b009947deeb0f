"""CPU rehearsal of the ``ppo-kimivl-recall`` cell: the ``ppo_seq_mla``
runner on the tiny preset (the cell's own files with the model and the
traffic cut: counts and correctness, never a time), the operations
function at the published widths against a hand count, the reference
against the model through the runner's two checks, and the controls'
tool end to end.

As in ``test_ppo_seq.py``, the helper here swaps the configuration's
preset for the tiny one as well as cutting the traffic: this family's
model is too large for the CPU at any traffic.
"""

import copy
import dataclasses
import types

import pytest

from perfbench.harness import checks, driver, flops, rows, spec
from perfbench.operations import kimi_vl as operations
from perfbench.tests.helpers import no_span, nothing

CELL = "ppo-kimivl-recall"
TINY = "ppo-kimivl-tiny"


def tiny_mla_cell(**program):
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
    config["model"]["held"].update(
        num_hidden_layers=model.num_hidden_layers,
        experts_held=model.experts_held, first_expert=model.first_expert,
        vocab_size=model.vocab_size, router_width=model.n_routed_experts,
        capacity_factor=model.capacity_factor,
    )
    envs = program.get("num_envs", tiny["num_envs"])
    minibatches = program.setdefault("num_minibatches", 4)
    traffic["program"].update(program, num_envs=envs, compute_dtype=dtype)
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
    assert cell.family == "ppo_seq_mla" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {
        "env_steps_per_s_per_chip", "peak_hbm_gib", "setup_s"
    }
    names = {m.name for m in cell.per_layer}
    assert {"mla_time_share", "mla_absorbed_time_share",
            "dense_mlp_time_share", "mla_roofline", "moe_time_share",
            "moe_dispatch_time_share", "moe_experts_roofline",
            "moe_expert_load_imbalance", "lm_head_time_share",
            "model_flops_util", "optimizer_time_share"} <= names
    assert not {n for n in names
                if n.startswith(("conv_", "allreduce", "gdn_", "gated_"))}
    # the configuration holds every published key of the catalog's row
    # at its published value, but the three that are cut
    published, cut = cell.config["model"]["published"], cell.config["reduced"]
    assert sorted(cut) == ["n_routed_experts", "num_hidden_layers",
                           "vocab_size"]
    for key, value in published.items():
        assert (cell.config[key] == value) == (key not in cut), key
    assert (cell.config["num_hidden_layers"], cell.config["n_routed_experts"],
            cell.config["vocab_size"]) == (6, 8, 20480)
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (27, 64, 163840)


def test_the_preset_is_the_configuration():
    """``_check_model`` at the published widths: the preset's model
    holds every published key its dataclass has, and a width that
    differs is refused."""
    runner = driver.load_runner("ppo_seq_mla")(spec.load_cell(CELL), seed=0)
    runner._check_model(runner.cfg)
    assert runner.cfg.seq_model.kv_lora_rank == 512
    wrong = dataclasses.replace(
        runner.cfg,
        seq_model=dataclasses.replace(runner.cfg.seq_model, v_head_dim=64),
    )
    with pytest.raises(spec.SpecError, match="v_head_dim"):
        runner._check_model(wrong)


def test_ppo_seq_mla_runner_tiny():
    cell = tiny_mla_cell()
    runner = driver.load_runner("ppo_seq_mla")(cell, seed=3)
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
    assert len(window["log_rows"]) == 2 and window["log_window_s"] > 0
    assert all(r["moe_overflow_pairs"] == 0.0 for r in window["log_rows"])
    assert 0.0 < runner.moe_pairs_per_token < 2.0
    assert 0.0 < runner.moe_experts_touched_share <= 1.0
    per_it = cell.traffic["expect"]["optimizer_updates_per_iteration"]
    assert checks.optimizer_count(runner.state.opt_state) == 3 * per_it
    verdict = runner.verify()
    assert verdict == {"reference_rollout": True,
                       "reference_block_grads": True}, runner.report
    # float32 products on the CPU: the absorbed rollout through the
    # cache IS the reference's expanded pass, at the stated precision
    # and in float32 alike.
    rollout = runner.report["rollout"]
    assert rollout["log_prob"]["max"] < 1e-4
    assert rollout["against_float32"]["value"]["max"] < 1e-4
    assert runner.report["grad_cosine"] > 0.9999


@pytest.mark.parametrize("fault", [
    "none", "a stale cache row in one env", "one env a little off",
    "not a number",
])
def test_judge_rollout_under_this_familys_limits(fault):
    import numpy as np

    from perfbench.runners import ppo_seq_mla

    rng = np.random.default_rng(0)
    limits = ppo_seq_mla.ROLLOUT_LIMITS["log_prob"]
    scale = limits["p90"] / 4
    lp, v = rng.normal(0, scale, (512, 128)), rng.normal(0, scale, (512, 128))
    if fault == "a stale cache row in one env":
        lp[100:, 7] += rng.normal(0, 50 * scale, 412)
    elif fault == "one env a little off":
        lp[:, 77] += 1.5 * limits["env_p50_max"]
    elif fault == "not a number":
        v[3, 3] = np.nan
    assert ppo_seq_mla.judge_rollout(lp, v)["ok"] == (fault == "none")


def test_precision_controls_on_the_tiny_cell(tmp_path):
    """The controls' tool end to end on the CPU with bfloat16 products:
    every control of the family is computed and judged (the limits are
    set at the published widths; here only the plumbing is held)."""
    import json

    from perfbench.runners import ppo_seq_mla
    from perfbench.tools import precision_controls_mla

    runner = driver.load_runner("ppo_seq_mla")(
        tiny_mla_cell(compute_dtype="bfloat16"), seed=11
    )
    out = tmp_path / "controls.jsonl"
    code = precision_controls_mla.run(
        runner, grads=["all_bfloat16"], out=str(out)
    )
    assert code in (0, 1)
    rows_ = [json.loads(l) for l in out.read_text().splitlines()]
    final = {r["row"]: r for r in rows_ if "ok" in r}
    controls = {**ppo_seq_mla.CONTROLS, **ppo_seq_mla.REPORTED}
    assert set(final) == {"program", *controls}
    assert "grads" in final["program"] and "grads" in final["all_bfloat16"]
    # each control is another function than the stated reference
    for name in controls:
        assert final[name]["rollout"]["log_prob"]["max"] > 0.0, name


def test_an_overflowing_dispatch_fails_the_window():
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    cell = tiny_mla_cell()
    runner = driver.load_runner("ppo_seq_mla")(cell, seed=0)
    base = PRESETS[TINY][1]["seq_model"]
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
    """Every row against a count by hand (ISSUE 31's arithmetic)."""
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(
        cfg=types.SimpleNamespace(rollout_length=512, num_envs=128,
                                  compute_dtype="bfloat16"),
        moe_pairs_per_token=None, moe_experts_touched_share=None,
    )
    layers = {l.name: l for l in operations.layers(cell.config, runner)}
    H = 2048
    assert layers["mla_q_proj"].macs == 6 * H * 16 * 192 == 6 * 6_291_456
    assert layers["mla_kv_a_proj"].macs == 6 * H * 576
    # carrying the latent up: 512 x 4096 a token a layer
    assert layers["mla_kv_b_proj"].macs == 6 * 512 * 4096
    assert layers["mla_out_proj"].w_elems == 6 * H * H
    # causal at the published head sizes: (T + 1) / 2 keys a query
    assert layers["mla_scores_values"].macs == 6 * 16 * (192 + 128) * 513 // 2
    assert layers["mla_scores_values"].w_elems == 0
    # the cache of latents: 576 bf16 elements a row, (T + 1) / 2 rows
    # read a token a layer, one row written
    cache = layers["mla_cache"]
    assert (cache.macs, cache.w_elems) == (0, 0)
    assert cache.in_elems * cache.in_bytes == 6 * 576 * 513 // 2 * 2
    assert cache.out_elems == 6 * 576
    assert layers["dense_mlp"].macs == 3 * H * 11264  # one layer
    assert layers["moe_router"].macs == 5 * H * 64
    assert layers["moe_shared"].macs == 5 * 3 * H * 2816
    # 6 x 8 / 64 pairs a token expected, three products each
    assert layers["moe_routed"].macs == round(5 * 0.75 * 3 * H * 1408)
    assert layers["moe_routed"].w_elems == 5 * 8 * 3 * H * 1408
    assert layers["lm_head"].macs == H * 20481
    total = flops.forward_flops_per_sample(list(layers.values())) / 2
    assert 310e6 < total < 325e6  # ISSUE 31: ~313 M multiply-adds a token
    # MLA's projections are 36 % of an expert layer's active products
    mla = sum(layers[n].macs for n in ("mla_q_proj", "mla_kv_a_proj",
                                       "mla_kv_b_proj", "mla_out_proj")) / 6
    assert mla == 13_762_560
    # the counted pairs move the routed experts' row and nothing else
    runner.moe_pairs_per_token = 1.5
    runner.moe_experts_touched_share = 0.5
    more = {l.name: l for l in operations.layers(cell.config, runner)}
    assert more["moe_routed"].macs == 2 * layers["moe_routed"].macs
    assert 2 * more["moe_routed"].w_elems == layers["moe_routed"].w_elems
    assert more["moe_shared"] == layers["moe_shared"]
    # a float32 cache is twice the bytes
    runner.cfg.compute_dtype = "float32"
    wide = {l.name: l for l in operations.layers(cell.config, runner)}
    assert wide["mla_cache"].in_bytes == 2 * cache.in_bytes


def test_the_new_metrics_name_rows_the_operations_function_has():
    import json
    import os

    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=512, compute_dtype="bfloat16"
    ))
    rows_ = {l.name for l in operations.layers(cell.config, runner)}
    for metric in ("mla_roofline", "moe_experts_roofline"):
        path = os.path.join(spec.BENCH_DIR, "metrics", metric + ".json")
        with open(path) as f:
            args = json.load(f)["args"]
        assert set(args["layers"]) | set(args.get("rollout_only", ())) <= rows_
