"""CPU rehearsal of the ``ppo-granite-recall`` cell: the ``ppo_seq_ssm``
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
import json
import os
import types

import pytest

from perfbench.harness import checks, driver, flops, rows, spec
from perfbench.operations import granite_hybrid as operations
from perfbench.tests.helpers import no_span, nothing

CELL = "ppo-granite-recall"
TINY = "ppo-granite-tiny"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_ssm_cell(**program):
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
        layer_types=model.layer_types, vocab_size=model.vocab_size,
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
    assert cell.family == "ppo_seq_ssm" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {
        "env_steps_per_s_per_chip", "peak_hbm_gib", "setup_s"
    }
    names = {m.name for m in cell.per_layer}
    assert {"mamba_time_share", "mamba_state_time_share",
            "mamba_chunk_scan_time_share", "mamba_roofline",
            "gqa_time_share", "dense_mlp_time_share", "lm_head_time_share",
            "mixer_proj_time_share", "mixer_pointwise_time_share",
            "mixer_core_time_share", "rollout_mixer_proj_time_share",
            "rollout_mixer_pointwise_time_share", "sample_time_share",
            "model_flops_util", "optimizer_time_share"} <= names
    # a dense core: no metric of the expert layer, nor another core's
    assert not {n for n in names if n.startswith((
        "moe_", "rollout_moe_", "conv_", "allreduce", "gdn_", "gated_",
        "mla_", "diffusion_", "gqa_roofline", "gqa_block", "gqa_seq",
    ))}
    # the configuration holds every published key at its published
    # value, but the three that are cut
    published, cut = cell.config["model"]["published"], cell.config["reduced"]
    assert sorted(cut) == ["layer_types", "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        assert (cell.config[key] == value) == (key not in cut), key
    held = cell.config["model"]["held"]
    assert (cell.config["num_hidden_layers"], cell.config["vocab_size"]) == (
        10, 12544
    ) == (held["num_hidden_layers"], held["vocab_size"])
    assert cell.config["layer_types"] == held["layer_types"] == (
        published["layer_types"][:10]
    )
    assert held["layer_types"].count("attention") == 1
    assert (published["num_hidden_layers"], published["vocab_size"]) == (
        40, 100352
    )
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "granite-4.0-h-micro"]
        assert published == row["config"]
        assert cell.config["source"] == row["source_url"]


def test_the_preset_is_the_configuration():
    """``_check_model`` at the published widths: the preset's model
    holds every published key its dataclass has, and a width that
    differs is refused."""
    runner = driver.load_runner("ppo_seq_ssm")(spec.load_cell(CELL), seed=0)
    runner._check_model(runner.cfg)
    assert runner.cfg.seq_model.mamba_d_state == 128
    for wrong in ({"mamba_d_state": 64},
                  {"layer_types": ("mamba",) * 10}):
        cfg = dataclasses.replace(
            runner.cfg,
            seq_model=dataclasses.replace(runner.cfg.seq_model, **wrong),
        )
        with pytest.raises(spec.SpecError, match=next(iter(wrong))):
            runner._check_model(cfg)


def test_ppo_seq_ssm_runner_tiny():
    cell = tiny_ssm_cell()
    runner = driver.load_runner("ppo_seq_ssm")(cell, seed=3)
    assert runner.setup() == {"placement": True}
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["attempted"] == window["iterations"] == 2
    assert window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    assert set(window["checks"]) >= {
        "optimizer_updates", "env_steps", "fused_loss_terms",
        "mamba_state_retained",
    }
    # no key of an expert layer is asked of a core that has none
    assert "moe_dispatch_dropless" not in window["checks"]
    steps = runner.cfg.num_envs * runner.cfg.rollout_length
    assert window["end_to_end"]["env_steps_per_s_per_chip"] == (
        rows.steady_rate(window["row_times_s"], steps)
    )
    assert len(window["log_rows"]) == 2 and window["log_window_s"] > 0
    assert set(window["log_rows"][0]) == {"mamba_state_retention", "loss"}
    assert 0.0 < runner.mamba_state_retention < 1.0
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


@pytest.mark.parametrize("fault", [
    "none", "a stale state in one env", "one env a little off",
    "not a number",
])
def test_judge_rollout_under_this_familys_limits(fault):
    import numpy as np

    from perfbench.runners import ppo_seq_ssm

    rng = np.random.default_rng(0)
    limits = ppo_seq_ssm.ROLLOUT_LIMITS["log_prob"]
    scale = min(limits["p90"] / 4, limits["env_p50_max"] / 2)
    lp, v = rng.normal(0, scale, (512, 32)), rng.normal(0, scale, (512, 32))
    if fault == "a stale state in one env":
        lp[100:, 7] += rng.normal(0, 50 * limits["p99"], 412)
    elif fault == "one env a little off":
        lp[:, 17] += 1.5 * limits["env_p50_max"]
    elif fault == "not a number":
        v[3, 3] = np.nan
    assert ppo_seq_ssm.judge_rollout(lp, v)["ok"] == (fault == "none")


def test_precision_controls_on_the_tiny_cell(tmp_path):
    """The controls' tool end to end on the CPU with bfloat16 products:
    every control of the family is computed and judged (the limits are
    set at the published widths; here only the plumbing is held)."""
    from perfbench.runners import ppo_seq_ssm
    from perfbench.tools import precision_controls_ssm

    runner = driver.load_runner("ppo_seq_ssm")(
        tiny_ssm_cell(compute_dtype="bfloat16"), seed=11
    )
    out = tmp_path / "controls.jsonl"
    code = precision_controls_ssm.run(
        runner, grads=["all_bfloat16", "state_bfloat16"], out=str(out)
    )
    assert code in (0, 1)
    rows_ = [json.loads(l) for l in out.read_text().splitlines()]
    final = {r["row"]: r for r in rows_ if "ok" in r}
    controls = {**ppo_seq_ssm.CONTROLS, **ppo_seq_ssm.REPORTED}
    assert set(final) == {"program", *controls}
    # the four steps below the stated precision, judged or reported
    assert set(controls) == {
        "state_bfloat16", "scan_bfloat16", "norms_bfloat16", "all_bfloat16"
    }
    assert "grads" in final["program"] and "grads" in final["all_bfloat16"]
    assert "grads" in final["state_bfloat16"]
    # each control is another function than the stated reference
    for name in controls:
        assert final[name]["rollout"]["log_prob"]["max"] > 0.0, name


def test_a_state_that_keeps_nothing_fails_the_window():
    """``mamba_state_retained``: a counter outside (0, 1) in any
    iteration is a window that tested nothing."""
    cell = tiny_ssm_cell()
    runner = driver.load_runner("ppo_seq_ssm")(cell, seed=0)
    runner.setup()
    iteration = runner.fns.iteration

    def forgetful(state):
        state, metrics = iteration(state)  # (keeps what it reports)
        runner._reported[-1] = dict(metrics, mamba_state_retention=0.0)
        return state, metrics

    runner.fns = runner.fns._replace(iteration=forgetful)
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["failed"] == 0
    assert not window["checks"]["mamba_state_retained"]


def test_operations_at_the_published_widths():
    """Every row against a count by hand (ISSUE 37's arithmetic)."""
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=512, num_envs=32, compute_dtype="bfloat16"
    ))
    layers = {l.name: l for l in operations.layers(cell.config, runner)}
    H = 2048
    # [z | xBC | dt] = 4096 | 4352 | 64
    assert layers["mamba_in_proj"].macs == 9 * H * 8512 == 9 * 17_432_576
    assert layers["mamba_out_proj"].w_elems == 9 * 4096 * H
    # C B^T 128 x 128, the masked product 4096 x 128, the chunk state
    # and the read-out 4096 x 128 each, a token a layer
    assert layers["mamba_scan"].macs == 9 * (16_384 + 3 * 524_288)
    assert layers["mamba_scan"].w_elems == 0
    assert layers["mamba_scan"].in_bytes == 4
    # the state: 2 MiB read and 2 MiB written an env a layer a step
    state = layers["mamba_state"]
    assert (state.macs, state.w_elems, state.out_elems) == (0, 0, 0)
    assert state.in_elems * state.in_bytes == 9 * 2 * 2 * 2**20
    assert layers["gqa_q_proj"].macs == H * H
    assert layers["gqa_kv_proj"].macs == H * 2 * 512
    assert layers["gqa_out_proj"].macs == H * H
    # causal: (T + 1) / 2 keys a query, a score and a value each
    assert layers["gqa_scores_values"].macs == 32 * 64 * 513
    cache = layers["gqa_cache"]
    assert (cache.macs, cache.w_elems) == (0, 0)
    assert cache.in_elems * cache.in_bytes == 2 * 512 * 513 // 2 * 2
    assert cache.out_elems == 2 * 512
    assert layers["dense_mlp"].macs == 10 * 3 * H * 8192 == 10 * 50_331_648
    assert layers["lm_head"].macs == H * 12545
    # a Mamba-2 layer's weights' products are the parameter count less
    # the small vectors: the issue's 76,182,976 a layer
    per_layer = (17_432_576 + 8_388_608 + 50_331_648)
    assert per_layer + 21_760 + 192 + 4_096 + 4_096 == 76_182_976
    total = flops.forward_flops_per_sample(list(layers.values())) / 2
    assert 780e6 < total < 800e6  # ~787 M multiply-adds a token
    # the state is between a third and a half of a decode step's bytes
    weights = 2 * sum(l.w_elems for l in layers.values())  # bfloat16
    moved = 32 * state.in_elems * state.in_bytes
    assert 1.4e9 < weights < 1.6e9 and 1.1e9 < moved < 1.3e9
    # a float32 cache is twice the bytes
    runner.cfg.compute_dtype = "float32"
    wide = {l.name: l for l in operations.layers(cell.config, runner)}
    assert wide["gqa_cache"].in_bytes == 2 * cache.in_bytes


def test_the_new_metrics_name_rows_the_operations_function_has():
    cell = spec.load_cell(CELL)
    runner = types.SimpleNamespace(cfg=types.SimpleNamespace(
        rollout_length=512, compute_dtype="bfloat16"
    ))
    rows_ = {l.name for l in operations.layers(cell.config, runner)}
    path = os.path.join(spec.BENCH_DIR, "metrics", "mamba_roofline.json")
    with open(path) as f:
        args = json.load(f)["args"]
    assert set(args["layers"]) | set(args["rollout_only"]) <= rows_
    assert args["scope"] == "mamba"
    for name, scope in (("mamba_time_share", "mamba"),
                        ("mamba_state_time_share", "mamba_state"),
                        ("mamba_chunk_scan_time_share", "mamba_chunk_scan")):
        with open(os.path.join(spec.BENCH_DIR, "metrics", name + ".json")) as f:
            decl = json.load(f)
        assert decl["rule"] == "scope_time_share"
        assert decl["args"] == {"scope": scope}
