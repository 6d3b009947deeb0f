"""CPU rehearsal of the ``ppo-sdar-turns`` cell: the ``ppo_seq_diffusion``
runner on the tiny preset (the cell's own files with the model and the
traffic cut: counts and correctness, never a time), the operations
function at the published widths against a hand count, the reference
against the model through the runner's two checks, and the controls'
tool end to end.

As in ``test_ppo_seq_mla.py``, the helper here swaps the
configuration's preset for the tiny one as well as cutting the traffic:
this family's model is too large for the CPU at any traffic.
"""

import copy
import dataclasses
import types

import pytest

from perfbench.harness import checks, driver, flops, rows, spec
from perfbench.operations import sdar as operations
from perfbench.tests.helpers import no_span, nothing

CELL = "ppo-sdar-turns"
TINY = "ppo-sdar-tiny"


def tiny_diffusion_cell(**program):
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
        vocab_size=model.vocab_size, router_width=model.num_experts,
        capacity_factor=model.capacity_factor,
        mask_token_id=model.mask_token_id,
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
    assert cell.family == "ppo_seq_diffusion" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {
        "env_steps_per_s_per_chip", "peak_hbm_gib", "setup_s"
    }
    names = {m.name for m in cell.per_layer}
    assert {"gqa_time_share", "gqa_block_step_time_share", "gqa_roofline",
            "diffusion_passes_per_token", "moe_time_share",
            "moe_dispatch_time_share", "moe_experts_roofline",
            "moe_expert_load_imbalance", "lm_head_time_share",
            "model_flops_util", "optimizer_time_share"} <= names
    assert not {n for n in names if n.startswith(
        ("conv_", "allreduce", "gdn_", "gated_", "mla_", "dense_mlp")
    )}
    # the configuration holds every published key of the catalog's row
    # at its published value, but the three that are cut
    published, cut = cell.config["model"]["published"], cell.config["reduced"]
    assert sorted(cut) == ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        assert (cell.config[key] == value) == (key not in cut), key
    assert (cell.config["num_hidden_layers"], cell.config["num_experts"],
            cell.config["vocab_size"]) == (6, 16, 18992)
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (48, 128, 151936)


def test_the_preset_is_the_configuration():
    """``_check_model`` at the published widths: the preset's model
    holds every published key its dataclass has and the sampler's
    numbers, and one that differs is refused."""
    runner = driver.load_runner("ppo_seq_diffusion")(
        spec.load_cell(CELL), seed=0
    )
    runner._check_model(runner.cfg)
    model = runner.cfg.seq_model
    assert (model.num_key_value_heads, model.head_dim) == (4, 128)
    assert model.cache_width == 1024 and model.reveal == 1
    env = runner.cfg.env_params
    assert env.episode_length == runner.cfg.rollout_length == 144
    assert env.tokens_per_episode == 192 and env.mask_id == 18991
    for key, value in (("head_dim", 64), ("block_length", 8),
                       ("mask_token_id", 0)):
        wrong = dataclasses.replace(
            runner.cfg,
            seq_model=dataclasses.replace(model, **{key: value}),
        )
        with pytest.raises(spec.SpecError, match=key):
            runner._check_model(wrong)


def test_ppo_seq_diffusion_runner_tiny():
    cell = tiny_diffusion_cell()
    runner = driver.load_runner("ppo_seq_diffusion")(cell, seed=3)
    assert runner.setup() == {"placement": True}
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["attempted"] == window["iterations"] == 2
    assert window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    assert set(window["checks"]) >= {
        "optimizer_updates", "env_steps", "fused_loss_terms",
        "moe_dispatch_dropless", "sampler_schedule",
    }
    steps = runner.cfg.num_envs * runner.cfg.rollout_length
    assert window["end_to_end"]["env_steps_per_s_per_chip"] == (
        rows.steady_rate(window["row_times_s"], steps)
    )
    assert len(window["log_rows"]) == 2 and window["log_window_s"] > 0
    for row in window["log_rows"]:
        assert row["moe_overflow_pairs"] == 0.0
        assert row["diffusion_passes_per_committed_token"] == (
            pytest.approx(0.75)
        )
        assert row["diffusion_revealed_per_denoise_pass"] == 1.0
        assert row["diffusion_scored_position_share"] == pytest.approx(1 / 6)
    assert 0.0 < runner.moe_pairs_per_token < 2.0
    per_it = cell.traffic["expect"]["optimizer_updates_per_iteration"]
    assert checks.optimizer_count(runner.state.opt_state) == 3 * per_it
    verdict = runner.verify()
    assert verdict == {"reference_rollout": True,
                       "reference_block_grads": True}, runner.report
    # float32 products on the CPU: the rollout through the cache IS the
    # reference's pass over the trajectory, at the stated precision and
    # in float32 alike.
    rollout = runner.report["rollout"]
    assert 0.0 < rollout["log_prob"]["max"] < 1e-4
    assert rollout["against_float32"]["value"]["max"] < 1e-4
    assert runner.report["grad_cosine"] > 0.9999


def test_log_probs_are_compared_on_the_passes_that_scored():
    import numpy as np

    from perfbench.runners import ppo_seq_diffusion as family

    got = np.zeros((6, 3)), np.ones((6, 3))
    got[0][1:5] = -2.0
    want = np.zeros((6, 3)), np.ones((6, 3))
    want[0][1:5] = -2.5
    log_prob, value = family.errors(got, want)
    assert log_prob.shape == (4, 3) and value.shape == (6, 3)
    assert (log_prob == 0.5).all() and (value == 0.0).all()
    # a log-probability where the reference has none is an error, kept
    got[0][5, 0] = -1.0
    assert family.errors(got, want)[0].shape == (5, 3)


@pytest.mark.parametrize("fault", [
    "none", "a stale cache row in one env", "one env a little off",
    "not a number",
])
def test_judge_rollout_under_this_familys_limits(fault):
    import numpy as np

    from perfbench.runners import ppo_seq_diffusion as family

    rng = np.random.default_rng(0)
    limits = family.ROLLOUT_LIMITS["log_prob"]
    scale = limits["p90"] / 4
    lp, v = rng.normal(0, scale, (96, 128)), rng.normal(0, scale, (144, 128))
    if fault == "a stale cache row in one env":
        lp[20:, 7] += rng.normal(0, 50 * scale, 76)
    elif fault == "one env a little off":
        lp[:, 77] += 1.5 * limits["env_p50_max"]
    elif fault == "not a number":
        v[3, 3] = np.nan
    assert family.judge_rollout(lp, v)["ok"] == (fault == "none")


def test_precision_controls_on_the_tiny_cell(tmp_path):
    """The controls' tool end to end on the CPU with bfloat16 products:
    every control of the family is computed and judged (the limits are
    set at the published widths; here only the plumbing is held)."""
    import json

    from perfbench.runners import ppo_seq_diffusion as family
    from perfbench.tools import precision_controls_mla as tool

    runner = driver.load_runner("ppo_seq_diffusion")(
        tiny_diffusion_cell(compute_dtype="bfloat16"), seed=11
    )
    out = tmp_path / "controls.jsonl"
    code = tool.run(runner, grads=["all_bfloat16"], out=str(out))
    assert code in (0, 1)
    rows_ = [json.loads(l) for l in out.read_text().splitlines()]
    final = {r["row"]: r for r in rows_ if "ok" in r}
    controls = family.CONTROLS
    assert len(controls) == 5 and not hasattr(family, "REPORTED")
    assert set(final) == {"program", *controls}
    assert "grads" in final["program"] and "grads" in final["all_bfloat16"]
    # each control is another function than the stated reference
    for name in controls:
        assert final[name]["rollout"]["log_prob"]["max"] > 0.0, name


def test_an_overflowing_dispatch_fails_the_window():
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    cell = tiny_diffusion_cell()
    runner = driver.load_runner("ppo_seq_diffusion")(cell, seed=0)
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


def _shapes_runner(**cfg):
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    return types.SimpleNamespace(
        cfg=types.SimpleNamespace(
            env_params=PRESETS["ppo-sdar-turns"][1]["env_params"],
            compute_dtype="bfloat16", **cfg
        ),
        moe_pairs_per_token=None, moe_experts_touched_share=None,
    )


def test_operations_at_the_published_widths():
    """Every row against a count by hand (ISSUE 33's arithmetic): a
    sample is a pass, 4 positions."""
    cell = spec.load_cell(CELL)
    runner = _shapes_runner()
    layers = {l.name: l for l in operations.layers(cell.config, runner)}
    H, L = 2048, 4
    assert layers["gqa_q_proj"].macs == L * 6 * H * 4096
    assert layers["gqa_kv_proj"].macs == L * 6 * H * 1024
    assert layers["gqa_out_proj"].w_elems == 6 * 4096 * H
    # the projections: 18.874 M a layer and position
    assert sum(layers[n].macs for n in ("gqa_q_proj", "gqa_kv_proj",
                                        "gqa_out_proj")) == (
        L * 6 * 18_874_368
    )
    # turn k opens on 8 k tokens: its first pass sees 8 k + 4 rows, its
    # other five 8 k + 8; the mean over 24 turns
    seen = operations.visible_rows(runner.cfg.env_params)
    assert seen == pytest.approx((96 + 5 * 100) / 6)
    assert layers["gqa_scores_values"].macs == round(
        L * 6 * 32 * 256 * seen
    )
    assert layers["gqa_scores_values"].w_elems == 0
    # the cache: the visible rows of 1,024 bf16 elements read once a
    # pass and layer, the block's 4 rows written
    cache = layers["gqa_cache"]
    assert (cache.macs, cache.w_elems, cache.in_bytes) == (0, 0, 2)
    assert cache.in_elems == round(6 * 1024 * seen)
    assert cache.out_elems == 6 * 1024 * L
    assert layers["moe_router"].macs == L * 6 * H * 128
    # 8 x 16 / 128 = 1 pair a position expected, three products each
    assert layers["moe_routed"].macs == L * 6 * 3 * H * 768
    assert layers["moe_routed"].w_elems == 6 * 16 * 3 * H * 768
    assert layers["lm_head"].macs == L * H * 18992 + H
    total = flops.forward_flops_per_sample(list(layers.values())) / 2 / L
    assert 180e6 < total < 190e6  # ISSUE 33: ~182 M + the scores' 4.9 M
    # the counted pairs move the routed experts' row and nothing else
    runner.moe_pairs_per_token = 2.0
    runner.moe_experts_touched_share = 0.5
    more = {l.name: l for l in operations.layers(cell.config, runner)}
    assert more["moe_routed"].macs == 2 * layers["moe_routed"].macs
    assert 2 * more["moe_routed"].w_elems == layers["moe_routed"].w_elems
    assert more["gqa_q_proj"] == layers["gqa_q_proj"]
    # a float32 cache is twice the bytes
    runner.cfg.compute_dtype = "float32"
    wide = {l.name: l for l in operations.layers(cell.config, runner)}
    assert wide["gqa_cache"].in_bytes == 2 * cache.in_bytes


def test_the_new_metrics_name_rows_the_operations_function_has():
    import json
    import os

    cell = spec.load_cell(CELL)
    rows_ = {l.name for l in operations.layers(cell.config, _shapes_runner())}
    for metric in ("gqa_roofline", "moe_experts_roofline"):
        path = os.path.join(spec.BENCH_DIR, "metrics", metric + ".json")
        with open(path) as f:
            args = json.load(f)["args"]
        assert set(args["layers"]) | set(args.get("rollout_only", ())) <= rows_
