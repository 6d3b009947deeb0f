"""The sequence cores' layer map one level down (PR 35): thirteen
``scope_time_share`` metrics over the sub-scopes of
``utils/profiling.py`` (``mixer_proj``, ``mixer_pointwise``,
``mixer_core``, ``mla_seq_attend``, ``gdn_chunk_solve``,
``gdn_chunk_products``, ``moe_combine``, ``sample``) and, with
``without``, over the rollout's part of a layer.

On a synthetic join ``without`` is worked out by hand; every new
metric's file loads, reads a scope the program declares and lists
accepted cells; and at a tiny size on the CPU the real lowering of each
language-model cell feeds every one of its metrics, with the sums the
layer map promises.
"""

import json
import os
import types

import pytest

from perfbench.harness import driver, spec, trace_reduce as tr
from perfbench.rules import scope_lowering as sl, scope_time_share

LM3 = ["ppo-qwen3next-recall", "ppo-kimivl-recall", "ppo-sdar-turns"]
ROLLOUT_ONLY = ["update", "advantage"]
# metric -> (scope, without, the cells that report it)
NEW = {
    "mixer_proj_time_share": ("mixer_proj", None, LM3),
    "mixer_pointwise_time_share": ("mixer_pointwise", None, LM3),
    "mixer_core_time_share": ("mixer_core", None, LM3),
    "rollout_mixer_proj_time_share": ("mixer_proj", ROLLOUT_ONLY, LM3),
    "rollout_mixer_pointwise_time_share":
        ("mixer_pointwise", ROLLOUT_ONLY, LM3),
    "gqa_seq_attend_time_share": ("gqa_seq_attend", None, LM3[2:]),
    "mla_seq_attend_time_share": ("mla_seq_attend", None, LM3[1:2]),
    "gdn_chunk_solve_time_share": ("gdn_chunk_solve", None, LM3[:1]),
    "gdn_chunk_products_time_share": ("gdn_chunk_products", None, LM3[:1]),
    "moe_combine_time_share": ("moe_combine", None, LM3),
    "sample_time_share": ("sample", None, LM3),
    "rollout_moe_experts_time_share": ("moe_experts", ROLLOUT_ONLY, LM3),
    "rollout_moe_dispatch_time_share": ("moe_dispatch", ROLLOUT_ONLY, LM3),
}
# the whole-iteration twin of a rollout-only metric
TWIN = {
    "rollout_mixer_proj_time_share": "mixer_proj_time_share",
    "rollout_mixer_pointwise_time_share": "mixer_pointwise_time_share",
    "rollout_moe_experts_time_share": None,     # `moe_experts` has none
    "rollout_moe_dispatch_time_share": "moe_dispatch_time_share",
}
MIXERS = {
    "ppo-qwen3next-recall": ["gdn_time_share", "gated_attn_time_share"],
    "ppo-kimivl-recall": ["mla_time_share"],
    "ppo-sdar-turns": ["gqa_time_share"],
}
PARTS = ["mixer_proj_time_share", "mixer_pointwise_time_share",
         "mixer_core_time_share"]


def synthetic_ctx(self_s_by_phases, busy_s):
    """A context whose join is given: the rule reads nothing else."""
    return types.SimpleNamespace(
        reduced=types.SimpleNamespace(busy_s=busy_s, chips=1),
        notes={"scope_join": {"why": None,
                              "self_s_by_phases": self_s_by_phases}},
    )


# ---- `without` on a join worked out by hand ------------------------------


def test_without_update_and_advantage_reads_the_rollouts_part_only():
    ctx = synthetic_ctx({
        "rollout/policy_act/moe/moe_experts": 3.0,
        "rollout/policy_act/moe/moe_dispatch": 2.0,
        "rollout/policy_act/moe/moe_dispatch/moe_combine": 0.5,
        "update/loss_grad/moe/moe_experts": 4.0,
        "update/loss_grad/moe/moe_dispatch": 1.0,
        "advantage/moe/moe_experts": 0.25,
        "advantage/moe/moe_dispatch/moe_combine": 0.25,
        "rollout/env_step": 1.0,
        sl.NO_PHASE: 8.0,
    }, busy_s=20.0)
    read = scope_time_share.read
    assert read(ctx, scope="moe_experts") == pytest.approx(36.25)
    assert read(ctx, scope="moe_experts", without=ROLLOUT_ONLY) == (
        pytest.approx(15.0)
    )
    # a scope nested in the one asked for counts for it, in both
    assert read(ctx, scope="moe_dispatch") == pytest.approx(18.75)
    assert read(ctx, scope="moe_dispatch", without=ROLLOUT_ONLY) == (
        pytest.approx(12.5)
    )
    assert read(ctx, scope="moe_combine", without=ROLLOUT_ONLY) == (
        pytest.approx(2.5)
    )
    # the update's part is the whole less the rollout's less advantage's
    update = read(ctx, scope="moe_experts", without=["rollout", "advantage"])
    assert update == pytest.approx(20.0)
    # a scope the program does not have (the parent commit's) reads 0,
    # and a join that does not stand reads nothing
    assert read(ctx, scope="mixer_proj", without=ROLLOUT_ONLY) == 0.0
    ctx.notes["scope_join"]["why"] = "no device operation in the trace"
    assert read(ctx, scope="moe_experts", without=ROLLOUT_ONLY) is None


# ---- the files -------------------------------------------------------------


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_new_metric_loads_and_lists_accepted_cells(metric):
    from actor_critic_algs_on_tensorflow_tpu.utils import profiling

    scope, without, cells = NEW[metric]
    bench = spec.load_benchmark()
    entries = [m["name"] for m in bench["per_layer"]]
    entry = bench["per_layer"][entries.index(metric)]
    # appended: after everything PR 34's benchmark had
    assert entries.index(metric) > entries.index("diffusion_passes_per_token")
    assert entry == {
        "name": metric, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "models",
        "moves": "env_steps_per_s_per_chip", "workloads": cells,
    }
    accepted = {w["name"] for w in bench["workloads"]}
    assert set(cells) <= accepted
    with open(os.path.join(spec.BENCH_DIR, "metrics", metric + ".json")) as f:
        decl = json.load(f)
    assert decl["name"] == metric and decl["rule"] == "scope_time_share"
    assert decl["args"] == (
        {"scope": scope} if without is None
        else {"scope": scope, "without": without}
    )
    assert len(decl["what"]) > 40
    assert scope in profiling.LAYER_SCOPES
    assert set(without or ()) <= set(profiling.PHASES)
    for name in accepted:
        cell = spec.load_cell(name)
        declared = {m.name: m for m in cell.per_layer}
        assert (metric in declared) == (name in cells), (metric, name)
        if name in cells:
            assert declared[metric].moves in {m.name for m in cell.end_to_end}


def test_the_old_cells_get_no_new_metric():
    for name in ("ppo-pong", "ppo-breakout", "ppo-pong-x4", "impala-pong"):
        reported = {m.name for m in spec.load_cell(name).per_layer}
        assert not reported & set(NEW), name


# ---- the real lowering, tiny, on the CPU ---------------------------------


def _tiny(name):
    from perfbench.tests import (
        test_ppo_seq, test_ppo_seq_diffusion, test_ppo_seq_mla,
    )

    return {
        "ppo-qwen3next-recall": test_ppo_seq.tiny_seq_cell,
        "ppo-kimivl-recall": test_ppo_seq_mla.tiny_mla_cell,
        "ppo-sdar-turns": test_ppo_seq_diffusion.tiny_diffusion_cell,
    }[name]()


@pytest.mark.parametrize("name", LM3)
def test_a_language_model_cells_program_feeds_its_layer_map(
    monkeypatch, name
):
    """One event of 1 us for every instruction the tiny cell's lowered
    program holds: every ``scope_time_share`` metric the cell declares
    reads a number, the rollout's part is no more than the whole, the
    combine is inside the dispatch, and the mixers' three parts add up
    to the mixers (but for the input norm Qwen3-Next traces before its
    mixer's scope opens, counted as pointwise)."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        scope_table,
    )

    cell = _tiny(name)
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
    )
    values = {
        m.name: scope_time_share.read(ctx, **m.args)
        for m in spec.load_cell(name).per_layer
        if m.rule == "scope_time_share"
    }
    joined = ctx.notes["scope_join"]
    assert joined["why"] is None, joined
    assert joined["coverage_pct"] == pytest.approx(100.0, abs=0.5)
    mine = {m for m, (_, _, cells) in NEW.items() if name in cells}
    assert mine <= set(values)
    assert all(v is not None and 0 < v < 100 for v in values.values()), values
    for rollout, whole in TWIN.items():
        if whole is not None:
            assert values[rollout] < values[whole], (rollout, values)
    assert values["moe_combine_time_share"] < values["moe_dispatch_time_share"]
    assert values["sample_time_share"] < values["rollout_time_share"]
    parts = sum(values[m] for m in PARTS)
    mixers = sum(values[m] for m in MIXERS[name])
    if name == "ppo-qwen3next-recall":
        assert mixers < parts < mixers + 2.0, (parts, mixers)
    else:
        assert parts == pytest.approx(mixers, abs=1e-9), (parts, mixers)
    nested = {"gqa_seq_attend_time_share", "mla_seq_attend_time_share",
              "gdn_chunk_solve_time_share", "gdn_chunk_products_time_share"}
    for metric in nested & mine:
        assert values[metric] < values["mixer_core_time_share"]
