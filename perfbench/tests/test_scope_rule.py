"""The per-phase reader (``rules/scope_time_share.py`` over
``rules/scope_lowering.py``).

On recorded data (``data/scope_trace.csv``: operations of one
``ppo-breakout`` iteration as the chip's trace names them, each once,
with the self time the trace gave it; ``data/scope_compiled.txt``: the
same instructions of ``jit_local_iteration`` as the chip's compiler
printed them, ``metadata=`` and all — my chip run, PR 24) the shares
are worked out by hand below. The join has to refuse what it cannot
stand behind: 2 % of the time without an instruction, a lowered
program the trace does not hold, one key under two phases. And at a
tiny size on the CPU the real lowering of both families feeds it.
"""

import json
import os
import types

import pytest

from perfbench.harness import driver, spec, trace_reduce as tr
from perfbench.rules import scope_lowering as sl, scope_time_share
from perfbench.tests.helpers import tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


def declared(cell_name):
    """``{metric: args}`` of the cell's ``scope_time_share`` metrics."""
    return {
        m.name: m.args for m in spec.load_cell(cell_name).per_layer
        if m.rule == "scope_time_share"
    }


def recorded_ctx(monkeypatch, texts, trace=None):
    reduced = tr.reduce_trace(
        trace or tr.load_csv(os.path.join(DATA, "scope_trace.csv"))
    )
    monkeypatch.setitem(sl._LOWER, "recorded", lambda runner: texts)
    return types.SimpleNamespace(
        reduced=reduced, notes={}, runner=None,
        cell=types.SimpleNamespace(family="recorded"),
    )


def compiled_text():
    with open(os.path.join(DATA, "scope_compiled.txt")) as f:
        return f.read()


# ---- the recorded iteration ---------------------------------------------

with open(os.path.join(DATA, "scope_expected.json")) as _f:
    EXPECTED = json.load(_f)


@pytest.mark.parametrize("metric", sorted(EXPECTED["shares_pct"]))
def test_recorded_shares_are_the_hand_computed_ones(monkeypatch, metric):
    ctx = recorded_ctx(monkeypatch, [compiled_text()])
    value = scope_time_share.read(ctx, **declared("ppo-breakout")[metric])
    assert value == pytest.approx(EXPECTED["shares_pct"][metric], rel=1e-9)
    joined = ctx.notes["scope_join"]
    assert joined["why"] is None and joined["coverage_pct"] == 100.0
    assert joined["programs"] == ["jit_local_iteration"]


def test_recorded_shares_close():
    """rollout (env_step is inside it) + advantage + what the update
    spends outside loss and optimizer + loss_grad + optimizer +
    unscoped is all of the busy time: every operation is in exactly one
    of them."""
    assert sum(EXPECTED["self_ns_by_phases"].values()) == EXPECTED["busy_ns"]
    assert sum(o["self_ns"] for o in EXPECTED["operations"]) == (
        EXPECTED["busy_ns"]
    )
    s = EXPECTED["shares_pct"]
    assert sum(
        s[m] for m in s if m != "env_step_time_share"
    ) == pytest.approx(100.0)
    assert s["env_step_time_share"] < s["rollout_time_share"]


def test_two_percent_without_an_instruction_reads_as_nothing(monkeypatch):
    trace = tr.load_csv(os.path.join(DATA, "scope_trace.csv"))
    ops = trace.devices[0].ops
    end = max(e.end_ns for e in ops)
    busy = sum(s for _, s in tr.self_times(ops))
    ops.append(tr.Event(
        "%fusion.9999 = f32[8]{0} fusion(f32[8]{0} %nobody), kind=kLoop",
        end, busy * 2 / 98,
    ))
    ctx = recorded_ctx(monkeypatch, [compiled_text()], trace)
    for args in declared("ppo-breakout").values():
        assert scope_time_share.read(ctx, **args) is None
    joined = ctx.notes["scope_join"]
    assert joined["coverage_pct"] == pytest.approx(98.0)
    assert "98.00 %" in joined["why"] and "< 99.0 %" in joined["why"]


def test_a_program_the_trace_does_not_hold_reads_as_nothing(monkeypatch):
    other = compiled_text().replace(
        "HloModule jit_local_iteration", "HloModule jit_local_learner_step"
    )
    ctx = recorded_ctx(monkeypatch, [other])
    assert scope_time_share.read(ctx, scope="rollout") is None
    assert "jit_local_learner_step" in ctx.notes["scope_join"]["why"]
    assert "coverage_pct" not in ctx.notes["scope_join"]


def test_one_key_in_two_programs_under_two_phases_is_not_found(monkeypatch):
    """The actor's program holds an instruction of the learner's name
    and shape under another phase: its time is nobody's, and here that
    is enough to sink the join."""
    text = compiled_text()
    victim = EXPECTED["largest_minibatch_prep_instruction"]
    line = next(l for l in text.splitlines() if l.strip().startswith(victim))
    second = "\n".join([
        "HloModule jit_actor_rollout, is_scheduled=true", "",
        "ENTRY %main () -> f32[] {",
        line.replace("/update/", "/rollout/").replace(
            "/minibatch_prep/", "/env_step/"),
        "}", "",
    ])
    trace = tr.load_csv(os.path.join(DATA, "scope_trace.csv"))
    trace.devices[0].modules.append(
        tr.Event("jit_actor_rollout(1)", 0.0, 1.0)
    )
    ctx = recorded_ctx(monkeypatch, [text, second], trace)
    assert scope_time_share.read(ctx, scope="minibatch_prep") is None
    joined = ctx.notes["scope_join"]
    assert joined["ambiguous_keys"] == 1
    lost = 100.0 - joined["coverage_pct"]
    assert lost == pytest.approx(EXPECTED["largest_minibatch_prep_pct"])
    # The same second program under the SAME phase is no conflict.
    ctx = recorded_ctx(monkeypatch, [text, second.replace(
        "/rollout/", "/update/").replace("/env_step/", "/minibatch_prep/")],
        trace)
    prep = declared("ppo-breakout")["minibatch_prep_time_share"]
    assert scope_time_share.read(ctx, **prep) == (
        pytest.approx(EXPECTED["shares_pct"]["minibatch_prep_time_share"])
    )


def test_a_program_without_the_phases_reads_as_nothing(monkeypatch):
    """The parent commit's checkout: no ``scope_table`` to import, and
    nothing is lowered for it; or compiled text that names no phase."""
    from actor_critic_algs_on_tensorflow_tpu.utils import profiling

    bare = "\n".join(
        l.split(", metadata=")[0] for l in compiled_text().splitlines()
    )
    ctx = recorded_ctx(monkeypatch, [bare])
    assert scope_time_share.read(ctx, scope=None) is None
    assert "no declared phase" in ctx.notes["scope_join"]["why"]

    def never(runner):
        raise AssertionError("lowered for a program without phases")

    ctx = recorded_ctx(monkeypatch, [])
    monkeypatch.setitem(sl._LOWER, "recorded", never)
    monkeypatch.delattr(profiling, "scope_table")
    assert scope_time_share.read(ctx, scope="rollout") is None
    assert "scope_table" in ctx.notes["scope_join"]["why"]


@pytest.mark.parametrize("traced,compiled", [
    # operand shapes and /*index=*/ marks among operands
    ("%fusion.462 = (bf16[1024]{0:T(1024)(128)(2,1)}, s32[1024]{0:T(1024)S(1)}) "
     "fusion(bf16[1024,4]{0,1:T(4,128)(2,1)S(1)} %get-tuple-element.5762, "
     "u32[]{:T(128)S(6)} %xor.7875), kind=kLoop, calls=%fused_computation.50",
     "%fusion.462 = (bf16[1024]{0:T(1024)(128)(2,1)}, s32[1024]{0:T(1024)S(1)}) "
     "fusion(%get-tuple-element.5762, /*index=5*/%xor.7875), kind=kLoop, "
     "calls=%fused_computation.50"),
    # the trace's async-start for the text's slice-start
    ("%slice-start.24 = ((u8[1024,84,84,4]{0,3,2,1:T(4,128)(4,1)}), "
     "u8[1024,21,84,4]{0,3,2,1:T(4,128)(4,1)S(1)}, s32[]{:S(2)}) "
     "async-start(u8[1024,84,84,4]{0,3,2,1:T(4,128)(4,1)} %gte.6218), "
     "calls=%async_computation.24",
     "%slice-start.24 = ((u8[1024,84,84,4]{0,3,2,1:T(4,128)(4,1)}), "
     "u8[1024,21,84,4]{0,3,2,1:T(4,128)(4,1)S(1)}, s32[]{:S(2)}) "
     "slice-start(%gte.6218), slice={[0:1024], [0:21], [0:84], [0:4]}"),
])
def test_join_key_is_what_trace_and_text_agree_on(traced, compiled):
    assert sl.join_key(traced) == sl.join_key(compiled)
    assert sl.join_key(traced).startswith(traced.split(" = ")[0] + " = ")


# ---- the real lowering, tiny, on the CPU --------------------------------


@pytest.mark.parametrize("name,program,modules", [
    ("ppo-pong", dict(num_envs=8), ["jit_local_iteration"]),
    ("ppo-breakout", dict(num_envs=16), ["jit_local_iteration"]),
    ("ppo-pong-x4", dict(num_envs=16), ["jit_local_iteration"]),
    ("impala-pong", dict(envs_per_actor=4),
     ["jit_local_learner_step", "jit_actor_rollout"]),
])
def test_cells_programs_lower_and_feed_every_declared_metric(
    monkeypatch, name, program, modules
):
    """One event of 1 us for every instruction the lowered programs
    hold, under programs named as the trace would name them: every
    declared metric of the cell reads a number, nothing is lost."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        scope_table,
    )

    cell = tiny_cell(name, **program)
    runner = driver.load_runner(cell.family)(cell, seed=3)
    runner.setup()
    runner.close()  # the driver closes the runner before the readers run
    texts = sl._LOWER[cell.family](runner)
    monkeypatch.setitem(sl._LOWER, cell.family, lambda runner: texts)
    assert [sl._MODULE.search(t).group(1) for t in texts] == modules
    events, at = [], 0.0
    for text in texts:
        for key in scope_table(text):
            events.append((tr.Event(key, at, 1000.0), 1000.0))
            at += 1000.0
    reduced = types.SimpleNamespace(
        op_events=events, busy_s=at / 1e9, chips=1,
        modules={m + "(123)": [1.0] for m in modules},
    )
    ctx = types.SimpleNamespace(
        reduced=reduced, notes={}, runner=runner, cell=cell,
    )
    values = {
        metric: scope_time_share.read(ctx, **args)
        for metric, args in declared(name).items()
    }
    joined = ctx.notes["scope_join"]
    assert joined["why"] is None, joined
    assert joined["coverage_pct"] == pytest.approx(100.0, abs=0.5)
    assert all(v is not None and 0 < v < 100 for v in values.values()), values
    assert sum(joined["self_s_by_phases"].values()) <= at / 1e9 + 1e-12
