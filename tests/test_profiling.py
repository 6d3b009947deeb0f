"""The program's phases and spans (``utils/profiling.py``,
``TimeSplit.span``): the counter and the span are one thing, a span
lands in the profiler's trace under its log-row key, the fused programs
carry every declared phase in their compiled text, and a scope changes
nothing but metadata."""

import contextlib
import glob
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
    ImpalaConfig,
    make_impala,
)
from actor_critic_algs_on_tensorflow_tpu.algos.ppo import PPOConfig, make_ppo
from actor_critic_algs_on_tensorflow_tpu.utils import (
    compile_cache,
    metric_names,
    profiling,
)
from actor_critic_algs_on_tensorflow_tpu.utils import metrics as metrics_lib
from actor_critic_algs_on_tensorflow_tpu.utils.metrics import TimeSplit
from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
    ADVANTAGE, ENV_STEP, LOSS_GRAD, MINIBATCH_PREP, OPTIMIZER, POLICY_ACT,
    ROLLOUT, UPDATE,
)

# ---- TimeSplit.span is TimeSplit.add -----------------------------------


@pytest.mark.parametrize("view", ["window", "cumulative"])
def test_span_accumulates_exactly_as_add(monkeypatch, view):
    """Two threads, a clock that ticks one second a reading in each:
    every span measures exactly 1.0 s however the threads interleave,
    so the spanned and the added accumulator must agree to the bit."""
    ticks = threading.local()

    def clock():
        ticks.t = getattr(ticks, "t", 0.0) + 1.0
        return ticks.t

    monkeypatch.setattr(metrics_lib.time, "perf_counter", clock)
    spanned, added = TimeSplit(), TimeSplit()
    read = lambda s: getattr(s, view)()

    def work(n_stall, n_transfer):
        for name, n in (("stall_s", n_stall), ("transfer_s", n_transfer)):
            for _ in range(n):
                with spanned.span(name):
                    pass
                added.add(name, 1.0)

    def round_of(*counts):
        threads = [threading.Thread(target=work, args=c) for c in counts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()

    round_of((50, 7), (30, 11))
    first = read(spanned)
    assert first == read(added) == {
        "pipeline_stall_s": 80.0, "pipeline_transfer_s": 18.0,
    }
    round_of((5, 0), (0, 2))
    total = {"pipeline_stall_s": 85.0, "pipeline_transfer_s": 20.0}
    delta = {"pipeline_stall_s": 5.0, "pipeline_transfer_s": 2.0}
    assert read(spanned) == read(added) == (
        delta if view == "window" else total
    )


def test_span_counts_a_block_that_raises():
    split = TimeSplit(prefix=metric_names.DEVICE)
    with pytest.raises(KeyError):
        with split.span("step_s"):
            time.sleep(0.01)
            raise KeyError("x")
    assert split.cumulative()["device_step_s"] >= 0.01


# ---- one clock: spans in the profiler's trace --------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A CPU profiler session holding one span of each kind; host
    events by name, and the counters beside them."""
    out = str(tmp_path_factory.mktemp("trace"))
    split = TimeSplit(prefix=metric_names.DEVICE)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with split.span("step_s"):
            time.sleep(0.05)
        with profiling.span(profiling.SENTINEL_CHECK):
            time.sleep(0.02)
        for it in profiling.traced_steps(range(3, 5)):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    with split.span("step_s"):  # outside a session: counted, not traced
        time.sleep(0.01)
    files = glob.glob(
        os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(files[-1])
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e)
    return events, split.cumulative()


def test_counter_span_is_in_the_trace_under_its_log_key(traced):
    events, counters = traced
    (event,) = events["device_step_s"]
    in_session = counters["device_step_s"] - 0.01
    assert event.duration_ns / 1e9 == pytest.approx(in_session, rel=0.1)


@pytest.mark.parametrize("name,count,at_least_s", [
    (profiling.SENTINEL_CHECK, 1, 0.02),
    ("train", 2, 0.01),
])
def test_bare_spans_and_steps_are_in_the_trace(traced, name, count,
                                               at_least_s):
    found = traced[0][name]
    assert len(found) == count
    assert all(e.duration_ns / 1e9 >= at_least_s for e in found)
    if name == "train":
        steps = sorted(dict(e.stats)["step_num"] for e in found)
        assert steps == [3, 4]


# ---- phases in the compiled programs -----------------------------------

# What may sit inside what. A phase list is sound when each phase's
# predecessor may contain it; anything else is two siblings on one
# instruction.
MAY_CONTAIN = {
    ROLLOUT: set(), UPDATE: set(),
    POLICY_ACT: {ROLLOUT}, ENV_STEP: {ROLLOUT},
    LOSS_GRAD: {UPDATE}, OPTIMIZER: {UPDATE},
    MINIBATCH_PREP: {UPDATE, LOSS_GRAD},
    ADVANTAGE: {UPDATE, LOSS_GRAD},
}
TINY_PONG = dict(
    env="PongTPU-v0", frame_stack=4, torso="nature_cnn", rollout_length=4,
    total_env_steps=10_000, num_devices=1,
)


def _compiled_text(which):
    key = jax.random.PRNGKey(0)
    if which.startswith("ppo"):
        fns = make_ppo(PPOConfig(
            num_envs=4, num_epochs=2,
            num_minibatches=2 if which == "ppo-minibatches" else 1,
            **TINY_PONG,
        ))
        state = jax.eval_shape(fns.init, key)
        return fns.iteration.lower(state).compile().as_text()
    progs = make_impala(ImpalaConfig(
        num_actors=1, envs_per_actor=2, batch_trajectories=1, **TINY_PONG,
    ))
    rollout, env_reset = progs.make_actor_programs(0)
    state = jax.eval_shape(progs.init, key)
    env_state, obs, carry = jax.eval_shape(env_reset, key)
    actor_args = (state.params, env_state, obs, carry, key)
    if which == "impala-actor":
        return rollout.lower(*actor_args).compile().as_text()
    traj = jax.eval_shape(rollout, *actor_args)[3]
    return progs.learner_step_donated.lower(state, traj).compile().as_text()


ALL = set(profiling.PHASES)


@pytest.mark.parametrize("which,expected", [
    ("ppo-whole-batch", ALL),
    ("ppo-minibatches", ALL),
    ("impala-learner", {UPDATE, MINIBATCH_PREP, LOSS_GRAD, ADVANTAGE,
                        OPTIMIZER}),
    ("impala-actor", {ROLLOUT, POLICY_ACT, ENV_STEP}),
])
def test_compiled_program_carries_its_phases(
    metadata_in_cache_key, which, expected
):
    text = _compiled_text(which)
    table = profiling.scope_table(text)
    lists = {p for p in table.values() if p}
    assert {phase for p in lists for phase in p} == expected
    # (a reduction's sub-computation keeps only the tail of its name,
    # so a list may start anywhere; what follows must nest.)
    for phases in lists:
        for outer, inner in zip(phases, phases[1:]):
            assert outer in MAY_CONTAIN[inner], (which, phases)
    assert None not in table.values()
    # Operations traced under no phase: the iteration's key derivation
    # (scalar threefry work, named here), the guard's and the metrics'
    # reductions. Anything heavier would be a phase without its scope.
    ops = [n for n in re.findall(r'op_name="([^"]*)"', text)
           if n.startswith("jit(")]
    bare = [n for n in ops if not profiling.phases_of(n)
            and "_threefry_" not in n]
    assert len(bare) < 0.05 * len(ops), (which, len(bare), len(ops))
    assert not any("NatureCNN" in n for n in bare)


SEQUENCE_PRESETS = {
    "qwen3_next": "ppo-qwen3next-tiny",
    "kimi_vl": "ppo-kimivl-tiny",
    "sdar": "ppo-sdar-tiny",
    "granite_hybrid": "ppo-granite-tiny",
}
# The cores with no expert layer: no moe_* scope is looked for there.
DENSE_CORES = {"granite_hybrid"}
_SEQUENCE_TEXTS = {}


def _sequence_text(core):
    """The compiled text of ``core``'s tiny preset's fused iteration,
    compiled once a module (under ``metadata_in_key``: see the fixture
    of that name)."""
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    if core not in _SEQUENCE_TEXTS:
        with compile_cache.metadata_in_key():
            fns = make_ppo(PPOConfig(**PRESETS[SEQUENCE_PRESETS[core]][1]))
            state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
            _SEQUENCE_TEXTS[core] = (
                fns.iteration.lower(state).compile().as_text()
            )
    return _SEQUENCE_TEXTS[core]


def _phase_lists(core):
    table = profiling.scope_table(_sequence_text(core))
    return {p for p in table.values() if p}


def test_the_qwen3_next_iteration_carries_its_layer_scopes():
    """The layers ``models/qwen3_next.py`` names — the Gated DeltaNet
    mixer with its step form's state update, the gated-attention mixer
    — and the shared expert layer's four, in the compiled text of the
    tiny preset's fused iteration, nested as declared."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        GATED_ATTN, GDN, GDN_STATE, LM_HEAD, MOE, MOE_DISPATCH,
        MOE_EXPERTS, MOE_ROUTER, MOE_SHARED,
    )

    lists = _phase_lists("qwen3_next")
    found = {phase for p in lists for phase in p}
    assert {GDN, GDN_STATE, GATED_ATTN, MOE, MOE_ROUTER, MOE_DISPATCH,
            MOE_EXPERTS, MOE_SHARED, LM_HEAD} <= found
    for phases in lists:
        if GDN_STATE in phases:
            assert GDN in phases[:phases.index(GDN_STATE)], phases
            assert LOSS_GRAD not in phases, phases
        if GDN in phases:
            assert GATED_ATTN not in phases and MOE not in phases, phases
    for mixer in (GDN, GATED_ATTN):
        assert any(mixer in p and LOSS_GRAD in p for p in lists)
        assert any(mixer in p and POLICY_ACT in p for p in lists)


def test_the_kimi_vl_iteration_carries_its_layer_scopes():
    """The layers ``models/kimi_vl.py`` names — the latent-attention
    mixer, its absorbed step inside it, the leading dense layer's
    feed-forward — and the shared expert layer's four, in the compiled
    text of the tiny preset's fused iteration, nested as declared."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        DENSE_MLP, LM_HEAD, MLA, MLA_ABSORBED, MOE, MOE_DISPATCH,
        MOE_EXPERTS, MOE_ROUTER, MOE_SHARED,
    )

    assert {MLA, MLA_ABSORBED, DENSE_MLP} <= set(profiling.LAYER_SCOPES)
    lists = _phase_lists("kimi_vl")
    found = {phase for p in lists for phase in p}
    assert {MLA, MLA_ABSORBED, DENSE_MLP, MOE, MOE_ROUTER, MOE_DISPATCH,
            MOE_EXPERTS, MOE_SHARED, LM_HEAD} <= found
    for phases in lists:
        if MLA_ABSORBED in phases:
            # the step form: in the mixer, in an acting pass, never in
            # the update's differentiated pass
            assert MLA in phases[:phases.index(MLA_ABSORBED)], phases
            assert LOSS_GRAD not in phases, phases
        if DENSE_MLP in phases:
            assert MLA not in phases and MOE not in phases, phases
    assert any(MLA in p and LOSS_GRAD in p for p in lists)
    assert any(MLA_ABSORBED in p and POLICY_ACT in p for p in lists)


def test_the_sdar_iteration_carries_its_layer_scopes():
    """The layers ``models/sdar.py`` names — the grouped-query mixer, a
    block's pass over the cache and the sequence form's attention
    inside it — and the shared expert
    layer's (no shared expert here), in the compiled text of the tiny
    preset's fused iteration, nested as declared."""
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        GQA, GQA_BLOCK_STEP, GQA_SEQ_ATTEND, LM_HEAD, MOE, MOE_DISPATCH,
        MOE_EXPERTS, MOE_ROUTER, MOE_SHARED,
    )

    assert {GQA, GQA_BLOCK_STEP, GQA_SEQ_ATTEND} <= set(
        profiling.LAYER_SCOPES
    )
    lists = _phase_lists("sdar")
    found = {phase for p in lists for phase in p}
    assert {GQA, GQA_BLOCK_STEP, MOE, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS,
            LM_HEAD} <= found
    assert MOE_SHARED not in found
    for phases in lists:
        if GQA_BLOCK_STEP in phases:
            # the step form: in the mixer, in an acting pass, never in
            # the update's differentiated pass
            assert GQA in phases[:phases.index(GQA_BLOCK_STEP)], phases
            assert LOSS_GRAD not in phases, phases
        if GQA_SEQ_ATTEND in phases:
            # the sequence form less its projections: in the mixer, in
            # the update's differentiated pass (the bootstrap value's
            # pass is one env step), never in an acting pass
            assert GQA in phases[:phases.index(GQA_SEQ_ATTEND)], phases
            assert POLICY_ACT not in phases, phases
        if GQA in phases:
            assert MOE not in phases, phases
    assert any(GQA in p and LOSS_GRAD in p for p in lists)
    assert any(GQA_SEQ_ATTEND in p and LOSS_GRAD in p for p in lists)
    assert any(GQA_BLOCK_STEP in p and POLICY_ACT in p for p in lists)
    # BlockReveal's log-prob and entropy in the update are the head's
    assert any(LM_HEAD in p and LOSS_GRAD in p for p in lists)


# One level down (PR 35): where each sub-scope must be found in a
# core's compiled iteration. (core, scope) -> (the scopes it is nested
# in, a phase it must be found under, the phases it must never be
# under).
_P = profiling
SUB_SCOPES = {
    ("qwen3_next", _P.MIXER_PROJ): ((), LOSS_GRAD, ()),
    ("qwen3_next", _P.MIXER_POINTWISE): ((), LOSS_GRAD, ()),
    ("qwen3_next", _P.MIXER_CORE): ((), POLICY_ACT, ()),
    ("qwen3_next", _P.GDN_CHUNK_SOLVE):
        ((_P.GDN, _P.MIXER_CORE), LOSS_GRAD, (POLICY_ACT, ADVANTAGE)),
    ("qwen3_next", _P.GDN_CHUNK_PRODUCTS):
        ((_P.GDN, _P.MIXER_CORE), LOSS_GRAD, (POLICY_ACT, ADVANTAGE)),
    ("qwen3_next", _P.GDN_STATE):
        ((_P.GDN, _P.MIXER_CORE), POLICY_ACT, (LOSS_GRAD,)),
    ("qwen3_next", _P.MOE_COMBINE):
        ((_P.MOE, _P.MOE_DISPATCH), LOSS_GRAD, ()),
    ("qwen3_next", _P.SAMPLE): ((ROLLOUT, POLICY_ACT), ROLLOUT, (UPDATE,)),
    ("kimi_vl", _P.MIXER_PROJ): ((_P.MLA,), LOSS_GRAD, ()),
    ("kimi_vl", _P.MIXER_POINTWISE): ((_P.MLA,), LOSS_GRAD, ()),
    ("kimi_vl", _P.MIXER_CORE): ((_P.MLA,), POLICY_ACT, ()),
    ("kimi_vl", _P.MLA_SEQ_ATTEND):
        ((_P.MLA, _P.MIXER_CORE), LOSS_GRAD, (POLICY_ACT, ADVANTAGE)),
    ("kimi_vl", _P.MOE_COMBINE): ((_P.MOE, _P.MOE_DISPATCH), LOSS_GRAD, ()),
    ("kimi_vl", _P.SAMPLE): ((ROLLOUT, POLICY_ACT), ROLLOUT, (UPDATE,)),
    ("sdar", _P.MIXER_PROJ): ((_P.GQA,), LOSS_GRAD, ()),
    ("sdar", _P.MIXER_POINTWISE): ((_P.GQA,), LOSS_GRAD, ()),
    ("sdar", _P.MIXER_CORE): ((_P.GQA,), POLICY_ACT, ()),
    ("sdar", _P.GQA_SEQ_ATTEND):
        ((_P.GQA, _P.MIXER_CORE), LOSS_GRAD, (POLICY_ACT, ADVANTAGE)),
    ("sdar", _P.GQA_BLOCK_STEP):
        ((_P.GQA, _P.MIXER_CORE), POLICY_ACT, (LOSS_GRAD,)),
    ("sdar", _P.MOE_COMBINE): ((_P.MOE, _P.MOE_DISPATCH), LOSS_GRAD, ()),
    ("sdar", _P.SAMPLE): ((ROLLOUT, POLICY_ACT), ROLLOUT, (UPDATE,)),
    ("granite_hybrid", _P.MIXER_PROJ): ((_P.MAMBA,), LOSS_GRAD, ()),
    ("granite_hybrid", _P.MIXER_POINTWISE): ((_P.MAMBA,), LOSS_GRAD, ()),
    ("granite_hybrid", _P.MIXER_CORE): ((_P.GQA,), POLICY_ACT, ()),
    ("granite_hybrid", _P.MAMBA_CHUNK_SCAN):
        ((_P.MAMBA, _P.MIXER_CORE), LOSS_GRAD, (POLICY_ACT, ADVANTAGE)),
    ("granite_hybrid", _P.MAMBA_STATE):
        ((_P.MAMBA, _P.MIXER_CORE), POLICY_ACT, (LOSS_GRAD,)),
    ("granite_hybrid", _P.DENSE_MLP): ((), LOSS_GRAD, ()),
    ("granite_hybrid", _P.LM_HEAD): ((), LOSS_GRAD, ()),
    ("granite_hybrid", _P.SAMPLE):
        ((ROLLOUT, POLICY_ACT), ROLLOUT, (UPDATE,)),
}


@pytest.mark.parametrize(
    "core,scope", sorted(SUB_SCOPES), ids=lambda x: str(x)
)
def test_a_sequence_core_iteration_carries_its_sub_scopes(core, scope):
    """Every sub-scope of ``utils/profiling.py`` is in the core's
    compiled tiny iteration, inside the scopes it is declared in (in
    that order), under the phase its work runs in and under no phase
    it must not run in."""
    outer, under, never = SUB_SCOPES[core, scope]
    assert scope in profiling.LAYER_SCOPES
    holding = [p for p in _phase_lists(core) if scope in p]
    assert holding, (core, scope)
    assert any(under in p for p in holding), (under, holding)
    assert any(set(outer) <= set(p[:p.index(scope)]) for p in holding)
    for phases in holding:
        assert not set(never) & set(phases), phases
        # (a reduction's sub-computation keeps only the tail of its
        # name: what is there of the declared nesting is in order)
        before = phases[:phases.index(scope)]
        kept = [s for s in outer if s in before]
        assert kept == [s for s in before if s in outer], phases
        if scope in profiling.MIXER_PARTS and outer:
            assert set(before) & set(profiling.MIXER_SCOPES), phases
    if scope == _P.MLA_SEQ_ATTEND:
        # the absorbed form's two products with kv_b_proj's halves are
        # projections, the rest of mla_absorbed is the core
        lists = _phase_lists(core)
        assert any(_P.MLA_ABSORBED in p and _P.MIXER_PROJ in p for p in lists)
        assert any(_P.MLA_ABSORBED in p and _P.MIXER_CORE in p for p in lists)


@pytest.mark.parametrize("core", sorted(SEQUENCE_PRESETS))
def test_a_mixer_is_partitioned_into_its_three_parts(core):
    """Every instruction traced under a mixer's scope (gdn, gated_attn,
    mla, gqa, mamba) is under exactly one of mixer_proj, mixer_pointwise and
    mixer_core, in the forward pass, the recomputed one and the
    backward pass: the three time shares add up to the mixers'."""
    names = [n for n in re.findall(r'op_name="([^"]*)"',
                                   _sequence_text(core))
             if n.startswith("jit(")]
    under = 0
    for name in names:
        # every name a merged instruction carries, not only the first
        for one in name.split(";"):
            phases = profiling.phases_of(one)
            if set(phases) & set(profiling.MIXER_SCOPES):
                under += 1
                parts = [p for p in phases if p in profiling.MIXER_PARTS]
                assert len(parts) == 1, one
    assert under > 100, (core, under)
    # and no part's name is found outside a mixer, but for the input
    # norm that Qwen3-Next traces before its mixer's scope opens
    loose = {
        one for name in names for one in name.split(";")
        if set(profiling.phases_of(one)) & set(profiling.MIXER_PARTS)
        and not set(profiling.phases_of(one)) & set(profiling.MIXER_SCOPES)
    }
    if core == "qwen3_next":
        assert loose and all(_P.MIXER_POINTWISE in n for n in loose), loose
    else:
        assert not loose, loose


@pytest.mark.parametrize("op_name,phases", [
    ("jit(local_iteration)/rollout/while/body/closed_call/env_step/add",
     (ROLLOUT, ENV_STEP)),
    ("jit(f)/update/while/body/loss_grad/transpose(jvp(Model))/Conv_0/dot",
     (UPDATE, LOSS_GRAD)),
    ("jit(f)/update/loss_grad/jvp(minibatch_prep)/div",
     (UPDATE, LOSS_GRAD, MINIBATCH_PREP)),
    ("jit(f)/update/loss_grad/transpose(jvp(advantage))/mul",
     (UPDATE, LOSS_GRAD, ADVANTAGE)),
    # a jitted function that happens to be called like a phase is none
    ("jit(update)/jit(rollout)/add", ()),
    # merged by the compiler: the first name speaks
    ("jit(f)/update/loss_grad/jvp()/broadcast_in_dim;jit(f)/update/"
     "minibatch_prep/reshape", (UPDATE, LOSS_GRAD)),
    ("state.params['params']['Dense_0']['bias']", ()),
    # the sequence cores' layers one level down, as the compiled text
    # of the tiny presets names them: forward, ...
    ("jit(local_iteration_recurrent)/rollout/while/body/closed_call/"
     "policy_act/SDARActorCritic/gqa/mixer_core/gqa_block_step/bms,bsd->bmd/"
     "dot_general",
     (ROLLOUT, POLICY_ACT, _P.GQA, _P.MIXER_CORE, _P.GQA_BLOCK_STEP)),
    ("jit(local_iteration_recurrent)/rollout/while/body/closed_call/"
     "policy_act/sample/argmax", (ROLLOUT, POLICY_ACT, _P.SAMPLE)),
    ("jit(local_iteration_recurrent)/advantage/KimiVLActorCritic/mla/"
     "mla_absorbed/mixer_proj/bhn,chn->bhc/dot_general",
     (ADVANTAGE, _P.MLA, _P.MLA_ABSORBED, _P.MIXER_PROJ)),
    # ... differentiated, ...
    ("jit(f)/update/while/body/closed_call/loss_grad/"
     "jvp(Qwen3NextActorCritic)/gdn/mixer_core/gdn_chunk_solve/"
     "triangular_solve",
     (UPDATE, LOSS_GRAD, _P.GDN, _P.MIXER_CORE, _P.GDN_CHUNK_SOLVE)),
    # ... and recomputed and transposed: the outer transform's own
    # copy of loss_grad is the same phase, named once
    ("jit(f)/update/while/body/closed_call/loss_grad/"
     "transpose(jvp(SDARActorCritic))/loss_grad/jvp(SDARActorCritic)/"
     "checkpoint/gqa/mixer_proj/dot_general",
     (UPDATE, LOSS_GRAD, _P.GQA, _P.MIXER_PROJ)),
    ("jit(f)/update/loss_grad/transpose(jvp(KimiVLActorCritic))/mla/"
     "transpose(jvp(mixer_core))/transpose(jvp(mla_seq_attend))/mul",
     (UPDATE, LOSS_GRAD, _P.MLA, _P.MIXER_CORE, _P.MLA_SEQ_ATTEND)),
    ("jit(f)/update/loss_grad/transpose(jvp(M))/moe/moe_dispatch/"
     "transpose(jvp(moe_combine))/gather",
     (UPDATE, LOSS_GRAD, _P.MOE, _P.MOE_DISPATCH, _P.MOE_COMBINE)),
    ("jit(f)/update/loss_grad/jvp(M)/gdn/mixer_core/gdn_chunk_products/"
     "while/body/closed_call/bhck,bhkv->bhcv/dot_general",
     (UPDATE, LOSS_GRAD, _P.GDN, _P.MIXER_CORE, _P.GDN_CHUNK_PRODUCTS)),
])
def test_phases_of_an_op_name(op_name, phases):
    assert profiling.phases_of(op_name) == phases


def test_scope_table_keys_and_conflicts():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%body (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  ROOT %add.1 = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p), '
        'metadata={op_name="jit(f)/rollout/while/body/env_step/add" '
        'stack_frame_id=3}, backend_config={"flag_configs":[]}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %copy-start.1 = (f32[4]{0}, f32[4]{0}) copy-start(f32[4]{0} %a)",
        '  %dup = f32[4]{0} copy(f32[4]{0} %a), '
        'metadata={op_name="jit(f)/update/optimizer/copy"}',
        "}",
        # a second module's text after the first: the same instruction
        "ENTRY %main.2 (a: f32[4]) -> f32[4] {",
        '  %dup = f32[4]{0} copy(f32[4]{0} %a), '
        'metadata={op_name="jit(f)/rollout/copy"}',
        "}",
    ])
    assert profiling.scope_table(text) == {
        "%p = f32[4]{0} parameter(0)": (),
        "%add.1 = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p)":
            (ROLLOUT, ENV_STEP),
        "%copy-start.1 = (f32[4]{0}, f32[4]{0}) copy-start(f32[4]{0} %a)":
            (),
        "%dup = f32[4]{0} copy(f32[4]{0} %a)": None,
    }


def test_scope_table_names_what_the_compiler_made():
    """No ``op_name``: a fusion takes the deepest phases it fused, a
    copy those of the instruction it feeds (through a start/done pair
    too), failing that of the one that feeds it; the rest has none."""
    md = lambda name: ', metadata={op_name="jit(f)/%s"}' % name
    text = "\n".join([
        "HloModule jit_f",
        "%fused (p: u8[4]) -> bf16[4] {",
        "  %p = u8[4]{0} parameter(0)",
        "  %c = bf16[4]{0} convert(%p)" + md("update/loss_grad/jvp(minibatch_prep)/convert"),
        "  ROOT %r = bf16[2,2]{1,0} bitcast(%c)" + md("update/loss_grad/jvp(M)/reshape"),
        "}",
        "ENTRY %main (a: u8[4]) -> f32[] {",
        "  %a = u8[4]{0} parameter(0)",
        "  %made = bf16[2,2]{1,0} fusion(%a), kind=kLoop, calls=%fused",
        "  %copy.1 = bf16[2,2]{0,1} copy(%made)",
        "  %conv = f32[2,2]{1,0} convolution(%copy.1, %made)" + md("update/loss_grad/jvp(M)/conv"),
        "  %copy-start.2 = (f32[2,2]{1,0}, u32[]) copy-start(%conv)",
        "  %copy-done.2 = f32[2,2]{1,0} copy-done(%copy-start.2)",
        "  %adam = f32[2,2]{1,0} add(%copy-done.2, %copy-done.2)" + md("update/optimizer/add"),
        "  %tail = f32[2,2]{0,1} copy(%adam)",
        "  %lone = f32[]{} constant(0)",
        "}",
    ])
    table = profiling.scope_table(text)
    short = {k.split(" = ")[0]: v for k, v in table.items()}
    assert short["%made"] == (UPDATE, LOSS_GRAD, MINIBATCH_PREP)
    assert short["%copy.1"] == (UPDATE, LOSS_GRAD)
    assert short["%copy-start.2"] == short["%copy-done.2"] == (
        UPDATE, OPTIMIZER,
    )
    assert short["%tail"] == (UPDATE, OPTIMIZER)   # nothing to feed
    assert short["%lone"] == ()


# ---- a scope is metadata only -------------------------------------------


def _ppo_iteration(seed, preset=None):
    """The metrics of one iteration and its compiled text: the tiny
    feed-forward PPO, or a preset of ``cli/train.py``."""
    if preset is None:
        cfg = PPOConfig(
            num_envs=4, num_epochs=2, num_minibatches=2, seed=seed,
            **TINY_PONG,
        )
    else:
        from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

        cfg = PPOConfig(**dict(PRESETS[preset][1], seed=seed))
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(seed))
    text = fns.iteration.lower(state).compile().as_text()
    _, metrics = fns.iteration(state)
    return jax.device_get(metrics), text


def _instructions(text):
    """The compiled instructions without their metadata and without
    their numbering (which follows the order of tracing), sorted."""
    lines = [
        re.sub(r"%[\w.\-]+", "%_", re.sub(r", metadata=\{[^}]*\}", "", l))
        for l in text.splitlines() if re.match(r"\s+(ROOT )?%", l)
    ]
    return sorted(lines)


@pytest.fixture
def metadata_in_cache_key():
    """Or the unscoped build would be handed the scoped build's
    executable by the persistent cache, names and all."""
    with compile_cache.metadata_in_key():
        yield


def test_scoped_and_unscoped_builds_are_one_program(
    monkeypatch, metadata_in_cache_key
):
    scoped_metrics, scoped_text = _ppo_iteration(seed=7)
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    plain_metrics, plain_text = _ppo_iteration(seed=7)
    assert f"/{UPDATE}/" in scoped_text and f"/{UPDATE}/" not in plain_text
    assert scoped_metrics.keys() == plain_metrics.keys()
    for k in scoped_metrics:
        np.testing.assert_array_equal(scoped_metrics[k], plain_metrics[k])
    assert _instructions(scoped_text) == _instructions(plain_text)


@pytest.mark.parametrize("core", sorted(SEQUENCE_PRESETS))
def test_scoped_and_unscoped_sequence_core_builds_are_one_program(
    monkeypatch, metadata_in_cache_key, core
):
    """The same for a sequence core's fused iteration, whose mixers and
    expert block open a scope around every few lines: the layers'
    names, and no instruction, counter or number, are what a scope
    adds."""
    preset = SEQUENCE_PRESETS[core]
    # the expert layer's two switches are traced once a process and
    # inlined where called (PR 36): without this a build would be
    # handed the trace of the one before it, its names and all
    jax.clear_caches()
    scoped_metrics, scoped_text = _ppo_iteration(seed=7, preset=preset)
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    jax.clear_caches()
    plain_metrics, plain_text = _ppo_iteration(seed=7, preset=preset)
    scopes = profiling.MIXER_PARTS + (profiling.SAMPLE,) + (
        () if core in DENSE_CORES else (profiling.MOE_COMBINE,)
    )
    for scope in scopes:
        assert f"/{scope}/" in scoped_text, scope
        assert f"/{scope}/" not in plain_text, scope
    assert scoped_metrics.keys() == plain_metrics.keys()
    for k in scoped_metrics:
        np.testing.assert_array_equal(scoped_metrics[k], plain_metrics[k])
    assert _instructions(scoped_text) == _instructions(plain_text)


def test_a_kernel_under_the_compilers_own_name_inherits_its_consumers_phases():
    """The TPU's grouped-product kernels reach the compiled text as
    ``op_name="ragged-dot-none"``: no traced function, so no scope of
    the program's. They take the phases of what they feed."""
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "ENTRY %main (a: f32[8,4], w: f32[2,4,4]) -> f32[8,4] {",
        '  %ragged-dot-none.1 = f32[8,4]{1,0} custom-call(f32[8,4]{1,0} %a, '
        'f32[2,4,4]{2,1,0} %w), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  ROOT %mul.1 = f32[8,4]{1,0} multiply(f32[8,4]{1,0} '
        '%ragged-dot-none.1, f32[8,4]{1,0} %a), '
        'metadata={op_name="jit(f)/update/loss_grad/moe/moe_experts/mul"}',
        "}",
    ])
    table = profiling.scope_table(text)
    kernel = next(k for k in table if k.startswith("%ragged-dot-none.1"))
    assert table[kernel] == (UPDATE, LOSS_GRAD, profiling.MOE,
                             profiling.MOE_EXPERTS)


def test_a_kernel_that_feeds_another_phase_stays_with_its_operands():
    """A weight gradient's grouped product feeds Adam (or the norm that
    clips it), and nothing that feeds it is in ``optimizer``: it is
    work of the differentiated function, and takes its operands'
    phases, the deepest, the later operand on a tie. The kernel that
    lays out its groups, the copy the compiler put before it and the
    handle on its result follow it. (On the chip the three
    language-model cells read 18, 15 and 12 such products an update
    block as ``optimizer`` until PR 35.)"""
    md = lambda name: ', metadata={op_name="%s"}' % name
    grad = "jit(f)/update/loss_grad/transpose(jvp(M))/moe/%s"
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "ENTRY %main (x: f32[8,4], g: f32[8,4], n: s32[2]) -> f32[2,4,4] {",
        "  %x = f32[8,4]{1,0} parameter(0)",
        "  %g = f32[8,4]{1,0} parameter(1)",
        "  %n = s32[2]{0} parameter(2)",
        "  %xs = bf16[8,4]{1,0} convert(%x)"
        + md(grad % "moe_dispatch/convert"),
        "  %pair = (f32[8,4]{1,0}, f32[8,4]{1,0}) fusion(%g), kind=kLoop, "
        "calls=%none" + md(grad % "moe_experts/mul"),
        "  %cotangent = f32[8,4]{1,0} get-tuple-element(%pair), index=0",
        "  %copy.1 = bf16[8,4]{0,1} copy(%xs)",
        "  %ragged-dot-metadata.1 = (s32[3]{0}, s32[1]{0}) custom-call(%n), "
        'custom_call_target="tpu_custom_call"' + md("ragged-dot-metadata"),
        "  %groups = s32[3]{0} get-tuple-element(%ragged-dot-metadata.1), "
        "index=0",
        "  %ragged-dot-none.1 = f32[2,4,4]{2,1,0} custom-call(%groups, "
        '%copy.1, %cotangent), custom_call_target="tpu_custom_call"'
        + md("ragged-dot-none"),
        "  %handle = f32[2,4,4]{2,1,0} bitcast(%ragged-dot-none.1)",
        "  ROOT %adam = f32[2,4,4]{2,1,0} multiply(%handle, %handle)"
        + md("jit(f)/update/optimizer/mul"),
        "}",
    ])
    short = {k.split(" = ")[0]: v
             for k, v in profiling.scope_table(text).items()}
    experts = (UPDATE, LOSS_GRAD, profiling.MOE, profiling.MOE_EXPERTS)
    assert short["%ragged-dot-none.1"] == experts
    assert short["%ragged-dot-metadata.1"] == short["%groups"] == experts
    assert short["%copy.1"] == experts
    # what it feeds keeps its own phases; a handle on the result is
    # for its consumer, as any copy
    assert short["%adam"] == short["%handle"] == (UPDATE, OPTIMIZER)
    assert short["%xs"] == (UPDATE, LOSS_GRAD, profiling.MOE,
                            profiling.MOE_DISPATCH)
    # a consumer in the operands' phase speaks, as before ...
    same = text.replace(
        "jit(f)/update/optimizer/mul", grad % "moe_combine/add"
    )
    short = {k.split(" = ")[0]: v
             for k, v in profiling.scope_table(same).items()}
    assert short["%ragged-dot-none.1"] == (
        UPDATE, LOSS_GRAD, profiling.MOE, profiling.MOE_COMBINE
    )


def test_a_kernel_that_feeds_a_fusion_takes_the_phases_of_what_reads_it():
    """The expert layer's last grouped product is read by a mask traced
    under ``moe_experts`` that the compiler fuses into the scatter-add
    of ``moe_combine``; the fusion carries its root's name. The fused
    instruction that reads the kernel speaks for it, not the root. (On
    the chip 0.69 ms of every 3.8 ms decode step of
    ``ppo-kimivl-recall`` read as ``moe_dispatch`` until PR 35: the
    product, not the dispatch.)"""
    md = lambda name: (
        ', metadata={op_name="jit(f)/rollout/policy_act/%s"}' % name
    )
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_scatter (p0: f32[4,4], p1: f32[8,4], p2: f32[8]) -> "
        "f32[4,4] {",
        "  %p0 = f32[4,4]{1,0} parameter(0)",
        "  %p1 = f32[8,4]{1,0} parameter(1)",
        "  %p2 = f32[8]{0} parameter(2)",
        "  %w = f32[8,4]{1,0} broadcast(%p2), dimensions={0}"
        + md("moe/moe_experts/broadcast_in_dim"),
        "  %masked = f32[8,4]{1,0} multiply(%p1, %w)"
        + md("moe/moe_experts/mul"),
        "  ROOT %out = f32[4,4]{1,0} scatter(%p0, %masked)"
        + md("moe/moe_dispatch/moe_combine/scatter-add"),
        "}",
        "ENTRY %main (h: f32[8,4], w: f32[2,4,4], r: f32[8]) -> f32[4,4] {",
        "  %h = f32[8,4]{1,0} parameter(0)",
        "  %w = f32[2,4,4]{2,1,0} parameter(1)",
        "  %r = f32[8]{0} parameter(2)",
        "  %zeros = f32[4,4]{1,0} broadcast(%r)"
        + md("moe/moe_dispatch/moe_combine/broadcast_in_dim"),
        "  %ragged-dot-none.7 = f32[8,4]{1,0} custom-call(%h, %w), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        "  ROOT %scatter_fusion = f32[4,4]{1,0} fusion(%zeros, "
        "%ragged-dot-none.7, %r), kind=kInput, calls=%fused_scatter"
        + md("moe/moe_dispatch/moe_combine/scatter-add"),
        "}",
    ])
    short = {k.split(" = ")[0]: v
             for k, v in profiling.scope_table(text).items()}
    assert short["%scatter_fusion"] == (
        ROLLOUT, POLICY_ACT, profiling.MOE, profiling.MOE_DISPATCH,
        profiling.MOE_COMBINE,
    )
    assert short["%ragged-dot-none.7"] == (
        ROLLOUT, POLICY_ACT, profiling.MOE, profiling.MOE_EXPERTS,
    )


def test_a_kernel_in_a_branch_computation_follows_what_it_feeds():
    """The expert layer picks its buffer's rows under ``lax.switch``
    (PR 36): the grouped products sit in the ``conditional``'s branch
    computations, fed by handles on the branch's parameter, which
    carry no scope of the program's. A kernel there takes the phases of
    what it feeds, branch by branch, as in any computation, and the
    branch's scatter-add keeps ``moe_combine``
    (``tests/test_tpu_hlo.py`` holds the same on the compiled SDAR
    step)."""
    md = lambda name: (
        ', metadata={op_name="jit(f)/rollout/policy_act/moe/%s"}' % name
    )
    in_branch = "cond/branch_%d_fun/%s"
    branch = lambda i, rows: [
        "%%region_%d (arg: (bf16[%d,4], bf16[2,4,4], s32[%d])) -> "
        "f32[4,4] {" % (i, rows, rows),
        "  %%arg.%d = (bf16[%d,4]{1,0}, bf16[2,4,4]{2,1,0}, s32[%d]{0}) "
        "parameter(0)" % (i, rows, rows),
        "  %%xs.%d = bf16[%d,4]{1,0} get-tuple-element(%%arg.%d), index=0"
        % (i, rows, i),
        "  %%w.%d = bf16[2,4,4]{2,1,0} get-tuple-element(%%arg.%d), index=1"
        % (i, i),
        "  %%token.%d = s32[%d]{0} get-tuple-element(%%arg.%d), index=2"
        % (i, rows, i),
        "  %%ragged-dot-none.%d = f32[%d,4]{1,0} custom-call(%%xs.%d, "
        '%%w.%d), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}' % (i, rows, i, i),
        "  %%masked.%d = f32[%d,4]{1,0} multiply(%%ragged-dot-none.%d, "
        "%%ragged-dot-none.%d)" % (i, rows, i, i)
        + md(in_branch % (i, "moe_experts/mul")),
        "  ROOT %%add.%d = f32[4,4]{1,0} scatter(%%masked.%d, %%token.%d)"
        % (i, i, i)
        + md(in_branch % (i, "moe_dispatch/moe_combine/scatter-add")),
        "}",
    ]
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        *branch(0, 8), *branch(1, 16),
        "ENTRY %main (r: s32[], a: (bf16[8,4], bf16[2,4,4], s32[8]), "
        "b: (bf16[16,4], bf16[2,4,4], s32[16])) -> f32[4,4] {",
        "  %r = s32[] parameter(0)",
        "  %a = (bf16[8,4]{1,0}, bf16[2,4,4]{2,1,0}, s32[8]{0}) "
        "parameter(1)",
        "  %b = (bf16[16,4]{1,0}, bf16[2,4,4]{2,1,0}, s32[16]{0}) "
        "parameter(2)",
        "  ROOT %cond.1 = f32[4,4]{1,0} conditional(%r, %a, %b), "
        "branch_computations={%region_0, %region_1}" + md("cond"),
        "}",
    ])
    short = {k.split(" = ")[0]: v
             for k, v in profiling.scope_table(text).items()}
    step = (ROLLOUT, POLICY_ACT, profiling.MOE)
    for i in (0, 1):
        assert short[f"%ragged-dot-none.{i}"] == step + (
            profiling.MOE_EXPERTS,
        )
        assert short[f"%add.{i}"] == step + (
            profiling.MOE_DISPATCH, profiling.MOE_COMBINE,
        )
    assert short["%cond.1"] == step
