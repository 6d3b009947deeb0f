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


def test_the_kimi_vl_iteration_carries_its_layer_scopes(
    metadata_in_cache_key
):
    """The layers ``models/kimi_vl.py`` names — the latent-attention
    mixer, its absorbed step inside it, the leading dense layer's
    feed-forward — and the shared expert layer's four, in the compiled
    text of the tiny preset's fused iteration, nested as declared."""
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        DENSE_MLP, LM_HEAD, MLA, MLA_ABSORBED, MOE, MOE_DISPATCH,
        MOE_EXPERTS, MOE_ROUTER, MOE_SHARED,
    )

    assert {MLA, MLA_ABSORBED, DENSE_MLP} <= set(profiling.LAYER_SCOPES)
    fns = make_ppo(PPOConfig(**PRESETS["ppo-kimivl-tiny"][1]))
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    table = profiling.scope_table(
        fns.iteration.lower(state).compile().as_text()
    )
    lists = {p for p in table.values() if p}
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


def test_the_sdar_iteration_carries_its_layer_scopes(metadata_in_cache_key):
    """The layers ``models/sdar.py`` names — the grouped-query mixer, a
    block's pass over the cache and the sequence form's attention
    inside it — and the shared expert
    layer's (no shared expert here), in the compiled text of the tiny
    preset's fused iteration, nested as declared."""
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS
    from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
        GQA, GQA_BLOCK_STEP, GQA_SEQ_ATTEND, LM_HEAD, MOE, MOE_DISPATCH,
        MOE_EXPERTS, MOE_ROUTER, MOE_SHARED,
    )

    assert {GQA, GQA_BLOCK_STEP, GQA_SEQ_ATTEND} <= set(
        profiling.LAYER_SCOPES
    )
    fns = make_ppo(PPOConfig(**PRESETS["ppo-sdar-tiny"][1]))
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    table = profiling.scope_table(
        fns.iteration.lower(state).compile().as_text()
    )
    lists = {p for p in table.values() if p}
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


def _ppo_iteration(seed):
    fns = make_ppo(PPOConfig(
        num_envs=4, num_epochs=2, num_minibatches=2, seed=seed, **TINY_PONG,
    ))
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
