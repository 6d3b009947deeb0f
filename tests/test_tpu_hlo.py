"""What the TPU's compiler makes of the main path, read off the chip:
the ``ppo-breakout`` iteration and the ``impala-pong`` learner step
compiled for a described v5e at their real sizes (nothing runs, ~13 s
and ~10 s), and their instruction text.

Keep every test that describes a topology in THIS file: the worker that
describes one holds the TPU library until it exits, and a second file
could land on another worker (``/opt/skills/guides/on-chip-measurement``
section 2; ``perfbench/tests/test_families_tpu_hlo.py`` is the
benchmark's own such file).
"""

import math
import os
import re

import jax
import pytest

from actor_critic_algs_on_tensorflow_tpu.algos import common
from actor_critic_algs_on_tensorflow_tpu.algos.ppo import PPOConfig, make_ppo
from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def on_described_chip(monkeypatch, topo):
    """A program builds its mesh from ``jax.devices()``: hand it the
    described chip while it does (until ``monkeypatch.undo()``)."""
    real = jax.devices
    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **k: [topo.devices[0]] if not a else real(*a, **k),
    )


def iteration_text(monkeypatch, topo, preset):
    """The preset's fused iteration on one described chip, as compiled
    text."""
    from jax.sharding import NamedSharding, PartitionSpec

    on_described_chip(monkeypatch, topo)
    _, base = PRESETS[preset]
    fns = make_ppo(PPOConfig(**base, num_devices=1))
    monkeypatch.undo()
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    args = jax.tree_util.tree_map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(fns.mesh, spec)
        ),
        state, common.state_specs(state),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    return fns.iteration.lower(args).compile().as_text()


INSTRUCTION = re.compile(r"%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")
# A view or a handle, no bytes moved.
FREE = {"bitcast", "get-tuple-element", "tuple", "parameter"}
# Under the update, in the epoch loop's body: the minibatch loop itself,
# and what was traced inside its body.
MINIBATCH_LOOP = re.compile(r"/update/while/body/.*while$")
IN_MINIBATCH_LOOP = re.compile(r"/update/while/body/.*while/body")


def unfused(text):
    """``{computation: [(name, result, opcode, op_name, line)]}`` over
    the instructions a trace can show (outside fused computations)."""
    comps, current = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = comps.setdefault(head.group(1), [])
        elif current is not None and line.startswith("  "):
            m = INSTRUCTION.match(line.strip().removeprefix("ROOT "))
            if m:
                op_name = re.search(r'op_name="([^"]*)"', line)
                current.append(
                    m.groups() + (op_name.group(1) if op_name else "", line)
                )
    fused = set(re.findall(r"kind=\w+, calls=%?([\w.\-]+)", text))
    return {k: v for k, v in comps.items() if k not in fused}


def conv0_frame_inputs(rows):
    """Results of the frame operands (``84,84``) of Conv_0's ``kOutput``
    fusions (forward passes and weight gradient) among ``rows``, one
    computation's instructions."""
    results = {name: result for name, result, *_ in rows}
    return [
        results[operand]
        for _, _, opcode, op_name, line in rows
        if opcode == "fusion" and "kind=kOutput" in line
        and "Conv_0/conv_general_dilated" in op_name
        for operand in re.findall(
            r"%([\w.\-]+)", line[line.index("fusion("):line.index("kind=")]
        )
        if "84,84" in results.get(operand, "")
    ]


def test_ppo_breakout_minibatch_loop_moves_no_observation(monkeypatch, topo):
    # PR 26: the env-sliced update reads a block-major arrangement made
    # once an iteration (data.rollout.env_blocks). Before it, each of
    # the 64 minibatches wrote its observations out as bf16 and re-laid
    # them for Conv_0 (convert_multiply_fusion.8 and copy.47, 28 % of
    # the iteration on the chip: PERF.md section 6).
    comps = unfused(iteration_text(monkeypatch, topo, "ppo-breakout"))
    bodies = {
        re.search(r"body=%?([\w.\-]+)", line).group(1)
        for rows in comps.values()
        for _, _, opcode, op_name, line in rows
        if opcode == "while" and MINIBATCH_LOOP.search(op_name)
    }
    assert len(bodies) == 1, bodies
    movers = [
        (name, result)
        for comp, rows in comps.items()
        for name, result, opcode, op_name, _ in rows
        if "84,84" in result and opcode not in FREE
        and (comp in bodies or IN_MINIBATCH_LOOP.search(op_name))
    ]
    assert not movers, movers
    # ... because Conv_0 converts for itself: its forward pass and its
    # weight gradient read the uint8 arrangement in place.
    (body,) = bodies
    conv0_inputs = conv0_frame_inputs(comps[body])
    assert len(conv0_inputs) >= 2, conv0_inputs
    assert all(r.startswith("u8[") for r in conv0_inputs), conv0_inputs


def learner_step_text(monkeypatch, topo, preset, **overrides):
    """The preset's donated IMPALA ``learner_step`` on one described
    chip, as compiled text, fed the trajectory batch its own actor
    program produces (shapes only)."""
    from jax.sharding import SingleDeviceSharding

    from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
        ActorTrajectory,
        ImpalaConfig,
        make_impala,
    )

    on_described_chip(monkeypatch, topo)
    _, base = PRESETS[preset]
    progs = make_impala(ImpalaConfig(**{**base, **overrides}, num_devices=1))
    rollout, env_reset = progs.make_actor_programs(0)
    monkeypatch.undo()
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree,
        )

    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(progs.init, key)
    env_state, obs, carry = jax.eval_shape(env_reset, key)
    traj = jax.eval_shape(
        rollout, *on_chip((state.params, env_state, obs, carry, key))
    )[3]
    assert isinstance(traj, ActorTrajectory)
    return progs.learner_step_donated.lower(
        on_chip(state), on_chip(traj)
    ).compile().as_text()


def test_impala_learner_reads_its_batch_at_one_byte_a_pixel(monkeypatch, topo):
    """PR 30: ``impala-pong``'s learner step at the benchmark cell's
    ``[32, 512]`` batch. The ``[T, B]`` axes of the frames are merged
    while they are uint8 and Conv_0 converts its own input. On the
    parent (PR 29) this test finds exactly two offenders, both bf16
    passes over the whole batch that compute nothing:
    ``multiply_bitcast_fusion -> bf16[32,84,84,1,4,512]`` (the batch
    written out converted) and ``copy.8`` of the same shape (the merge
    done on that copy), 16.7 % of the chip's time (PERF.md section 6)."""
    text = learner_step_text(
        monkeypatch, topo, "impala-pong", envs_per_actor=512
    )
    (rows,) = [
        rows for rows in unfused(text).values()
        if any(op_name == "batch.obs" for *_, op_name, _ in rows)
    ]
    frames = [
        (name, result, opcode) for name, result, opcode, _, _ in rows
        if "84,84" in result and opcode not in FREE
    ]
    # (i) no pass over the frames in the compute dtype, or wider
    wide = [f for f in frames if re.match(r"\(*(bf16|f32)\[", f[1])]
    assert not wide, wide
    # (ii) Conv_0 converts for itself: the forward pass over the batch,
    # the one over the bootstrap observation and the weight gradient
    # read uint8.
    conv0_inputs = conv0_frame_inputs(rows)
    assert len(conv0_inputs) >= 3, conv0_inputs
    assert all(r.startswith("u8[") for r in conv0_inputs), conv0_inputs
    # (iii) at most one uint8 pass besides the convolutions' own reads:
    # what lands in fast memory (``S(1)``: the bootstrap observation's
    # slices and their concatenation) is a prefetch, not a pass.
    movers = [f for f in frames if "S(1)" not in f[1]]
    assert len(movers) <= 1, movers


# ---- the Qwen3-Next core's two new kernels at published widths (PR 27) ----


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def test_expert_layer_compiles_to_grouped_product_kernels(one_chip):
    """One timed minibatch of the ``ppo-qwen3next-recall`` preset
    through the expert layer, forward and backward, for the described
    v5e: the grouped products are the TPU's own ragged-dot kernels (no
    dense loop over the held experts), nine of them — gate, up, down,
    each forward, input gradient and weight gradient — and the buffer
    is the stated 2.0 x the expected local pairs."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import qwen3_next as qn

    cfg = PRESETS["ppo-qwen3next-recall"][1]["seq_model"]
    tokens = 32 * 256
    assert cfg.moe_capacity(tokens) == 2 * tokens * 10 * 32 // 512 == 10240
    spec = qn.layer_param_spec(cfg, 0)
    names = ("router", "w_gate", "w_up", "w_down")
    p = {n: jax.ShapeDtypeStruct(spec[n][0], jnp.float32, sharding=one_chip)
         for n in names}
    x = jax.ShapeDtypeStruct((tokens, cfg.hidden_size), jnp.float32,
                             sharding=one_chip)

    def loss(p, x):
        y, stats = qn.routed_experts(p, x, cfg, jnp.bfloat16)
        return jnp.sum(y * y), stats

    text = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        p, x
    ).compile().as_text()
    kernels = re.findall(r"%(ragged-dot-[\w\-]+\.?\d*) = (\S+) custom-call",
                         text)
    products = [shape for name, shape in kernels if "metadata" not in name]
    assert len(products) == 9, kernels
    assert sum(s.startswith("f32[32,") for s in products) == 3  # dW
    assert sum(s.startswith("f32[10240,") for s in products) == 6
    # one rung at 2.0 x: the buffer's rows are not chosen a call
    assert cfg.expert_spec.buffer_ladder(tokens) == (10240,)
    assert " conditional(" not in text


def test_a_blocks_pass_picks_its_buffers_rows_by_the_pairs_it_counted(
    one_chip
):
    """One pass of the ``ppo-sdar-turns`` preset (128 blocks of 4
    positions) through the expert layer for the described v5e: ONE
    ``conditional`` over the ladder's three row counts, each branch the
    three grouped products at its own rows and the scatter-add back;
    the experts' weights reach the branches as they are (cast once,
    outside, where a rollout's loop can hoist it; no copy of them); and
    the trace reader's join (``profiling.scope_table``) finds every
    kernel inside a branch under ``moe_experts`` and every branch's
    scatter-add under ``moe_combine``, though a branch's parameter
    carries no scope."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import sdar
    from actor_critic_algs_on_tensorflow_tpu.utils import profiling

    cfg = PRESETS["ppo-sdar-turns"][1]["seq_model"]
    ladder = (768, 1536, 4096)
    spec = sdar.layer_param_spec(cfg)
    names = ("post_norm", "router", "w_gate", "w_up", "w_down")
    p = {n: jax.ShapeDtypeStruct(spec[n][0], jnp.float32, sharding=one_chip)
         for n in names}
    x = jax.ShapeDtypeStruct((128, cfg.block_length, cfg.hidden_size),
                             jnp.float32, sharding=one_chip)

    def step(p, x):
        with jax.named_scope(profiling.ROLLOUT), jax.named_scope(
            profiling.POLICY_ACT
        ):
            return sdar._expert_layer(
                p, x, cfg, jnp.bfloat16, every_pair=True
            )

    text = jax.jit(step).lower(p, x).compile().as_text()
    (branches,) = re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}", text
    )
    branches = [b.strip().lstrip("%") for b in branches.split(",")]
    assert len(branches) == len(ladder)
    computation, inside = None, {}
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            computation = head.group(1)
        elif " = " in line:
            inside.setdefault(computation, []).append(line.strip())
    table = profiling.scope_table(text)
    step_phases = (profiling.ROLLOUT, profiling.POLICY_ACT, profiling.MOE)

    def phases(line):
        return table[line.removeprefix("ROOT ").split(", metadata=")[0]]

    for rows, branch in zip(ladder, branches):
        kernels = [line for line in inside[branch]
                   if line.startswith("%ragged-dot-none")]
        assert sorted(
            line.split(" = ")[1].split("{")[0] for line in kernels
        ) == [f"f32[{rows},2048]", f"f32[{rows},768]", f"f32[{rows},768]"]
        for line in kernels:
            assert phases(line) == step_phases + (profiling.MOE_EXPERTS,)
        adds = [line for line in inside[branch]
                if re.match(r"%\S+ = f32\[512,2048\]\S* fusion\(", line)
                and "/scatter-add" in line]
        assert adds, branch
        for line in adds:
            assert phases(line) == step_phases + (
                profiling.MOE_DISPATCH, profiling.MOE_COMBINE
            )
    # no kernel outside the branches, and the weights as they came: in
    # float32 the parameters alone, in bfloat16 one cast each, made in
    # the entry computation, and no copy of either (what the compiler's
    # memory-space assignment prefetches into fast memory, `S(1)`, for
    # a kernel is its own business)
    assert sum(line.startswith("%ragged-dot-none")
               for lines in inside.values() for line in lines) == 9
    weights = re.compile(
        r"^%?\S+ = (f32|bf16)\[16,(?:2048,768|768,2048)\](\S*) ([\w\-]+)\("
    )
    made = [(c, *m.groups()) for c, lines in inside.items()
            for m in map(weights.match, lines)
            if m and m.group(3) not in FREE]
    assert not [m for m in made if m[1] == "f32"], made
    casts = [m for m in made if m[3] in ("convert", "fusion")]
    assert len(casts) == 3 and not any(m[0] in branches for m in casts), made
    assert all("S(1)" in m[2] and m[3] != "copy"
               for m in made if m not in casts), made


def test_chunked_deltanet_compiles_at_the_timed_shape(one_chip):
    """The chunked delta rule at a timed minibatch's shape (32 envs, 32
    value heads, 256 steps, chunk 64), forward and backward: the
    chip's compiler takes it, the scan over the four chunks stays a
    loop, and the state it carries is float32. The chunks' 4,096
    unit-lower systems are inverted once, by the kernel that
    substitutes 128 of them at once in VMEM, and nowhere by the
    compiler's serial InvertDiagBlocksLowerTriangular: the backward
    reuses the inverse."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import qwen3_next as qn

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(q, k, v, g, beta):
        o, _ = qn.chunk_gated_delta_rule(q, k, v, g, beta, 64)
        return jnp.sum(o * o)

    qk, gb = arr(32, 32, 256, 128), arr(32, 32, 256)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, qk, gb, gb
    ).compile()
    text = compiled.as_text()
    assert re.search(r" while\(", text)
    assert "f32[32,32,128,128]" in text
    assert "bf16[32,32,128,128]" not in text
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert " triangular-solve(" not in text
    kernels = re.findall(
        r"%(unit_lower_inverse[\w.]*) = f32\[64,64,4096\]\S* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\"",
        text,
    )
    assert len(kernels) == 1, kernels
    # well inside the chip beside the weights (activations of one layer)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def test_the_decode_step_is_the_one_pass_kernel_in_place(one_chip):
    """The step form's state update at the timed shape (128 envs, 32
    value heads of 128 x 128), lowered for the described v5e through
    the model's own choice (``qn._state_step``): the Pallas kernel is
    what the TPU gets (no conditional left, no plain reduce pass), the
    donated state is its output's buffer, and beside the state the
    program holds no second copy of it."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import qwen3_next as qn

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    B, h, d = 128, 32, 128
    compiled = jax.jit(qn._state_step, donate_argnums=0).lower(
        arr(B, h, d, d), arr(B, h, d), arr(B, h, d), arr(B, h, d),
        arr(B, h), arr(B, h), arr(B),
    ).compile()
    text = compiled.as_text()
    kernels = re.findall(
        r"%(gdn_state_step[\w.]*) = .*custom_call_target=\"tpu_custom_call\"",
        text,
    )
    assert len(kernels) == 1, kernels
    assert "output_to_operand_aliasing={{0}: (1, {})}" in text
    assert " conditional(" not in text
    assert not re.search(r"f32\[128,32,128,128\]\S* (fusion|copy)\(", text)
    memory = compiled.memory_analysis()
    state = B * h * d * d * 4
    assert memory.alias_size_in_bytes == state
    assert memory.temp_size_in_bytes < state // 16


def test_the_absorbed_step_reads_the_latents_and_rebuilds_no_key(one_chip):
    """Latent attention's step form at the timed shape (128 envs, six
    layers' caches of 512 rows of 512 + 64 in one array, 16 heads) as
    the rollout runs it: in a loop that carries the donated caches,
    lowered for the described v5e. No instruction holds a per-head key
    or value of the cache (``[128, 512, 16, ...]`` in any order), the
    caches are written in place (a scatter of 128 rows, no pass over
    them), and beside them the program holds less than a tenth of one
    layer's. Scores, softmax and weighted sum are the one-pass kernel
    and nothing else: one custom call, no conditional left of the
    choice by platform, no ``[128, 16, 512]`` float32 scores, and
    beside the scatter and the kernel no instruction that takes the
    caches: no copy, no staging through VMEM.

    The caches come and go in the layout the loop holds them in, rows
    of 576 minor: the chip's own choice for a parameter of this shape
    puts the 512 rows minor, which is one transposing copy in and one
    out of the PROGRAM (an iteration, not a step), outside the loop."""
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout

    from actor_critic_algs_on_tensorflow_tpu.models import kimi_vl as kv

    cfg = PRESETS["ppo-kimivl-recall"][1]["seq_model"]
    B, L, H, layers = 128, 512, cfg.hidden_size, cfg.num_hidden_layers
    steps = 3
    assert cfg.cache_width == 576 and layers == 6

    def arr(shape, dtype=jnp.float32, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    spec = kv.layer_param_spec(cfg, 1)
    names = ("q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj")
    p = {n: arr(spec[n][0]) for n in names}
    rows_minor = Format(
        Layout(major_to_minor=(0, 1, 2, 3), tiling=((8, 128), (2, 1))),
        one_chip,
    )

    def rollout(p, xs, caches, pos):
        def step(carry, x):
            caches, pos = carry
            y, caches = kv.mla_step(p, x, caches, 1, pos, cfg, jnp.bfloat16)
            return (caches, pos + 1), y

        (caches, pos), ys = jax.lax.scan(step, (caches, pos), xs)
        return ys, caches, pos

    compiled = jax.jit(
        rollout, donate_argnums=2,
        out_shardings=(one_chip, rows_minor, one_chip),
    ).lower(
        p, arr((steps, B, H)),
        arr((B, layers, L, 576), jnp.bfloat16, rows_minor),
        arr((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text
    shapes = {tuple(map(int, dims.split(",")))
              for dims in re.findall(r"[a-z]\d*\[([\d,]+)\]", text)}
    per_head = [s for s in shapes if len(s) >= 4 and {128, 512, 16} <= set(s)]
    assert not per_head, per_head
    assert re.search(r"bf16\[128,6,512,576\]\S* scatter\(", text)
    cache = B * L * 576 * 2
    memory = compiled.memory_analysis()
    # the caches as the chip holds them: rows of 576 in 5 lane tiles
    assert memory.alias_size_in_bytes == layers * cache * 640 // 576
    assert memory.temp_size_in_bytes < cache // 10

    kernels = re.findall(
        r"%(mla_absorbed_step[\w.]*) = .*custom_call_target=\"tpu_custom_call\"",
        text,
    )
    assert len(kernels) == 1, kernels
    assert " conditional(" not in text
    # The plain form's scores [B, heads, L] (L = the latent's width
    # here, so the kernel's own result has the shape; no fusion, dot or
    # reduce may).
    scores = [
        (name, op) for name, shape, op in INSTRUCTION.findall(text)
        if shape.startswith("f32[128,16,512]") and op not in FREE
    ]
    assert [op for _, op in scores] == ["custom-call"], scores
    # Who takes the caches, outside fused computations, in the whole
    # program: the scatter's fusion (in place) and the kernel, in the
    # loop's body. A layer's own 75 MB array went to VMEM and back
    # around them every step; an array of six layers is larger than
    # VMEM.
    takers = sorted(
        (opcode, comp.startswith("main"))
        for comp, rows in unfused(text).items()
        for _, _, opcode, _, line in rows
        if opcode not in FREE | {"while"} and any(
            result.startswith("bf16[128,6,512,576]")
            for name, result, *_ in rows
            if re.search(rf"%{re.escape(name)}\b",
                         line[line.index(opcode + "("):])
        )
    )
    assert takers == [("custom-call", False), ("fusion", False)], takers
    staged = [line for line in text.splitlines()
              if "-start(" in line and "[128,6,512,576]" in line]
    assert not staged, staged


def test_a_blocks_pass_writes_its_rows_in_place_and_copies_no_cache(one_chip):
    """The block-diffusion core's step form at the timed shape (128
    envs, six layers' key/value caches of 192 rows of 1,024 in one
    array, blocks of 4) as the rollout runs it: in a loop that carries
    the donated caches, lowered for the described v5e. The block's rows
    are written in place (a scatter of 4 x 128 rows into the aliased
    array), beside the caches the program holds less than a tenth of
    one layer's in HBM, nothing but that scatter produces an array of
    the caches' shape (no copy of them, none in flight), and the
    layer's rows are read through ONE slice of them: the compiler
    stages that layer's 50 MB through VMEM once a pass and cuts the
    eight key and value heads' columns there (whole lane tiles). No
    instruction holds a key or value half ``[128, 192, 512]`` or its
    per-head reshape: that was the first formulation, a 50 MB copy and
    two transposes a pass (PERF.md section 6, PR 33)."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import sdar

    cfg = PRESETS["ppo-sdar-turns"][1]["seq_model"]
    B, L, H, layers, n = 128, 192, cfg.hidden_size, cfg.num_hidden_layers, 4
    steps = 3
    assert cfg.cache_width == 1024 and layers == 6 and cfg.block_length == n

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spec = sdar.layer_param_spec(cfg)
    names = ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm")
    p = {name: arr(spec[name][0]) for name in names}

    def rollout(p, xs, caches, pos):
        def step(carry, x):
            caches, pos = carry
            y, caches = sdar.gqa_block_step(
                p, x, caches, 1, pos, cfg, jnp.bfloat16
            )
            return (caches, pos + n), y

        (caches, pos), ys = jax.lax.scan(step, (caches, pos), xs)
        return ys, caches, pos

    compiled = jax.jit(rollout, donate_argnums=2).lower(
        p, arr((steps, B, n, H)), arr((B, layers, L, 1024), jnp.bfloat16),
        arr((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text
    assert re.search(r"bf16\[128,6,192,1024\]\S* scatter\(", text)
    cache = B * L * 1024 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == layers * cache
    assert memory.temp_size_in_bytes < cache // 10
    rows = [row for comp in unfused(text).values() for row in comp]
    made = [(name, opcode) for name, result, opcode, *_ in rows
            if result.startswith("bf16[128,6,192,1024]")
            and opcode not in FREE | {"while"}]
    assert [opcode for _, opcode in made] == ["fusion"], made  # the scatter
    assert not [line for line in text.splitlines()
                if "-start(" in line and "[128,6,192,1024]" in line]
    halves = [(name, result) for name, result, opcode, *_ in rows
              if opcode not in FREE and re.match(
                  r"bf16\[128,192,(512|4,128)", result)]
    assert not halves, halves
    layer_rows = [name for name, result, opcode, *_ in rows
                  if result.startswith("bf16[128,192,1024]")
                  and opcode not in FREE]
    assert len(layer_rows) == 1, layer_rows


def test_the_update_attention_keeps_its_scores_off_the_chips_memory(one_chip):
    """The sequence form of SDAR's mixer at the timed shape (a minibatch
    of 16 sequences of 144 passes of 4 positions, published widths, one
    layer) as the update runs it: recomputed under ``jax.checkpoint``
    and differentiated, lowered for the described v5e. The attention
    is the kernel pair and nothing else: the forward kernel twice (the
    pass and its recomputation), the backward once, no conditional left
    of the choice by platform, and no buffer of the scores' size, in
    any arrangement of their ``16 x 32 x 576 x 576`` elements, forward
    or backward; the plain form held several of 680 MB each."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import sdar
    from actor_critic_algs_on_tensorflow_tpu.models.decoder import rms_norm

    cfg = PRESETS["ppo-sdar-turns"][1]["seq_model"]
    b, T, L, H = 16, 144, cfg.block_length, cfg.hidden_size
    n = T * L
    assert (n, cfg.num_attention_heads, cfg.head_dim) == (576, 32, 128)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spec = sdar.layer_param_spec(cfg)
    names = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj",
             "q_norm", "k_norm")
    p = {name: arr(spec[name][0]) for name in names}

    def loss(p, x, commit):
        positions, step, key_commit = sdar.trajectory_steps(commit, L)

        def mixer(p, x):
            h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
            return x + sdar.gqa_seq(
                p, h, positions, step, key_commit, cfg, jnp.bfloat16
            )

        return jnp.sum(jax.checkpoint(mixer)(p, x) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        p, arr((b, n, H)), arr((T, b), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    kernels = re.findall(
        r"%(block_attention[a-z_]*)[\w.]* = .*"
        r"custom_call_target=\"tpu_custom_call\"", text,
    )
    assert sorted(kernels) == [
        "block_attention", "block_attention", "block_attention_backward"
    ], kernels
    assert " conditional(" not in text
    scores = b * cfg.num_attention_heads * n * n
    large = {
        dims for dims in re.findall(r"[a-z]\d*\[([\d,]+)\]", text)
        if math.prod(map(int, dims.split(","))) >= scores
    }
    assert not large, large
    # beside the arguments 0.94 GiB: the queries, the output, their
    # cotangents (151 MB each in float32); the plain form's 1.53
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * 2 ** 30


def test_the_state_space_step_updates_its_carry_in_place(one_chip):
    """The Granite hybrid core's step form at the timed shape (32 envs;
    nine Mamba-2 layers' states ``[64, 64, 128]`` and convolution tails
    in one array each, one attention layer's key and value caches of
    512 rows) as the rollout runs it: two Mamba-2 mixers and the
    attention in a loop that carries the donated arrays, lowered for
    the described v5e through the model's own choice
    (``gh._state_step``). All four arrays are aliased to the program's
    arguments; the states, 18 MiB an env, are written by the one-pass
    kernel and by nothing else (one ``mamba_state_step`` custom call a
    layer in the loop's body, its output the state operand's buffer, no
    conditional left of the choice by platform, and no fusion, copy or
    ``copy-start`` that produces or stages an array of the states'
    shape); the tails and the caches, 15 and 2 x 16 MiB in all, are, in
    the loop's body, either written in place or staged whole through
    VMEM around the step (``copy-start`` / ``copy-done``) and never
    copied in HBM (the program may change a cache's layout once, outside
    the loop); and beside the carry the program holds less than one
    layer's states."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import granite_hybrid as gh

    cfg = PRESETS["ppo-granite-recall"][1]["seq_model"]
    B, L, H, steps = 32, 512, cfg.hidden_size, 3
    n_mamba, n_attn = cfg.layers_of("mamba"), cfg.layers_of("attention")
    assert (n_mamba, n_attn) == (9, 1)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def params(layer, names):
        spec = gh.layer_param_spec(cfg, layer)
        return {name: arr(spec[name][0]) for name in names}

    mamba = params(0, ("in_proj", "conv", "conv_bias", "dt_bias", "A_log",
                       "D", "mamba_norm", "out_proj"))
    attn = params(5, ("q_proj", "k_proj", "v_proj", "o_proj"))
    state = (B, n_mamba, cfg.mamba_n_heads, cfg.mamba_d_head,
             cfg.mamba_d_state)
    tails = (B, n_mamba, cfg.mamba_d_conv - 1, cfg.conv_channels)
    cache = (B, n_attn, L, cfg.num_key_value_heads, cfg.head_dim)

    def rollout(mamba, attn, xs, carry, pos):
        def step(carried, x):
            (S, tails, k, v), pos = carried
            keep = jnp.ones((B,), jnp.float32)
            for layer in (3, 4):
                y, S, tails, _ = gh.mamba_mixer_step(
                    mamba, x, S, tails, layer, keep, cfg, jnp.bfloat16
                )
                x = x + y
            y, k, v = gh.gqa_step(attn, x, k, v, 0, pos, cfg, jnp.bfloat16)
            return ((S, tails, k, v), pos + 1), x + y

        (carry, pos), ys = jax.lax.scan(step, (carry, pos), xs)
        return ys, carry, pos

    compiled = jax.jit(rollout, donate_argnums=3).lower(
        mamba, attn, arr((steps, B, H)),
        (arr(state), arr(tails), arr(cache, jnp.bfloat16),
         arr(cache, jnp.bfloat16)),
        arr((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text
    memory = compiled.memory_analysis()
    carried = 4 * (math.prod(state) + math.prod(tails)) + 2 * 2 * (
        math.prod(cache)
    )
    # (the chip may pad a carried array's tiles: at least the carry)
    assert memory.alias_size_in_bytes >= carried
    assert memory.temp_size_in_bytes < 4 * math.prod(state) // n_mamba
    comps = unfused(text)

    def made(shape, in_loop=False):
        return [(name, opcode) for comp, rows in comps.items()
                if not (in_loop and comp.startswith("main"))
                for name, result, opcode, *_ in rows
                if result.startswith(shape) and opcode not in FREE | {"while"}]

    dims = lambda shape: ",".join(map(str, shape))
    kernels = [
        (comp, line) for comp, rows in comps.items()
        for name, _, opcode, _, line in rows
        if opcode == "custom-call" and name.startswith("mamba_state_step")
    ]
    assert len(kernels) == 2, kernels
    # the kernel sees the state as tiles of 128 rows, a view
    tiled = state[:2] + (state[2] * state[3] // 128, 128, state[4])
    for comp, line in kernels:
        assert not comp.startswith("main"), comp
        assert 'custom_call_target="tpu_custom_call"' in line
        assert f" = (f32[{dims(tiled)}]" in line
        # operands 0 and 1 are the layer and the decays; the state is 2
        assert "output_to_operand_aliasing={{0}: (2, {})}" in line
    assert " conditional(" not in text
    for shape in (state, tiled):
        assert not made(f"f32[{dims(shape)}]"), made(f"f32[{dims(shape)}]")
        assert not [line for line in text.splitlines()
                    if "-start(" in line and f"[{dims(shape)}]" in line]
    # (one attention layer: the loop may hold its cache without the
    # layer axis, a view)
    for shapes in ((dims(tails),), (dims(cache), dims(cache[:1] + cache[2:]))):
        moved = {opcode for shape in shapes for kind in ("f32", "bf16")
                 for _, opcode in made(f"{kind}[{shape}]", in_loop=True)}
        assert moved and moved <= {
            "fusion", "copy-start", "copy-done", "custom-call"
        }, (shapes, moved)


def test_the_xing_step_writes_its_cache_in_place_beside_the_streams(one_chip):
    """Xing's step form at the timed shape (128 envs, five layers'
    caches of 256 rows of 512 + 64 in one array, 32 heads, a query of
    rank 768, four float32 streams of 3,584 around the mixer and the
    expert block) as the rollout runs an expert layer: in a loop that
    carries the donated caches, lowered for the described v5e. The
    hyper-connections change nothing of what Kimi-VL's step does to its
    cache: the caches are written in place (a scatter of 128 rows),
    scores, softmax and weighted sum are the one-pass kernel (one custom
    call, no conditional left), beside them no instruction takes the
    caches and none is staged; and the Sinkhorn runs on matrices with
    the envs minor (no ``[128, 4, 4]`` float32 array is made in the
    loop: on the chip its two axes of 4 would be padded to a tile)."""
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout

    from actor_critic_algs_on_tensorflow_tpu.models import (
        hyper_connections as hc,
    )
    from actor_critic_algs_on_tensorflow_tpu.models import xing

    cfg = PRESETS["ppo-xing-recall"][1]["seq_model"]
    B, L, H, layers = 128, 256, cfg.hidden_size, cfg.num_hidden_layers
    assert cfg.cache_width == 576 and layers == 5 and cfg.hc_mult == 4

    def arr(shape, dtype=jnp.float32, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    p = {n: arr(s) for n, (s, _) in xing.layer_param_spec(cfg, 1).items()}
    rows_minor = Format(
        Layout(major_to_minor=(0, 1, 2, 3), tiling=((8, 128), (2, 1))),
        one_chip,
    )

    def rollout(p, xs, caches, pos):
        def step(carry, x):
            caches, pos = carry
            X, caches, _, _ = xing._decoder_layer_step(
                p, hc.expand(x, cfg.hc_mult), caches, 1, pos, cfg,
                jnp.bfloat16, True,
            )
            return (caches, pos + 1), hc.reduce(X, cfg.hc_mult)

        (caches, pos), ys = jax.lax.scan(step, (caches, pos), xs)
        return ys, caches, pos

    compiled = jax.jit(
        rollout, donate_argnums=2,
        out_shardings=(one_chip, rows_minor, one_chip),
    ).lower(
        p, arr((3, B, H)),
        arr((B, layers, L, 576), jnp.bfloat16, rows_minor),
        arr((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text
    assert re.search(r"bf16\[128,5,256,576\]\S* scatter\(", text)
    cache = B * L * 576 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == layers * cache * 640 // 576
    kernels = re.findall(
        r"%(mla_absorbed_step[\w.]*) = .*custom_call_target=\"tpu_custom_call\"",
        text,
    )
    assert len(kernels) == 1, kernels
    assert " conditional(" not in text
    comps = unfused(text)
    takers = sorted(
        (opcode, comp.startswith("main"))
        for comp, rows in comps.items()
        for _, _, opcode, _, line in rows
        if opcode not in FREE | {"while"} and any(
            result.startswith("bf16[128,5,256,576]")
            for name, result, *_ in rows
            if re.search(rf"%{re.escape(name)}\b",
                         line[line.index(opcode + "("):])
        )
    )
    assert takers == [("custom-call", False), ("fusion", False)], takers
    staged = [line for line in text.splitlines()
              if "-start(" in line and "[128,5,256,576]" in line]
    assert not staged, staged
    # (an array of that shape whose minor axis is the envs' is no pad)
    padded = [(name, result) for comp, rows in comps.items()
              if not comp.startswith("main")
              for name, result, opcode, *_ in rows
              if result.startswith("f32[128,4,4]") and opcode not in FREE
              and not result.startswith("f32[128,4,4]{0,")]
    assert not padded, padded


def test_the_xing_update_mixes_its_streams_in_the_mhc_kernels(one_chip):
    """Xing's leading decoder layer as the update runs it (a minibatch of
    16 sequences of 256 tokens, four float32 streams of 3,584, published
    widths): recomputed under ``jax.checkpoint`` and differentiated,
    lowered for the described v5e. Each sublayer's two passes over the
    streams are the kernels of ``ops/pallas_hyper_connections.py``
    (``models/hyper_connections.sublayer`` chooses by the lowering
    platform and ``fits``): the pass before the sublayer four times (two
    sublayers, each in the pass and its recomputation), the pass after
    it three times (the layer's output is not recomputed), each backward
    twice; no conditional is left of the choice; no float32 array
    ``[..., 4, 4, 3584]`` is made anywhere in the program, fused or not
    (the plain residual mix's broadcast product and its transpose, 940
    MB each at these tokens); and nothing copies or relays the streams
    out of the ``[T, b, 4 x 3584]`` the layer carries them in."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import xing

    cfg = PRESETS["ppo-xing-recall"][1]["seq_model"]
    T, b, n, C = 256, 16, cfg.hc_mult, cfg.hidden_size
    assert (n, C) == (4, 3584) and not cfg.is_expert_layer(0)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spec = xing.layer_param_spec(cfg, 0)
    p = {name: arr(shape) for name, (shape, _) in spec.items()}

    def loss(p, X):
        layer = jax.checkpoint(
            lambda p, X: xing._decoder_layer_seq(p, X, cfg, jnp.bfloat16,
                                                 False)[0]
        )
        return jnp.sum(layer(p, X))

    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        p, arr((T, b, n * C))
    ).compile().as_text()
    kernels = re.findall(
        r"%(mhc_[a-z_]+)[\w.]* = .*custom_call_target=\"tpu_custom_call\"",
        text,
    )
    assert sorted(kernels) == sorted(
        ["mhc_pre"] * 4 + ["mhc_post"] * 3 + ["mhc_pre_backward"] * 2
        + ["mhc_post_backward"] * 2
    ), kernels
    assert " conditional(" not in text
    mixed = re.findall(rf"f32\[[\d,]*{n},{n},{C}\]", text)
    assert not mixed, mixed[:5]
    # the streams stay [tokens, n C] from layer to kernel and back: no
    # relayout of them (the plain path's copies to and from [T, b, n, C]
    # were ~9 of its passes, PERF.md section 5)
    streams = T * b * n * C
    moved = [
        (name, result) for rows in unfused(text).values()
        for name, result, opcode, *_ in rows
        if opcode in ("copy", "copy-start", "reshape", "transpose")
        and any(math.prod(map(int, dims.split(","))) >= streams
                for dims in re.findall(r"f32\[([\d,]+)\]", result))
    ]
    assert not moved, moved


def test_the_lfm2_step_writes_its_tails_and_cache_in_place(one_chip):
    """The LFM2 core's step form at the timed shape (128 envs; the four
    short convolutions' float32 tails ``[2, 2048]`` in one array, one
    attention layer's key and value caches of 512 rows) as the rollout
    runs it: two convolution mixers and the attention in a loop that
    carries the donated arrays, lowered for the described v5e. All three
    arrays are aliased to the program's arguments; in the loop's body
    each is either written in place or staged whole through VMEM around
    the step (``copy-start`` / ``copy-done``) or, for the caches, laid
    out anew by a fusion for attention's products; and beside the carry
    the program holds no more than those two relayouts, double-buffered
    (the scatter and the products want different layouts of one carried
    array: PERF.md section 7). The convolutions alone hold
    nothing beside the carry: their tails are written in place."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import lfm2

    cfg = PRESETS["ppo-lfm2-recall"][1]["seq_model"]
    B, L, H, steps = 128, 512, cfg.hidden_size, 3
    n_conv, n_attn = cfg.layers_of(lfm2.CONV), cfg.layers_of(lfm2.ATTENTION)
    assert (n_conv, n_attn) == (4, 1)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def params(layer, names):
        spec = lfm2.layer_param_spec(cfg, layer)
        return {name: arr(spec[name][0]) for name in names}

    conv = params(2, ("in_proj", "conv", "out_proj"))
    attn = params(1, ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                      "k_norm"))
    tails = (B, n_conv, cfg.conv_L_cache - 1, H)
    cache = (B, n_attn, L, cfg.num_key_value_heads, cfg.head_dim)

    def rollout(conv, attn, xs, carry, pos):
        def step(carried, x):
            (tails, k, v), pos = carried
            keep = jnp.ones((B,), jnp.float32)
            for layer in (1, 3):
                y, tails, _ = lfm2.shortconv_step(
                    conv, x, tails, layer, keep, cfg, jnp.bfloat16
                )
                x = x + y
            y, k, v = lfm2.gqa_step(attn, x, k, v, 0, pos, cfg, jnp.bfloat16)
            return ((tails, k, v), pos + 1), x + y

        (carry, pos), ys = jax.lax.scan(step, (carry, pos), xs)
        return ys, carry, pos

    compiled = jax.jit(rollout, donate_argnums=3).lower(
        conv, attn, arr((steps, B, H)),
        (arr(tails), arr(cache, jnp.bfloat16), arr(cache, jnp.bfloat16)),
        arr((B,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert " while(" in text
    memory = compiled.memory_analysis()
    cache_bytes = 2 * math.prod(cache)
    carried = 4 * math.prod(tails) + 2 * cache_bytes
    assert memory.alias_size_in_bytes >= carried
    # (and the step's own small arrays: scores, a row of each cache)
    assert memory.temp_size_in_bytes <= 2 * 2 * cache_bytes + 8 * 2**20
    convs_only = jax.jit(
        lambda conv, x, tails: lfm2.shortconv_step(
            conv, x, tails, 1, jnp.ones((B,)), cfg, jnp.bfloat16
        )[1:2],
        donate_argnums=2,
    ).lower(conv, arr((B, H)), arr(tails)).compile().memory_analysis()
    assert convs_only.alias_size_in_bytes >= 4 * math.prod(tails)
    assert convs_only.temp_size_in_bytes < 4 * math.prod(tails) // n_conv
    comps = unfused(text)

    def made(shape):
        return [opcode for comp, rows in comps.items()
                if not comp.startswith("main")
                for name, result, opcode, *_ in rows
                if result.startswith(shape) and opcode not in FREE | {"while"}]

    dims = lambda shape: ",".join(map(str, shape))
    # (one attention layer: the loop may hold its cache without the
    # layer axis, a view; the tails, 8 MiB, may be laid out in VMEM by a
    # copy there)
    for shapes, also in (((dims(tails),), {"copy"}),
                         ((dims(cache), dims(cache[:1] + cache[2:])), set())):
        moved = {opcode for shape in shapes for kind in ("f32", "bf16")
                 for opcode in made(f"{kind}[{shape}]")}
        assert moved and moved <= {
            "fusion", "copy-start", "copy-done", "custom-call"
        } | also, (shapes, moved)


@pytest.mark.parametrize("ent_coef", [0.0, 0.01])
def test_the_lfm2_update_head_takes_its_row_statistics_in_one_pass(
    one_chip, ent_coef
):
    """The ``ppo-lfm2-recall`` update's head at a minibatch's shapes
    (``h [512, 16, 2048]``, the tied embedding ``[8192, 2048]`` in
    bfloat16 with float32 sums), its log-probability and entropy and a
    PPO loss, differentiated for the described v5e. With
    ``log_prob_and_entropy`` no ``reduce-window`` is left (``log_softmax``
    took the row's max as one 16,383 wide, once a column: 189 ms an
    iteration on the chip, PERF.md section 5), and fewer float32
    logits-sized arrays are written than with ``log_prob`` + ``entropy``
    (one, the logits, where those write three)."""
    import jax.numpy as jnp

    from actor_critic_algs_on_tensorflow_tpu.models import decoder
    from actor_critic_algs_on_tensorflow_tpu.ops import (
        Categorical, ppo_clip_loss,
    )

    cfg = PRESETS["ppo-lfm2-recall"][1]
    T, b = cfg["rollout_length"], cfg["num_envs"] // cfg["num_minibatches"]
    H, V = cfg["seq_model"].hidden_size, cfg["seq_model"].vocab_size
    assert (T, b, H, V) == (512, 16, 2048, 8192)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled_text(one_pass):
        def loss(head, x, actions, old_log_probs, adv):
            _, logits = decoder.lm_head(x, head, 1e-5, jnp.bfloat16)
            dist = Categorical(logits)
            if one_pass:
                log_probs, ent = dist.log_prob_and_entropy(actions)
            else:
                log_probs, ent = dist.log_prob(actions), dist.entropy()
            clip = ppo_clip_loss(log_probs.reshape(-1),
                                 old_log_probs.reshape(-1), adv.reshape(-1),
                                 clip_eps=cfg["clip_eps"])
            return clip.policy_loss - ent_coef * ent.mean()

        head = {"final_norm": arr((H,)), "embedding": arr((V, H))}
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            head, arr((T, b, H)), arr((T, b), jnp.int32), arr((T, b)),
            arr((T, b)),
        ).compile().as_text()

    def logits_written(text):
        return [name for rows in unfused(text).values()
                for name, result, opcode, *_ in rows
                if f"f32[{T},{b},{V}]" in result and opcode not in FREE]

    text = compiled_text(True)
    assert " reduce-window(" not in text
    written = logits_written(text)
    before = logits_written(compiled_text(False))
    assert len(written) < len(before), (written, before)
    assert len(written) == 1, written
