"""The program's phases, named once, on both sides of the dispatch.

- ``trace(log_dir)``: one ``jax.profiler`` session (``train.py
  --profile-dir``). Tracing is on exactly when such a session is; there
  is no other switch.
- The phase names below: ``jax.named_scope(<phase>)`` wraps the part of
  a fused program that does that work (``algos/common.py``,
  ``algos/ppo.py``, ``make_impala``). A scope is HLO metadata only —
  every instruction's ``op_name`` carries the scopes it was traced
  under — so the compiled program, its memory and its speed are those
  of the unscoped build.
- ``LAYER_SCOPES``: the sequence cores' layers, named the same way
  inside ``policy_act`` and ``loss_grad``: a mixer (``gdn``,
  ``gated_attn``, ``mla``, ``gqa``, ``mamba``), inside it exactly one of
  ``mixer_proj`` / ``mixer_pointwise`` / ``mixer_core`` over every
  instruction (projections; norms, rotary, gates, convolution,
  reshapes; scores to weighted sum or the delta rule, with the cache or
  state write), inside the core its forms (``gdn_state``,
  ``gdn_chunk_solve``, ``gdn_chunk_products``, ``mla_seq_attend``,
  ``gqa_block_step``, ``gqa_seq_attend``, ``mamba_state``,
  ``mamba_chunk_scan``; ``mla_absorbed`` holds its two
  products with ``kv_b_proj``'s halves as ``mixer_proj``); the expert
  block (``moe`` with ``moe_router``, ``moe_dispatch`` and in it
  ``moe_combine``, ``moe_experts``, ``moe_shared``), ``dense_mlp``,
  ``lm_head`` and, in ``policy_act``, ``sample``. No layer index in a
  name: a rollout's part of a layer is its operations under ``rollout``.
- ``span(name)``: a host span in the profiler's trace
  (``jax.profiler.TraceAnnotation``) for a wait that has no counter.
  ``utils/metrics.py::TimeSplit.span`` is the same span under the
  counter's log-row key, so a column of the log and a span of the
  trace are one name. Outside a session a span is a flag test.
- ``traced_steps(range)``: a run loop's iterations, each one step of
  XProf's step view.
- ``scope_table(hlo_text)``: which phases each instruction of a
  compiled program belongs to, keyed the way the TPU trace names a
  device operation, so a trace reader can join device time to phases
  (``perfbench/rules/scope_time_share.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Dict, Tuple

import jax

# Device phases. Sites import the constant: a typo is an ImportError.
ROLLOUT = "rollout"                # the whole rollout scan
POLICY_ACT = "policy_act"          # in rollout: the acting forward pass
ENV_STEP = "env_step"              # in rollout: dynamics, render, stack
ADVANTAGE = "advantage"            # GAE / V-trace
UPDATE = "update"                  # everything that trains
MINIBATCH_PREP = "minibatch_prep"  # in update: slice, convert, relayout
LOSS_GRAD = "loss_grad"            # in update: forward + backward
OPTIMIZER = "optimizer"            # in update: all-reduce + Adam
# Layers of the sequence-policy cores (models/qwen3_next.py,
# models/kimi_vl.py, models/sdar.py, models/granite_hybrid.py; the
# expert block is models/moe.py's), inside policy_act and loss_grad; read like the
# phases, listed apart.
GDN = "gdn"                        # a Gated DeltaNet mixer
GDN_STATE = "gdn_state"            # in gdn: the step form's state update
GATED_ATTN = "gated_attn"          # a gated softmax-attention mixer
MOE = "moe"                        # the whole expert block
MOE_ROUTER = "moe_router"          # in moe: product, softmax, top-k
MOE_DISPATCH = "moe_dispatch"      # in moe: sort, gather, combine
MOE_EXPERTS = "moe_experts"        # in moe: the grouped products
MOE_SHARED = "moe_shared"          # in moe: the shared expert
LM_HEAD = "lm_head"                # final norm, logits, log-prob, entropy
MLA = "mla"                        # a latent-attention mixer, both forms
MLA_ABSORBED = "mla_absorbed"      # in mla: the step form over the cache
DENSE_MLP = "dense_mlp"            # a leading dense layer's feed-forward
GQA = "gqa"                        # a grouped-query mixer, both forms
GQA_BLOCK_STEP = "gqa_block_step"  # in gqa: a block's pass over the cache
GQA_SEQ_ATTEND = "gqa_seq_attend"  # in gqa: the sequence form less projections
# One level down, the same three names in every mixer (gdn, gated_attn,
# mla, gqa, mamba), both forms, no layer index: every instruction traced under
# a mixer's scope is under exactly one of them.
MIXER_PROJ = "mixer_proj"          # the linear projections in and out
MIXER_POINTWISE = "mixer_pointwise"  # norms, rotary, gates, conv, reshapes
MIXER_CORE = "mixer_core"          # scores to weighted sum, or the delta rule
MLA_SEQ_ATTEND = "mla_seq_attend"  # in mla's core: the expanded attention
GDN_CHUNK_SOLVE = "gdn_chunk_solve"  # in gdn's core: a, rhs, the solve
GDN_CHUNK_PRODUCTS = "gdn_chunk_products"  # in gdn's core: decay, qk, scan
MOE_COMBINE = "moe_combine"        # in moe_dispatch: the scatter-add back
SAMPLE = "sample"                  # in policy_act: sample and its log-prob
MAMBA = "mamba"                    # a Mamba-2 mixer, both forms
MAMBA_STATE = "mamba_state"        # in mamba's core: the step form's update
MAMBA_CHUNK_SCAN = "mamba_chunk_scan"  # in mamba's core: the chunked scan
LAYER_SCOPES = (GDN, GDN_STATE, GATED_ATTN, MOE, MOE_ROUTER, MOE_DISPATCH,
                MOE_EXPERTS, MOE_SHARED, LM_HEAD, MLA, MLA_ABSORBED,
                DENSE_MLP, GQA, GQA_BLOCK_STEP, GQA_SEQ_ATTEND,
                MIXER_PROJ, MIXER_POINTWISE, MIXER_CORE, MLA_SEQ_ATTEND,
                GDN_CHUNK_SOLVE, GDN_CHUNK_PRODUCTS, MOE_COMBINE, SAMPLE,
                MAMBA, MAMBA_STATE, MAMBA_CHUNK_SCAN)
MIXER_SCOPES = (GDN, GATED_ATTN, MLA, GQA, MAMBA)
MIXER_PARTS = (MIXER_PROJ, MIXER_POINTWISE, MIXER_CORE)
PHASES = (ROLLOUT, POLICY_ACT, ENV_STEP, ADVANTAGE, UPDATE,
          MINIBATCH_PREP, LOSS_GRAD, OPTIMIZER)

# Host spans that have no TimeSplit counter behind them.
ACTOR_ROLLOUT_DISPATCH = "actor_rollout_dispatch"
ACTOR_QUEUE_PUT = "actor_queue_put"
SENTINEL_CHECK = "sentinel_check"
VALIDATE_BATCH = "validate_batch"
PUBLISH_PARAMS = "publish_params"
LOG_FETCH = "log_fetch"

span = jax.profiler.TraceAnnotation


def traced_steps(iterations):
    """``for it in traced_steps(range(n))``: each pass of the loop's
    body inside one ``StepTraceAnnotation("train", step_num=it)``."""
    for it in iterations:
        with jax.profiler.StepTraceAnnotation("train", step_num=it):
            yield it


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace: ``with trace("/tmp/tb"): run_iterations()``.

    View with XProf/TensorBoard or load the .trace.json.gz in Perfetto.
    """
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?(%[\w.\-]+ = .*?)(?:, metadata=\{(.*?)\})?"
    r"(?:, backend_config=.*)?$"
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_NAME = re.compile(r"%[\w.\-]+")
# A transform wraps the scope under it: ``transpose(jvp(loss_grad))``.
_TRANSFORMED = re.compile(r"^(?:\w+\()+([\w.\-]*)\)+$")


def phases_of(op_name: str) -> Tuple[str, ...]:
    """The declared phases in one ``op_name``, outermost first. Where
    the compiler merged instructions and joined their names with
    ``;``, the first name speaks."""
    found = []
    for segment in op_name.split(";")[0].split("/"):
        m = _TRANSFORMED.match(segment)
        if m and not segment.startswith(("jit(", "pjit(")):
            segment = m.group(1)
        if segment in PHASES + LAYER_SCOPES and segment not in found:
            found.append(segment)
    return tuple(found)


def scope_table(hlo_text: str) -> Dict[str, Tuple[str, ...] | None]:
    """Compiled HLO text -> ``{instruction: phases}``.

    The key is the instruction's text up to ``, metadata=``, which is
    the name the TPU trace gives the operation's events (there with
    the operands' shapes, so print the text with them, or join on less
    of it, where the two are to be joined).

    An instruction the compiler made carries no ``op_name`` (or, a
    kernel it put in, one of its own coining with no traced function in
    it) — a layout copy, an async start/done pair, a fusion it assembled
    itself, a grouped-product custom call — and on the chip those are
    9-15 % of the device's time. Such a fusion
    takes the deepest phase list among the instructions it fused; any
    other takes the phases of the instruction it feeds (a copy exists
    for its consumer), failing that of the one that feeds it. A kernel
    of the compiler's own name is work, not a copy: where it feeds a
    fusion, the fused instruction that reads it speaks and not the
    fusion's root, and it takes a consumer's phases only where
    something that feeds it is in the same phase (``PHASES``), its
    operands' otherwise — a weight gradient's grouped product feeds
    Adam and is no part of ``optimizer`` (``_kernel_phases``). What is
    still unnamed has no phase: ``()``. One text under two different
    phase lists maps to ``None``: a reader must take that as not
    found, never pick one.
    """
    computations = _parse(hlo_text)
    table: Dict[str, Tuple[str, ...] | None] = {}
    for instructions in computations.values():
        _inherit(instructions, computations)
        for inst in instructions.values():
            phases = inst.phases or ()
            if table.setdefault(inst.key, phases) != phases:
                table[inst.key] = None
    return table


@dataclasses.dataclass
class _Instruction:
    key: str
    phases: Tuple[str, ...] | None      # None: no op_name of its own
    operands: Tuple[str, ...]
    calls: Tuple[str, ...]
    kernel: bool = False                # a kernel under the compiler's name


def _parse(hlo_text: str) -> Dict[str, Dict[str, _Instruction]]:
    """``{computation: {instruction name: _Instruction}}``."""
    computations: Dict[str, Dict[str, _Instruction]] = {}
    current: Dict[str, _Instruction] = {}
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(1), {})
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        key = m.group(1)
        name, _, rest = key.partition(" = ")
        op_name = _OP_NAME.search(m.group(2) or "")
        calls = tuple(_CALLED.findall(rest))
        # A kernel the compiler put in under a name of its own coining
        # (the TPU's grouped products are all `ragged-dot-none`, 9.7 %
        # of the Qwen3-Next iteration: PERF.md section 6, PR 27) is as
        # good as unnamed, and inherits as `_kernel_phases` says.
        kernel = op_name is not None and (
            " custom-call(" in rest and "jit(" not in op_name.group(1)
        )
        traced = op_name is not None and not kernel
        current[name] = _Instruction(
            key=key,
            phases=phases_of(op_name.group(1)) if traced else None,
            operands=tuple(
                n for n in _NAME.findall(rest) if n[1:] not in calls
            ),
            calls=calls,
            kernel=kernel,
        )
    return computations


def _inherit(instructions, computations) -> None:
    """Phases for one computation's instructions without ``op_name``."""
    for inst in instructions.values():
        if inst.phases is None and inst.calls:
            fused = [
                i.phases for c in inst.calls
                for i in computations.get(c, {}).values() if i.phases
            ]
            if fused:
                inst.phases = max(fused, key=len)
    users: Dict[str, list] = {}
    for name, inst in instructions.items():
        for operand in inst.operands:
            users.setdefault(operand, []).append(name)

    def spread(neighbours, kernels: bool) -> None:
        changed = True
        while changed:
            changed = False
            for name, inst in instructions.items():
                if inst.phases is not None or (inst.kernel and not kernels):
                    continue
                for other in neighbours(name) or ():
                    near = instructions.get(other)
                    if near is not None and near.phases is not None:
                        inst.phases, changed = near.phases, True
                        break

    # Consumers first, the kernels held back until what they feed is
    # known; then each kernel; then whatever is left, either way.
    spread(users.get, kernels=False)
    for name, inst in instructions.items():
        if inst.kernel and inst.phases is None:
            inst.phases = _kernel_phases(
                name, instructions, users, computations
            )
    spread(users.get, kernels=True)
    spread(lambda n: instructions[n].operands, kernels=True)


def _kernel_phases(name, instructions, users, computations):
    """A kernel's phases: those of an instruction it feeds, if one of
    its operands is in the same program phase (or none has any); else
    the deepest list among its operands, the later operand on a tie (a
    product's right-hand side). ``None`` while nothing it feeds is
    known (a kernel that feeds a kernel: it waits for that one). Where
    it feeds a fusion, the fused instruction that reads it speaks, not
    the fusion's root (the expert layer's last product is read by a
    mask traced under ``moe_experts``, fused into the scatter-add of
    ``moe_combine``). An operand the compiler made is read through to
    what feeds it."""

    def read_by(user):
        inst = instructions[user]
        if " fusion(" in inst.key:
            fused = computations.get(inst.calls[0], {})
            parameter = f" parameter({inst.operands.index(name)})"
            inside = {n for n, i in fused.items() if parameter in i.key}
            for reader in fused.values():
                if reader.phases and inside.intersection(reader.operands):
                    return reader.phases
        return inst.phases

    def fed_by(operand):
        near = instructions.get(operand)
        while near is not None and near.phases is None and not near.kernel:
            near = instructions.get(next(iter(near.operands), None))
        return near.phases if near is not None else None

    def program(phases):
        return tuple(p for p in phases if p in PHASES)

    feeds = [p for p in map(read_by, users.get(name, ())) if p is not None]
    fed = [p for p in map(fed_by, instructions[name].operands) if p]
    for phases in feeds:
        if not fed or program(phases) in map(program, fed):
            return phases
    return max(reversed(fed), key=len) if feeds else None
