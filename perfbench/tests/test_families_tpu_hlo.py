"""The operation families of ``perfbench/metrics/*.json`` against the TPU
compiler's own text: the cells' programs are compiled here for a
described v5e (no chip, nothing runs), at the published widths and a
tiny traffic size, and every instruction a trace could show is put to
the families.

Why: the trace of this installation carries no HLO category, so the
families tell operations by their instruction text, and the conv family
counts every ``kind=kOutput`` fusion as a matrix product. (Read by kind
names alone, the first chip run put most convolutions nowhere and
``conv_roofline`` read 138.7 %: PERF.md section 6, PR 23.) What has to
hold for that: every kOutput fusion has a convolution or dot inside, no
convolution or dot runs outside the family, and no instruction is in
two families. The same script at the cells' real sizes gave the same
answers and the instruction names of the chip's traces (PERF.md
section 5).
"""

import collections
import json
import os
import re

import jax
import pytest

from perfbench.harness import program, trace_reduce as tr
from perfbench.tests.helpers import tiny_cell

METRICS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "metrics"
)


def family(metric):
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return json.load(f)["args"]["family"]


FAMILIES = {
    "conv": family("conv_time_share"),
    "relayout": family("relayout_time_share"),
    "allreduce": family("allreduce_time_share"),
}
assert family("async_conv_time_share") == FAMILIES["conv"]
assert family("conv_roofline") == family("async_conv_roofline") == (
    FAMILIES["conv"]
)


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


def described(monkeypatch, topo, chips):
    """The program builds its mesh from ``jax.devices()``: hand it the
    described chips while it does."""
    real = jax.devices
    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **k: list(topo.devices)[:chips] if not a else real(*a, **k),
    )


def computations(text):
    comps, current = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = head.group(1)
            comps[current] = []
        elif current is not None and line.startswith("  "):
            comps[current].append(line.strip().removeprefix("ROOT "))
    return comps


def census(text):
    """Over every instruction outside a fused computation (what a trace
    can show): how many fall in each family, which fall in two, which
    kOutput fusions hold no matrix product, which matrix products the
    conv family misses."""
    comps = computations(text)
    called = lambda line: re.search(r"calls=%?([\w.\-]+)", line).group(1)
    fused = {called(l) for ls in comps.values() for l in ls
             if " fusion(" in l}
    product = lambda l: " convolution(" in l or " dot(" in l
    out = {"count": collections.Counter(), "two": [], "hollow": [],
           "missed": []}
    for name, lines in comps.items():
        if name in fused:
            continue
        for l in lines:
            event = tr.Event(name=l, start_ns=0, dur_ns=1)
            inside = [k for k, f in FAMILIES.items() if tr.in_family(event, f)]
            out["count"].update(inside)
            if len(inside) > 1:
                out["two"].append(l[:120])
            holds = product(l) or (
                " fusion(" in l and any(map(product, comps[called(l)]))
            )
            if "kind=kOutput" in l and not holds:
                out["hollow"].append(l[:120])
            if holds and "conv" not in inside:
                out["missed"].append(l[:120])
    return out


def check(text, allreduce):
    c = census(text)
    assert c["count"]["conv"] >= 3 and c["count"]["relayout"] > 0, c["count"]
    assert (c["count"]["allreduce"] > 0) == allreduce, c["count"]
    assert not c["two"] and not c["hollow"] and not c["missed"], c


def ppo_text(monkeypatch, topo, name, chips, **traffic):
    from jax.sharding import NamedSharding, PartitionSpec

    from actor_critic_algs_on_tensorflow_tpu.algos import common
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    cell = tiny_cell(name, chips=chips, compute_dtype="bfloat16", **traffic)
    described(monkeypatch, topo, chips)
    fns = make_ppo(program.build_config(cell, 0))
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


@pytest.mark.parametrize("name,chips,envs", [
    ("ppo-pong", 1, 16), ("ppo-breakout", 1, 32), ("ppo-pong-x4", 4, 64),
])
def test_ppo_iteration_families(monkeypatch, topo, name, chips, envs):
    text = ppo_text(monkeypatch, topo, name, chips, num_envs=envs)
    check(text, allreduce=chips > 1)


def test_impala_learner_and_actor_families(monkeypatch, topo):
    from jax.sharding import SingleDeviceSharding

    from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
        ActorTrajectory,
        make_impala,
    )

    cell = tiny_cell("impala-pong", envs_per_actor=16,
                     compute_dtype="bfloat16")
    described(monkeypatch, topo, 1)
    progs = make_impala(program.build_config(cell, 0))
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
    actor_args = on_chip((state.params, env_state, obs, carry, key))
    traj = next(
        x for x in jax.tree_util.tree_leaves(
            jax.eval_shape(rollout, *actor_args),
            is_leaf=lambda x: isinstance(x, ActorTrajectory),
        ) if isinstance(x, ActorTrajectory)
    )
    learner = progs.learner_step_donated.lower(
        on_chip(state), on_chip(traj)
    ).compile().as_text()
    check(learner, allreduce=False)
    check(rollout.lower(*actor_args).compile().as_text(), allreduce=False)
