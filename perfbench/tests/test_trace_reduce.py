"""The reducer on a small recorded trace whose numbers are known by
hand (perfbench/tests/data/recorded_trace.csv): the names are those a
TPU trace of this installation carries (whole HLO instructions, from
the PR 23 chip runs), the times are set so that every number can be
worked out: two chips, a ``while`` that contains three operations, one
2 us idle gap on each chip while the host was in
``np.asarray(jax.Array)``."""

import json

import os

import pytest

from perfbench.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


def family(metric):
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return json.load(f)["args"]["family"]


CONV, RELAYOUT, ALLREDUCE = (
    family("conv_time_share"), family("relayout_time_share"),
    family("allreduce_time_share"),
)
WHILE = "while.18 while -> (s32[], f32[1024])"
CONV_ROLLOUT = "fusion.471 fusion/kOutput -> bf16[1024,9,9,64]"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(
        tr.load_csv(os.path.join(HERE, "data", "recorded_trace.csv"))
    )


def test_busy_idle_and_window(reduced):
    assert reduced.chips == 2
    assert reduced.window_s == pytest.approx(19e-6)      # 1 us .. 20 us
    # chip 0: [1,10] and [12,20] us = 17 us; chip 1: the same 17 us
    assert reduced.busy_s == pytest.approx(17e-6)
    assert reduced.idle_share == pytest.approx(2 / 19)


def test_self_times_do_not_count_a_while_twice(reduced):
    # while.1 lasts 6 us and contains 2 + 1 + 1.5 us of operations
    assert reduced.op_self_s[WHILE] == pytest.approx(1.5e-6 / 2)
    # fusion.471: chip 0 2 + 4 us, chip 1 9 + 8 us, averaged
    assert reduced.op_self_s[CONV_ROLLOUT] == pytest.approx(23e-6 / 2)
    assert reduced.top_ops(1)[0][0] == CONV_ROLLOUT
    assert sum(reduced.op_self_s.values()) == pytest.approx(reduced.busy_s)


def test_families_as_the_metric_files_declare_them(reduced):
    # conv: the two kOutput fusions, 23 + 1.5 us
    assert reduced.family_self_s(CONV) == pytest.approx(24.5e-6 / 2)
    # relayout: the copy (1 us) + the select fusion into a frame buffer (3)
    assert reduced.family_self_s(RELAYOUT) == pytest.approx(4e-6 / 2)
    assert reduced.family_self_s(ALLREDUCE) == pytest.approx(4e-6 / 2)
    plain = tr.Event("%add.3 = f32[1024]{0} add(f32[1024]{0} %a, f32[1024]{0} %b)",
                     0, 1)
    assert not any(tr.in_family(plain, f) for f in (CONV, RELAYOUT, ALLREDUCE))
    by_category = tr.Event("%fusion.1 = f32[8]{0} fusion(...)", 0, 1,
                           "convolution fusion")
    assert tr.in_family(by_category, CONV)


def test_programs_and_gaps(reduced):
    name = "jit_local_iteration(2216987709410141469)"
    assert sorted(reduced.modules[name]) == pytest.approx(
        [8e-6, 9e-6, 9e-6]
    )
    gaps = dict(map(tuple, reduced.idle_gaps))
    assert gaps == {"np.asarray(jax.Array)": pytest.approx(2e-6)}


def test_no_device_operation_reduces_to_nothing():
    assert tr.reduce_trace(tr.Trace(devices=[], host=[])) is None


def test_work_is_counted_from_the_traces_own_executions(reduced):
    from perfbench.harness import driver

    per_execution = {"^jit_local_iteration": {"train_samples": 100,
                                              "train_calls": 2}}
    # three executions over two chips: 1.5 a chip
    assert driver._traced_work(per_execution, reduced) == {
        "train_samples": 150.0, "train_calls": 3.0,
    }
    assert driver._traced_work(per_execution, None) is None


def test_collectives_as_the_v5e_compiler_names_them():
    """Instruction texts from compiling the four-chip PPO iteration for
    a described v5e:2x2 (PR 23): the gradient all-reduce, a scalar psum
    lowered to an all-reduce, and a read of the all-reduce's result,
    which is not a collective."""
    grads = tr.Event(
        "%all-reduce.144 = (f32[6]{0:T(128)S(1)}, f32[512,6]{0,1:T(8,128)"
        "S(1)}) all-reduce(%fusion.1, %fusion.2), channel_id=2, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_16.22", 0, 1)
    psum = tr.Event(
        "%psum.142 = f32[]{:T(128)} all-reduce(%div.1569), channel_id=1, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_16.22", 0, 1)
    read = tr.Event(
        "%get-tuple-element.4157 = f32[1]{0:T(128)} get-tuple-element("
        "%all-reduce.144), index=2", 0, 1)
    assert tr.in_family(grads, ALLREDUCE) and tr.in_family(psum, ALLREDUCE)
    assert not tr.in_family(read, ALLREDUCE)
    assert tr.label(psum.name) == "psum.142 all-reduce -> f32[]"


def test_families_are_disjoint_on_the_chips_own_instructions():
    """``data/chip_instructions.txt``: every distinct instruction on the
    ``XLA Ops`` line at the head of the three one-chip cells' traces
    (chip runs, PR 23; `ppo-*` heads reach into the rollout only,
    `impala-pong`'s holds both of its programs whole). None is in two
    families; every ``kind=kOutput`` fusion is in the conv family, and
    `impala-pong` shows 22 of them, the 18 + 4 that its learner step and
    actor rollout hold when compiled for a described v5e, each with a
    convolution inside (``test_families_tpu_hlo.py``)."""
    families = {"conv": CONV, "relayout": RELAYOUT, "allreduce": ALLREDUCE}
    k_output = {}
    with open(os.path.join(HERE, "data", "chip_instructions.txt")) as f:
        for line in f:
            cell, text = line.rstrip("\n").split("\t")
            inside = [k for k, fam in families.items()
                      if tr.in_family(tr.Event(text, 0, 1), fam)]
            assert len(inside) <= 1, (inside, text[:120])
            if "kind=kOutput" in text:
                assert inside == ["conv"], text[:120]
                k_output[cell] = k_output.get(cell, 0) + 1
    assert k_output == {"ppo-pong": 4, "ppo-breakout": 4, "impala-pong": 22}
