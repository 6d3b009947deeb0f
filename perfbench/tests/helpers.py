"""Tiny cells for the CPU: a real cell's files with the widths of the
TRAFFIC cut (envs, rollout), never the model's."""

import copy
import contextlib
import dataclasses

from perfbench.harness import spec


def tiny_cell(name: str, chips: int | None = None, **program):
    cell = spec.load_cell(name)
    config = copy.deepcopy(cell.config)
    traffic = copy.deepcopy(cell.traffic)
    rollout = program.pop("rollout_length", 8)
    # float32 throughout by default: at 32 samples bfloat16's rounding
    # does not average out as it does over a real batch, and the point
    # here is the arithmetic, which then has to agree tightly.
    dtype = program.pop("compute_dtype", "float32")
    config["program"].update(rollout_length=rollout, compute_dtype=dtype)
    traffic["program"].update(
        program, rollout_length=rollout, compute_dtype=dtype
    )
    chips = chips or cell.chips
    if cell.family == "ppo":
        traffic["expect"]["env_steps_per_iteration"] = (
            traffic["program"]["num_envs"] * rollout
        )
        config["reference_check"].update(rollout=rollout, envs=4)
    else:
        traffic["expect"]["env_steps_per_learner_batch"] = (
            traffic["program"]["envs_per_actor"] * rollout
        )
        config["reference_check"]["env_chunks"] = 2
        traffic.update(log_interval=2, warmup_learner_steps=4)
    return dataclasses.replace(
        cell, config=config, traffic=traffic, chips=chips
    )


@contextlib.contextmanager
def no_span(name):
    yield


def nothing():
    pass
