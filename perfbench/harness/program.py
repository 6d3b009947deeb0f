"""The program's config for a cell: the preset the configuration file
stands for, with the traffic file's keys over it."""

from __future__ import annotations

import dataclasses
import importlib

from perfbench.harness.spec import Cell, SpecError


def build_config(cell: Cell, seed: int):
    """The configuration file's ``config_class`` (``module:Class``,
    the program's own dataclass) built from
    ``cli/train.py::PRESETS[<preset>]`` with the traffic file's
    ``program`` keys, the seed and the chips over it. Every key the
    configuration file states must hold in the result, so neither a
    traffic file nor a later change of the preset can alter the
    configuration unseen; every key the traffic file does not name is
    the preset's."""
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    module, _, cls_name = cell.config["config_class"].partition(":")
    cls = getattr(importlib.import_module(module), cls_name)
    _, base = PRESETS[cell.config["preset"]]
    overrides = dict(cell.traffic["program"])
    overrides.update(seed=seed, num_devices=cell.chips)
    try:
        cfg = dataclasses.replace(cls(**base), **overrides)
    except TypeError as e:
        raise SpecError(
            f"cell {cell.name!r}: preset {cell.config['preset']!r} and "
            f"the traffic file's keys do not make a {cls_name}: {e}"
        )
    for key, stated in cell.config["program"].items():
        if getattr(cfg, key) != stated:
            raise SpecError(
                f"cell {cell.name!r}: the configuration states "
                f"{key}={stated!r}, the program would run "
                f"{getattr(cfg, key)!r}"
            )
    return cfg
