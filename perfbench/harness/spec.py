"""Reads ``BENCHMARK.json`` and the data files it names.

A cell is found by name alone: its configuration file is the ``file``
of its ``configs`` entry, its traffic file is
``perfbench/traffic/<traffic>.json``, a per-layer metric's declaration
is ``perfbench/metrics/<name>.json``. Nothing here knows a cell, a
configuration or a metric by name, so a later PR adds one with files
and entries only.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A data file is missing, malformed or disagrees with another."""


def _load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    except json.JSONDecodeError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}")


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str | None = None
    moves: str | None = None
    rule: str | None = None
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def family(self) -> str:
        return self.config["family"]


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _metric(entry: Dict[str, Any], root: str, per_layer: bool) -> Metric:
    rule, args = None, {}
    if per_layer:
        decl = _load_json(
            os.path.join(root, "perfbench", "metrics", entry["name"] + ".json")
        )
        if decl.get("name") != entry["name"]:
            raise SpecError(
                f"metrics/{entry['name']}.json declares {decl.get('name')!r}"
            )
        rule, args = decl["rule"], decl.get("args", {})
    return Metric(
        name=entry["name"], unit=entry["unit"], better=entry["better"],
        source=entry["source"],
        layer=entry.get("layer"), moves=entry.get("moves"),
        rule=rule, args=args,
    )


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its files loaded and cross-checked."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(
            f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})"
        )
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    if config.get("name") != w["config"]:
        raise SpecError(
            f"{configs[w['config']]['file']} declares {config.get('name')!r}"
        )
    for key in ("family", "config_class", "preset", "program"):
        if key not in config:
            raise SpecError(f"{configs[w['config']]['file']} lacks {key!r}")
    if config.get("source") != configs[w["config"]]["source"]:
        raise SpecError(
            f"config {w['config']!r}: its file and BENCHMARK.json give "
            f"different sources"
        )
    if sorted(config.get("reduced", [])) != sorted(
        configs[w["config"]]["reduced"]
    ):
        raise SpecError(
            f"config {w['config']!r}: its file and BENCHMARK.json list "
            f"different `reduced` keys"
        )
    traffic = _load_json(
        os.path.join(root, "perfbench", "traffic", w["traffic"] + ".json")
    )
    if traffic.get("name") != w["traffic"]:
        raise SpecError(
            f"traffic/{w['traffic']}.json declares {traffic.get('name')!r}"
        )
    for key in ("who", "program", "expect", "assumed"):
        if key not in traffic:
            raise SpecError(f"traffic/{w['traffic']}.json lacks {key!r}")
    def reported_here(entries):
        return [e for e in entries if name in e.get("workloads", [name])]

    end_to_end = [
        _metric(e, root, False) for e in reported_here(bench["end_to_end"])
    ]
    reported = {m.name for m in end_to_end}
    per_layer = [
        _metric(e, root, True) for e in reported_here(bench["per_layer"])
    ]
    for m in per_layer:
        # The contract: a per-layer metric is reported only where the
        # end-to-end metric it moves is.
        if m.moves not in reported:
            raise SpecError(
                f"per-layer metric {m.name!r} moves {m.moves!r}, which "
                f"cell {name!r} does not report"
            )
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config, traffic=traffic,
        end_to_end=end_to_end, per_layer=per_layer,
    )


def cell_names(root: str = ROOT) -> List[str]:
    return [w["name"] for w in load_benchmark(root)["workloads"]]
