"""BENCHMARK.json against the contract's limits, and every file a cell
names: they load, and they agree with one another."""

import json
import os
import re

import pytest

from perfbench.harness import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head|expansion|experts_per")


def test_top_level_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert BENCH["command"][:2] == ["python3", "perfbench/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200
    size = os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_text_limits():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\t" not in e["why"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200
        assert c["file"].startswith("perfbench/")
        assert not any(WIDTH.search(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_cells_and_chips():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == ["ppo-pong-x4"]          # exactly one asks for four
    assert len(four) <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("name", spec.cell_names())
def test_cell_files_load_and_cross_reference(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["name"] == cell.traffic_name
    assert cell.config["family"] in ("ppo", "impala")
    assert os.path.exists(os.path.join(
        spec.BENCH_DIR, "runners", cell.family + ".py"
    ))
    for key in ("source", "assumed", "reduced", "preset", "program",
                "model", "stands_for"):
        assert key in cell.config, key
    assert cell.config["reduced"] == []
    assert len(cell.traffic["who"]) > 20 and "\n" not in cell.traffic["who"]
    assert cell.traffic["program"]["total_env_steps"] >= 10**9
    names = [m.name for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m.moves in names
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "rules", m.rule + ".py"
        )), m.rule


def test_a_metric_that_lists_its_cells_is_reported_by_those_only():
    by_cell = {
        n: {m.name for m in spec.load_cell(n).end_to_end
            + spec.load_cell(n).per_layer}
        for n in spec.cell_names()
    }
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        listed = m.get("workloads", list(by_cell))
        for cell, reported in by_cell.items():
            assert (m["name"] in reported) == (cell in listed), (m, cell)
    ppo = ["ppo-pong", "ppo-breakout", "ppo-pong-x4"]
    hbm = next(m for m in BENCH["end_to_end"] if m["name"] == "peak_hbm_gib")
    assert hbm["workloads"] == ppo
    assert "peak_hbm_gib" not in by_cell["impala-pong"]
    assert "async_peak_hbm_gib" in by_cell["impala-pong"]
    assert by_cell["ppo-pong-x4"] - by_cell["ppo-pong"] == {
        "allreduce_time_share"
    }


def test_a_cell_that_names_a_missing_file_is_refused(tmp_path):
    root = tmp_path
    (root / "perfbench" / "traffic").mkdir(parents=True)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["traffic"] = "no-such-traffic"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in bench["configs"]:
        dst = root / c["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(open(os.path.join(spec.ROOT, c["file"])).read())
    with pytest.raises(spec.SpecError, match="missing file"):
        spec.load_cell(bench["workloads"][0]["name"], str(root))
