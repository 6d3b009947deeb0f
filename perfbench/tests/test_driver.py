"""The driver's flow on the CPU: the command refuses to run off the
chip; with the device gate stood in for (in the test, not by a switch
of the command) a tiny cell runs end to end and prints the contract's
one JSON object, holding the metrics the cell declares and no other."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import device as device_lib, driver, peaks, spec

from perfbench.tests.helpers import tiny_cell

ROOT = spec.ROOT


def test_command_off_the_chip_prints_no_result_and_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "ppo-pong",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "platform=cpu" in p.stderr
    assert not any(
        line.startswith("{") for line in p.stdout.splitlines()
    ), p.stdout


def test_unknown_workload_fails_before_jax():
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "nope",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert p.returncode == 6 and "no workload 'nope'" in p.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.fixture
def on_fake_chip(monkeypatch, tmp_path):
    """Stand in for the device gate and send outputs to a temp dir."""
    def fake(chips):
        return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}

    monkeypatch.setattr(device_lib, "require_chips", fake)
    monkeypatch.setattr(driver, "OUT_DIR", str(tmp_path))
    return tmp_path


def _run(capsys, monkeypatch, name, trace, **program):
    cell = tiny_cell(name, chips=1, **program)
    monkeypatch.setattr(spec, "load_cell", lambda n: cell)
    rc = driver.run(name, seed=2, seconds=1.5, trace=trace, t_process0=0.0)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    result["_log"] = "\n".join(out[:-1])
    return cell, result


@pytest.mark.parametrize("name,program", [
    ("ppo-breakout", dict(num_envs=16)),
    ("impala-pong", dict(envs_per_actor=4)),
])
def test_result_line_holds_the_cells_own_end_to_end_metrics(
    on_fake_chip, capsys, monkeypatch, name, program
):
    cell, result = _run(capsys, monkeypatch, name, False, **program)
    log = result.pop("_log")
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0, log
    assert result["attempted"] >= 2
    declared = {m.name for m in cell.end_to_end}
    assert set(result["metrics"]) == declared
    # peak_hbm_gib lists the PPO cells and is reported by no other.
    assert ("peak_hbm_gib" in declared) == (cell.family == "ppo")
    assert result["metrics"]["setup_s"]["unit"] == "s"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_setup_leaves_out_the_runtimes_own_start(
    on_fake_chip, capsys, monkeypatch
):
    """`setup_s` runs from the process's start to the window's opening
    without the seconds `jax.devices()` took: the runtime's start is
    timed apart (`backend_init_s` in the run file)."""
    import time

    def slow_gate(chips):
        time.sleep(0.6)
        return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}

    monkeypatch.setattr(device_lib, "require_chips", slow_gate)
    cell = tiny_cell("ppo-pong", chips=1, num_envs=8)
    monkeypatch.setattr(spec, "load_cell", lambda n: cell)
    t0 = time.perf_counter()
    assert driver.run("ppo-pong", 1, 0.0, False, t0) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    run = json.load(open(on_fake_chip / "ppo-pong" / "run_trace0.json"))
    assert 0.6 <= run["backend_init_s"] < 0.7
    phases = run["setup_phases_s"]
    assert list(phases) == ["imported", "backend_up", "runner_set_up",
                            "window_opens"]
    assert result["metrics"]["setup_s"]["value"] == pytest.approx(
        phases["window_opens"] - run["backend_init_s"]
    )


def test_traced_run_reports_only_what_its_readers_found(
    on_fake_chip, capsys, monkeypatch
):
    """A CPU trace has no device plane: the readers of the device trace
    find nothing and return nothing, the others report, and a run in
    which no operation ran on a device is not `correct`."""
    cell, result = _run(capsys, monkeypatch, "impala-pong", True,
                        envs_per_actor=4)
    declared = {m.name for m in cell.per_layer}
    assert set(result["metrics"]) <= declared
    assert {"compile_s", "trace_lower_s", "learner_stall_share",
            "async_pause_share"} <= set(result["metrics"])
    assert "async_device_idle_share" not in result["metrics"]
    assert "busy_s" not in result["device"]
    assert result["correct"] is False


def test_traced_ppo_run_reports_its_pause_share(
    on_fake_chip, capsys, monkeypatch
):
    """The family whose window is whole iterations hands the rule the
    iterations' ends as its rows; the async cell's name for the same
    reading is not reported here."""
    cell, result = _run(capsys, monkeypatch, "ppo-pong", True, num_envs=8)
    assert {"compile_s", "trace_lower_s", "pause_share"} <= set(
        result["metrics"]
    )
    assert "async_pause_share" not in result["metrics"]
    assert result["metrics"]["pause_share"]["unit"] == "%"


class _MlpRunner:
    """A family the harness has never heard of, with a model that is
    not a Nature-CNN: no `model` group, no `operations`, no
    `num_actions`. It multiplies a matrix and counts."""

    def __init__(self, cell, seed):
        self.cell, self.seed, self.report = cell, seed, {}

    def setup(self):
        import jax
        import jax.numpy as jnp

        self.step = jax.jit(lambda x: jnp.tanh(x @ x))
        self.x = self.step(jnp.eye(8) * (1 + self.seed))
        return {"placement": True}

    def measure(self, seconds, on_start, on_stop, span):
        import jax

        on_start()
        for _ in range(3):
            with span("perfbench:dispatch"):
                self.x = jax.block_until_ready(self.step(self.x))
        on_stop()
        return {"attempted": 3, "failed": 0,
                "end_to_end": {"async_env_steps_per_s_per_chip": 1.0},
                "checks": {"counted": True},
                "work_per_execution": {"^jit_": {"forward_samples": 8}}}

    def verify(self):
        return {"reference_mlp": True}

    def close(self):
        pass


def test_a_configuration_that_is_no_nature_cnn_runs_traced(
    on_fake_chip, capsys, monkeypatch
):
    """A configuration file without `model.conv` and without
    `operations`, under a family that is neither `ppo` nor `impala`,
    goes through the traced path of `driver.run`: the FLOP and roofline
    readers find no layers and return nothing, the others report."""
    import dataclasses

    cell = spec.load_cell("impala-pong")
    config = {k: v for k, v in cell.config.items()
              if k not in ("model", "operations")}
    config["family"] = "mlp"
    cell = dataclasses.replace(cell, config=config)
    monkeypatch.setattr(spec, "load_cell", lambda n: cell)
    monkeypatch.setattr(driver, "load_runner", lambda family: _MlpRunner)
    assert driver.run("impala-pong", 1, 1.0, True, 0.0) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "compile_s" in result["metrics"]
    assert not {"async_model_flops_util", "async_conv_roofline"} & set(
        result["metrics"]
    )


def test_operations_function_is_found_by_the_configurations_key():
    import types

    cell = spec.load_cell("ppo-pong")
    ctx = types.SimpleNamespace(
        cell=cell, runner=types.SimpleNamespace(num_actions=6)
    )
    layers = driver.Context.layers.func(ctx)
    assert [l.name for l in layers] == [
        "conv0", "conv1", "conv2", "dense", "heads"
    ]
    import dataclasses

    bad = dataclasses.replace(
        cell, config=dict(cell.config, operations="no_such_model")
    )
    ctx.cell = bad
    with pytest.raises(spec.SpecError, match="operations/no_such_model"):
        driver.Context.layers.func(ctx)


def test_config_class_comes_from_the_configuration_file():
    """No family is known by name: the program's config class is the
    one the file names, and a file that names none is refused."""
    import dataclasses

    from perfbench.harness import program

    cell = spec.load_cell("impala-pong")
    assert type(program.build_config(cell, 0)).__name__ == "ImpalaConfig"
    other = dataclasses.replace(cell, config=dict(
        cell.config, family="a-third-family",
    ))
    assert type(program.build_config(other, 0)).__name__ == "ImpalaConfig"
    wrong = dataclasses.replace(cell, config=dict(
        cell.config,
        config_class="actor_critic_algs_on_tensorflow_tpu.algos.ppo:PPOConfig",
    ))
    with pytest.raises(spec.SpecError, match="do not make a PPOConfig"):
        program.build_config(wrong, 0)


def test_benchmark_files_alone_print_no_result_and_fail(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under `paths`, the command has nothing to measure."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "ppo-pong",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
    )
    assert p.returncode == 4 and "not in this checkout" in p.stderr
    assert p.stdout.strip() == ""
