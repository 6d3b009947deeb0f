"""One run of one cell: set-up, the measured window, the result line.

The runner of the cell's family (``perfbench/runners/<family>.py``)
builds the program and runs the window; everything that makes a number
out of it is here or in the modules beside this one.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import importlib
import json
import os
import re
import shutil
import sys
import time
from typing import Any, Dict

from perfbench.harness import (
    compile_events,
    device as device_lib,
    peaks as peaks_lib,
    spec,
    trace_reduce,
)

OUT_DIR = os.path.join(spec.ROOT, "perfbench_out")


def _load(registry: str, name: str, attr: str):
    """The registries are directories: ``perfbench/<registry>/<name>.py``
    holding ``attr``. A new runner, rule or operations function is a
    new file."""
    path = os.path.join(spec.BENCH_DIR, registry, name + ".py")
    if not os.path.exists(path):
        raise spec.SpecError(f"no perfbench/{registry}/{name}.py")
    return getattr(
        importlib.import_module(f"perfbench.{registry}.{name}"), attr
    )


def load_runner(family: str):
    return _load("runners", family, "Runner")


def load_rule(rule: str):
    return _load("rules", rule, "read")


class Context:
    """What a per-layer reader may read."""

    def __init__(self, cell, runner, device, window, reduced,
                 setup_compile, memory_peak_bytes):
        self.cell = cell
        self.runner = runner
        self.device = device
        self.reduced = reduced
        self.setup_compile = setup_compile
        self.memory_peak_bytes = memory_peak_bytes
        self.peaks = peaks_lib.peaks_for(device["kind"])
        self.work_per_chip = _traced_work(
            window.get("work_per_execution"), reduced
        )
        self.log_rows = window.get("log_rows", [])
        self.log_window_s = window.get("log_window_s", 0.0)
        self.row_times_s = window.get("row_times_s", [])
        self.notes: Dict[str, Any] = {}

    @functools.cached_property
    def layers(self):
        """The model's matrix products, by the operations function the
        configuration file names; None where it names none, and the
        readers that need them then have nothing to read."""
        name = self.cell.config.get("operations")
        if name is None:
            return None
        return _load("operations", name, "layers")(
            self.cell.config, self.runner
        )


def _traced_work(per_execution, reduced):
    """The work one chip did in the traced window: each program's work
    an execution (from the runner, by the program's name) times the
    executions the trace holds of it, so that work and device time come
    from the same window."""
    if not per_execution or reduced is None:
        return None
    total: Dict[str, float] = {}
    for pattern, work in per_execution.items():
        runs = sum(
            len(durs) for name, durs in reduced.modules.items()
            if re.search(pattern, name)
        ) / reduced.chips
        for key, value in work.items():
            total[key] = total.get(key, 0.0) + runs * value
    return total


def per_layer_metrics(cell, ctx) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = load_rule(m.rule)(ctx, **m.args)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_process0: float) -> int:
    # Where the time before the window goes, in seconds since the
    # process began, for whoever has to shorten or steady it
    # (run_trace<n>.json).
    phases: Dict[str, float] = {}

    def phase_done(name: str) -> None:
        phases[name] = time.perf_counter() - t_process0

    cell = spec.load_cell(cell_name)
    Runner = load_runner(cell.family)

    import jax

    from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache

    compile_cache.enable()
    log = compile_events.CompileLog().install()
    phase_done("imported")
    try:
        device = device_lib.require_chips(cell.chips)
    except device_lib.NoChip as e:
        print(f"[perfbench] FAIL: {e}", file=sys.stderr, flush=True)
        return 5
    phase_done("backend_up")
    # The accelerator runtime's own start (`jax.devices()`: libtpu and
    # the chip's reset) is timed apart and is NOT in `setup_s`. It is
    # none of this repo's work, no change to the repo can move it, and
    # it is where the whole of set-up's unsteadiness sits: 8.2 to 12.7 s
    # in seven runs of one call, later runs slower, while every other
    # phase held to +-0.2 s (PERF.md section 2; my chip runs, PR 23).
    # Left in, two sets of runs of the same code differ by more than
    # the 10 % that `setup_s` may have at most.
    backend_init_s = phases["backend_up"] - phases["imported"]
    peaks_lib.peaks_for(device["kind"])  # an unknown device is an error
    say(f"cell {cell.name}: {cell.config_name} x {cell.traffic_name} on "
        f"{device['count']} x {device['kind']}; seed {seed}")

    runner = Runner(cell, seed)
    checks = dict(runner.setup())
    phase_done("runner_set_up")
    out_dir = os.path.join(OUT_DIR, cell.name)
    trace_dir = os.path.join(out_dir, "trace")
    marks: Dict[str, Any] = {}

    def on_start():
        # Set-up ends here: the next thing the runner does is the
        # first timed dispatch.
        phase_done("window_opens")
        marks["setup_s"] = phases["window_opens"] - backend_init_s
        marks["setup_compile"] = log.mark()
        marks["window"] = log.mark()
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host TraceMe spans only
            jax.profiler.start_trace(trace_dir, profiler_options=options)

    def on_stop():
        marks["in_window"] = log.since(marks["window"])
        if trace:
            jax.profiler.stop_trace()

    length = float(cell.traffic.get("trace_seconds", 3)) if trace else seconds
    span = jax.profiler.TraceAnnotation if trace else _no_span
    window = runner.measure(min(length, seconds), on_start, on_stop, span)
    # The peak is read before the reference check runs, so that it is
    # the program's own: the check's seeded batch and float32 passes
    # would lift the high-water mark of live arrays and hide a change
    # of the program's under it.
    memory_peak = device_lib.memory_peak_bytes()
    checks.update(runner.verify())  # named by what each one guards
    runner.close()

    # Nothing may be lowered or compiled (or read from the compile
    # cache) inside the window; a bare jaxpr trace of microseconds, as
    # a host-side helper's first call makes, is not a compilation.
    compiled = sum(
        marks["in_window"]["counts"].get(e, 0)
        for e in (compile_events.BACKEND_EVENT,
                  compile_events.TRACE_LOWER_EVENTS[1])
    )
    checks["no_compile_in_window"] = compiled == 0
    checks.update(window.get("checks", {}))
    device["memory_peak_bytes"] = memory_peak

    result: Dict[str, Any] = {
        "correct": all(checks.values()),
        "attempted": int(window["attempted"]),
        "failed": int(window["failed"]),
    }
    if trace:
        reduced = _reduce(trace_dir, out_dir)
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
        ctx = Context(cell, runner, device, window, reduced,
                      marks["setup_compile"], memory_peak)
        result["metrics"] = per_layer_metrics(cell, ctx)
        if reduced is not None:
            # (keywords, not a dict literal: the repo's metric-name
            # gate reads `device_*` literals as run-loop log keys.)
            result["breakdown"] = dict(
                device_ops=reduced.top_ops(10), idle_gaps=reduced.idle_gaps,
            )
        checks["ran_on_device"] = reduced is not None and reduced.busy_s > 0
        result["correct"] = all(checks.values())
        notes = ctx.notes
    else:
        values = dict(window["end_to_end"])
        values["setup_s"] = marks["setup_s"]
        values["peak_hbm_gib"] = memory_peak / 2**30
        result["metrics"] = {
            m.name: {"value": float(values[m.name]), "unit": m.unit}
            for m in cell.end_to_end
        }
        notes = {}
    result["device"] = device
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"run_trace{int(trace)}.json"), "w") as f:
        json.dump({"result": result, "checks": checks, "notes": notes,
                   "reference_report": runner.report,
                   "window": {k: v for k, v in window.items()
                              if k not in ("log_rows",)},
                   "setup_phases_s": phases,
                   "backend_init_s": backend_init_s,
                   "setup_compile_seconds":
                       dict(marks["setup_compile"]["seconds"])},
                  f, indent=1, default=str)
    say(f"before the window, seconds since the process began: "
        f"{json.dumps(phases)}; backend_init_s {backend_init_s:.3f} of "
        f"them is the runtime's start and not in setup_s")
    say(f"checks: {json.dumps(checks, default=str)}")
    say(f"reference: {json.dumps(runner.report, default=str)}")
    for failed in (k for k, v in checks.items() if not v):
        say(f"CHECK FAILED: {failed}")
    print(json.dumps(result), flush=True)
    return 0


def _reduce(trace_dir: str, out_dir: str):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        return None
    trace = trace_reduce.load_xplane(files[-1])
    with open(os.path.join(out_dir, "trace_described.json"), "w") as f:
        json.dump(trace_reduce.describe(trace), f, indent=1)
    shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB; read once
    return trace_reduce.reduce_trace(trace)


@contextlib.contextmanager
def _no_span(name: str):
    yield


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)
