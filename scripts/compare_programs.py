"""Whether two trees of this repository build the same sequence-core
programs: the same initialised parameters and the same lowered PPO
iteration, preset by preset.

For each preset, in one process per tree (the tree's own package
imported), it records:

* the parameter tree's paths, shapes and dtypes (``jax.eval_shape`` of
  the trainer's ``init``), and where ``--values`` names the preset the
  sha256 of every leaf's bytes, initialised from ``PRNGKey(0)``;
* the sha256 of ``iteration.lower(state).as_text(debug_info=False)``,
  the serialised Pallas kernels in it stripped of their source
  locations, which name the tree's files;
* the sha256 of the name stacks of the lowered operations, in order
  (the named part of each location, without file or line): the scopes
  the benchmark's trace readers join on.

It prints one line a preset, ``<preset> equal`` or ``<preset>
differing: <what>``, and exits 1 if any differs. Lowering compiles
nothing; on a TPU host the text holds the TPU's program with its
kernels.

    python scripts/compare_programs.py <other-tree>    # the tiny presets
    python scripts/compare_programs.py <other-tree> --presets ppo-sdar-turns
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ("ppo-qwen3next-tiny", "ppo-kimivl-tiny", "ppo-sdar-tiny",
        "ppo-granite-tiny", "ppo-xing-tiny", "ppo-lfm2-tiny")
# A Mosaic kernel's serialised module; in MLIR text with debug
# information, a named location's definition and an operation's
# reference to its location.
_KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')
_LOC_NAME = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)
_OP_LOC = re.compile(r'loc\((#loc\d+)\)\s*$', re.M)


def _sha(data) -> str:
    return hashlib.sha256(
        data if isinstance(data, bytes) else data.encode()
    ).hexdigest()[:16]


def _kernel_without_locations(match) -> str:
    """A kernel's module re-serialised with no debug information."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    with ir.Context() as ctx, ir.Location.unknown():
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(2)))
        text = module.operation.get_asm(enable_debug_info=False)
    return match.group(1) + _sha(text) + match.group(3)


def _scopes(text):
    """The name stack of every operation in ``text``, in order."""
    names = dict(_LOC_NAME.findall(text))
    return [names.get(ref, "") for ref in _OP_LOC.findall(text)]


def summarise(presets, values):
    """One JSON line a preset, of the tree this process imports."""
    import jax
    import numpy as np

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )
    from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS

    for name in presets:
        fns = make_ppo(PPOConfig(**PRESETS[name][1]))
        key = jax.random.PRNGKey(0)
        state = jax.eval_shape(fns.init, key)
        leaves = jax.tree_util.tree_leaves_with_path(state.params)
        row = {
            "preset": name,
            "params": _sha(json.dumps([
                (jax.tree_util.keystr(path), leaf.shape, str(leaf.dtype))
                for path, leaf in leaves
            ])),
            "param_count": sum(int(np.prod(leaf.shape)) for _, leaf in leaves),
        }
        if name in values:
            params = jax.device_get(fns.init(key).params)
            row["values"] = _sha(b"".join(
                np.ascontiguousarray(leaf).tobytes()
                for leaf in jax.tree_util.tree_leaves(params)
            ))
        lowered = fns.iteration.lower(state)
        text = _KERNEL_BODY.sub(_kernel_without_locations,
                                lowered.as_text(debug_info=False))
        row["program"] = _sha(text)
        row["kernels"] = text.count("tpu_custom_call")
        row["scopes"] = _sha("\n".join(_scopes(
            lowered.as_text(debug_info=True)
        )))
        row["platform"] = jax.default_backend()
        print(json.dumps(row), flush=True)


def _run(tree, presets, values):
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), tree, "--summarise",
         "--presets", *presets, "--values", *values],
        capture_output=True, text=True, cwd=tree, check=False,
    )
    if out.returncode:
        sys.exit(f"{tree}: exit {out.returncode}\n{out.stderr[-4000:]}")
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    return {row["preset"]: row for row in rows}


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("other", help="the other tree, e.g. a `git archive` "
                   "of the parent commit")
    p.add_argument("--presets", nargs="+", default=list(TINY))
    p.add_argument("--values", nargs="*", default=list(TINY),
                   help="presets whose initialised values are hashed too")
    p.add_argument("--summarise", action="store_true",
                   help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.summarise:
        sys.path.insert(0, os.getcwd())
        summarise(args.presets, set(args.values))
        return
    values = [v for v in args.values if v in args.presets]
    theirs = _run(os.path.abspath(args.other), args.presets, values)
    ours = _run(ROOT, args.presets, values)
    differing = 0
    for name in args.presets:
        a, b = theirs[name], ours[name]
        moved = [k for k in a if a[k] != b.get(k)]
        differing += bool(moved)
        detail = " ".join(f"{k}={b[k]}" for k in
                          ("platform", "param_count", "kernels", "program"))
        verdict = "differing: " + ", ".join(moved) if moved else "equal"
        print(f"{name} {verdict} ({detail})", flush=True)
    sys.exit(1 if differing else 0)


if __name__ == "__main__":
    main()
