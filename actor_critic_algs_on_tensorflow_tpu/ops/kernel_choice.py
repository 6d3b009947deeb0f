"""Where a model chooses between a Pallas kernel and its plain form.

A core hands ``kernel_or_plain`` the name of a Pallas module under
``ops/``, a test of whether the operands fit that module's tiling
(``fits``), the kernel (``kernel(ops)`` gives the function a TPU runs on
the operands) and the plain form. The kernel runs where the program is
lowered for a TPU (``jax.lax.platform_dependent``) and the operands
fit; the plain form runs anywhere else. Nothing else chooses:
no flag, no question to the backend. ``kernel_ran`` is the same choice
as the float32 scalar 1 (the kernel ran) or 0, and the models' path
counters are made from it; a counter whose kernel side has a value of
its own is ``kernel_or_plain`` of two scalar functions.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp


def _on_tpu(kernel, plain, *operands):
    return jax.lax.platform_dependent(*operands, tpu=kernel, default=plain)


def _lowered_for(name: str):
    """``(ops, choose)``: the Pallas module ``ops/<name>.py``, imported
    here where it is first used (Pallas is ~1.5 s of imports, and
    ``cli/train.py``'s PRESETS import every core), and the choice by
    the platform the program is lowered for, ``choose(kernel, plain,
    *operands)``.

    The one seam of every such choice: a test that runs the kernels off
    the chip replaces this function with one that hands out the
    module's functions in the Pallas interpreter and always takes the
    kernel (``tests/helpers.py::kernels_interpreted``)."""
    return importlib.import_module(f"{__package__}.{name}"), _on_tpu


def kernel_or_plain(name: str, fits, kernel, plain, *operands):
    """``kernel(ops)(*operands)`` where the program is lowered for a TPU
    and ``fits(ops)`` holds, ``ops`` the Pallas module ``ops/<name>.py``;
    ``plain(*operands)`` anywhere else. JAX caches a branch's trace on
    the function: a module's own function (``lambda ops: ops.inverse``)
    is traced once for every layer that calls it with the same shapes."""
    ops, choose = _lowered_for(name)
    if not fits(ops):
        return plain(*operands)
    return choose(kernel(ops), plain, *operands)


def kernel_ran(name: str, fits):
    """1.0 where ``kernel_or_plain(name, fits, ...)`` runs the kernel,
    0.0 where it runs the plain form (float32)."""

    def ran():
        return jnp.ones((), jnp.float32)

    def not_ran():
        return jnp.zeros((), jnp.float32)

    return kernel_or_plain(name, fits, lambda ops: ran, not_ran)
