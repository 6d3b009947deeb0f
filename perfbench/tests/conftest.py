"""CPU rehearsal of the benchmark's harness: virtual CPU devices (the x4
runner takes four of them), tiny widths, counts and correctness only —
never a time or a rate.

`perfbench/tests` is a package, so that its `conftest` and `helpers`
do not take the module names of `tests/conftest.py` and
`tests/helpers.py`, and both directories collect in one session. Both
ask for the same eight devices; whichever comes second finds the
backend up and leaves it."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:
    pass  # tests/conftest.py came first and the backend is up: 8 there too

from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()
