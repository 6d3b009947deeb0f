#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, needs the chips the cell asks
for, and prints the result as ONE JSON object on the last line of its
standard output. Off the chip it prints no result and exits non-zero.
See perfbench/README.md.
"""

import time

T_PROCESS0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not os.path.isdir(
        os.path.join(ROOT, "actor_critic_algs_on_tensorflow_tpu")
    ):
        print("[perfbench] FAIL: the program under test is not in this "
              "checkout (BENCHMARK.json and perfbench/ alone measure "
              "nothing)", file=sys.stderr)
        return 4
    sys.path.insert(0, ROOT)
    # A hang ends inside the driver's limit, with a traceback.
    faulthandler.dump_traceback_later(1150, exit=True)
    from perfbench.harness import driver, spec

    try:
        return driver.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROCESS0)
    except spec.SpecError as e:
        print(f"[perfbench] FAIL: {e}", file=sys.stderr)
        return 6


if __name__ == "__main__":
    sys.exit(main())
