#!/usr/bin/env python3
"""``tools/precision_controls_mla.py`` for the ``ppo-sdar-turns`` cell:
what each step below the stated precision reads in the
``ppo_seq_diffusion`` family's comparison, at the timed sizes — the
evidence for the limits of ``runners/ppo_seq_diffusion.py``.

    python3 perfbench/tools/precision_controls_diffusion.py --seed N \\
        [--grads all_bfloat16,...] [--out chiprun_out/controls.jsonl]

That tool reads ``CONTROLS`` (and ``REPORTED``, which this family does
not have), ``judge_rollout``, ``errors`` and ``compare_loss_and_grads``
from the module the cell's runner is defined in, so it is this family's
tool as it stands; only the cell it runs by default differs.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from precision_controls_mla import main, run  # noqa: E402,F401

if __name__ == "__main__":
    sys.exit(main(["--workload", "ppo-sdar-turns", *sys.argv[1:]]))
