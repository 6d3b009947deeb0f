#!/usr/bin/env python3
"""``tools/precision_controls_mla.py`` for the ``ppo-granite-recall``
cell: what each step below the stated precision reads in the
``ppo_seq_ssm`` family's comparison, at the timed sizes — the evidence
for the limits of ``runners/ppo_seq_ssm.py``.

    python3 perfbench/tools/precision_controls_ssm.py --seed N \\
        [--grads all_bfloat16,...] [--out chiprun_out/controls.jsonl]

That tool reads ``CONTROLS``, ``REPORTED``, ``judge_rollout``,
``errors`` and ``compare_loss_and_grads`` from the module the cell's
runner is defined in, so it is this family's tool as it stands; only
the cell it runs by default differs. The controls here: the
state-space state in bfloat16, the scan's products with bfloat16
inputs, the norms in bfloat16, everything in bfloat16.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from precision_controls_mla import main, run  # noqa: E402,F401

if __name__ == "__main__":
    sys.exit(main(["--workload", "ppo-granite-recall", *sys.argv[1:]]))
