#!/usr/bin/env python
"""supervise — elastic run supervisor: checkpoint, reshard, replan,
relaunch.

Usage:
    # supervise a training run (everything after -- is the child):
    python scripts/supervise.py -- \\
        python -m stochastic_gradient_push_tpu.run.gossip_sgd \\
        --world_size 8 --trace_dir /runs/t1 --checkpoint_dir /runs/t1 ...

    # the CI chaos e2e (kill a rank mid-run -> reshard 8->4 -> relaunch):
    python scripts/supervise.py --selftest

Exit codes: 0 run complete, 1 selftest failure / restart budget spent,
75 preempted-after-checkpoint (requeue me), 2 unusable configuration.

The supervisor tails the child's typed events.jsonl stream and acts on
rank loss, sustained re-plan suggestions, watchdog stalls, crashes, and
preemption signals; see stochastic_gradient_push_tpu/supervise/.
"""

import os
import signal
import sys

# die quietly when piped into `head` instead of tracebacking
signal.signal(signal.SIGPIPE, signal.SIG_DFL)

# the CHILD must inherit the environment as the operator set it (a TPU
# child on a TPU host): snapshot BEFORE pinning the supervisor's own
# platform to CPU below
CHILD_ENV = dict(os.environ)

# the supervisor itself is pure host work (tailer, planner numpy,
# msgpack reshard), and a chip belongs to one process at a time: it must
# never initialise the accelerator its child needs
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stochastic_gradient_push_tpu.supervise.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(child_env=CHILD_ENV))
