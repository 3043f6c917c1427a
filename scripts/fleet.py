#!/usr/bin/env python
"""fleet — two-level fleet supervision: per-host supervisors + a pod
coordinator that survive whole-slice loss.

Usage:
    # the pod coordinator (one per fleet, shared filesystem):
    python scripts/fleet.py --coordinator --fleet_dir /runs/f1 \\
        --hosts 4 --rows 8

    # one per-host supervisor (everything after -- is that host's
    # training command):
    python scripts/fleet.py --host 2 --fleet_dir /runs/f1 -- \\
        python -m stochastic_gradient_push_tpu.run.gossip_sgd \\
        --world_size 32 --num_processes 4 --process_id 2 --fleet True \\
        --checkpoint_dir /runs/f1 --trace_dir /runs/f1/host2 ...

    # the CI chaos e2e (SIGKILL a whole simulated slice mid-run ->
    # rendezvous excludes it -> concurrent 6->4 reshard -> one
    # coordinated relaunch -> run completes at the shrunken world):
    python scripts/fleet.py --selftest

Exit codes: 0 clean, 1 selftest failure / fleet gave up, 75
preempted-after-checkpoint (requeue me), 2 unusable configuration,
4 this host was excluded from the new world.

The coordinator tails every host's supervisor.jsonl and broadcasts
rendezvous calls and fleet decisions through coordinator.jsonl; see
stochastic_gradient_push_tpu/supervise/coordinator.py.
"""

import os
import signal
import sys

# die quietly when piped into `head` instead of tracebacking
signal.signal(signal.SIGPIPE, signal.SIG_DFL)

# the CHILD must inherit the environment as the operator set it (a TPU
# child on a TPU host): snapshot BEFORE pinning our own platform to CPU
CHILD_ENV = dict(os.environ)

# coordinator and supervisor are pure host work (tailers, planner
# numpy, msgpack reshard), and a chip belongs to one process at a time:
# they must never initialise the accelerator a child needs
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stochastic_gradient_push_tpu.supervise.fleetcli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(child_env=CHILD_ENV))
