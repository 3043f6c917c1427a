#!/usr/bin/env python
"""plan — launch-time gossip topology & mixing planner.

Usage:
    python scripts/plan.py --world 64 --ppi 1             # recommend
    python scripts/plan.py --world 64 --ppi 1 --report    # ranked table
    python scripts/plan.py --world 64 --topology ring     # check a forced choice
    python scripts/plan.py --world 64 --self-weighted     # co-optimized alpha
    python scripts/plan.py --world 8 --selftest           # CI self-check

Exit codes: 0 clean plan, 2 unsupported configuration, 3 plan carries
warnings (e.g. a forced topology below the gap floor).

Pure numpy over small matrices; runs in about a second anywhere.
"""

import os
import signal
import sys

# die quietly when piped into `head` instead of tracebacking
signal.signal(signal.SIGPIPE, signal.SIG_DFL)

# importing the package pulls in jax; force CPU so the
# planner behaves identically on dev boxes, CI, and TPU hosts
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stochastic_gradient_push_tpu.planner.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
