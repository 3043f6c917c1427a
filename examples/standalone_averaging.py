"""Distributed averaging without a model (reference README's standalone
Gossiper use case).

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=.. python standalone_averaging.py
"""

import jax
import numpy as np

import stochastic_gradient_push_tpu as sgp
from stochastic_gradient_push_tpu.parallel import (
    consensus_error,
    make_gossip_mesh,
    push_sum_average,
)

world = jax.device_count()
mesh = make_gossip_mesh(world)
schedule = sgp.build_schedule(
    sgp.NPeerDynamicDirectedExponentialGraph(world, peers_per_itr=1))

# each rank holds a different measurement; we want every rank to learn the mean
values = np.random.default_rng(0).normal(size=(world, 10)).astype(np.float32)
print(f"before: consensus error {consensus_error(values):.4f}")

averaged = push_sum_average(values, mesh, schedule, rounds=40)
print(f"after : consensus error {consensus_error(averaged):.2e}")
print(f"true mean recovered: "
      f"{np.allclose(np.asarray(averaged)[0], values.mean(0), atol=1e-4)}")
