"""Minimal custom training loop: bring your own model and data, use the
algorithm/collective layers directly (no Trainer).

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=.. python custom_training_loop.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import stochastic_gradient_push_tpu as sgp
from stochastic_gradient_push_tpu.algorithms import sgp as make_sgp
from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS, make_gossip_mesh

world = jax.device_count()
mesh = make_gossip_mesh(world)
schedule = sgp.build_schedule(
    sgp.DynamicDirectedExponentialGraph(world, peers_per_itr=1))
alg = make_sgp(schedule, GOSSIP_AXIS)

# per-rank least-squares problems; the consensus optimum is their average
rng = np.random.default_rng(0)
A = rng.normal(size=(world, 32, 6)).astype(np.float32)
b = rng.normal(size=(world, 32)).astype(np.float32)


def step(params, gstate, a, y):
    a, y = a[0], y[0]
    params, gstate = alg.pre_step(params, gstate)
    z = alg.eval_params(params, gstate)
    grads = jax.grad(
        lambda p: jnp.mean((a @ jnp.reshape(p, (-1,)) - y) ** 2))(z)
    params = params - 0.05 * jnp.reshape(grads, jnp.shape(params))
    return alg.post_step(params, gstate)


train = jax.jit(jax.shard_map(
    step, mesh=mesh, in_specs=(P(GOSSIP_AXIS),) * 4,
    out_specs=(P(GOSSIP_AXIS), P(GOSSIP_AXIS))))

params = np.zeros((world, 6), np.float32)
gstate = jax.tree.map(
    lambda t: np.broadcast_to(np.asarray(t), (world,) + np.shape(t)).copy(),
    alg.init(jnp.zeros((6,), jnp.float32)))

for i in range(400):
    params, gstate = jax.block_until_ready(train(params, gstate, A, b))

z = np.asarray(params) / np.asarray(gstate.ps_weight).reshape(world, 1)
spread = np.abs(z - z.mean(0)).max()
print(f"trained {world} gossip ranks; cross-rank spread {spread:.2e}")
