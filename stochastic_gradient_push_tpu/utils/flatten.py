"""Pytree reductions over a whole parameter tree.

The reference flattens parameter lists into one contiguous 1-D buffer per
dtype so each gossip round is a single NCCL message (gossip/utils/
helpers.py:21-88).  On TPU the collective layer mixes pytrees leaf by leaf
and XLA coalesces the transfers, so nothing here flattens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["global_norm"]


def global_norm(tree) -> jnp.ndarray:
    """L2 norm over all leaves (feeds the per-step ``grad_norm`` metric).

    Per-leaf sum-of-squares, not ``ravel_pytree``: the ravel would
    materialize a flat copy of the whole tree every step just to reduce
    it."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))
