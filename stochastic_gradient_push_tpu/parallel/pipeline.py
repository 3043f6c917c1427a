"""SPMD pipeline parallelism: GPipe-style microbatch rotation over a mesh
axis.

The reference has no pipeline parallelism (SURVEY.md §2: PP "absent") — this
is a TPU-native extension in the same spirit as ring attention: one more mesh
axis the decentralized algorithms compose with.  Design follows the standard
single-program formulation (scaling-book pipelining recipe): every device
holds one *stage* (a contiguous slice of the layer stack) and runs the same
compiled loop of ``M + S - 1`` ticks; at each tick a device applies its stage
to the activation it holds, then passes the result to the next stage with
``lax.ppermute``.  Stage 0 injects a fresh microbatch each tick, the last
stage collects finished microbatches.  There are no host threads and no
per-stage programs — the schedule is one ``lax.scan`` inside the jitted
train step, so XLA overlaps each tick's ppermute with the next tick's
compute the same way the gossip layer overlaps its rounds.

The fill/drain bubble costs ``(S - 1) / (M + S - 1)`` of the ticks — pick
``n_micro >> n_stages`` to amortize.  Backward runs the reverse schedule
automatically: autodiff transposes the scan-of-ppermute into a
drain-ordered backward pipeline (the transpose of a cyclic shift is the
opposite cyclic shift), which is exactly GPipe's synchronous
forward-all-then-backward-all schedule.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_spmd", "pvary_missing"]


def pvary_missing(x, axes):
    """Mark ``x`` varying over any of ``axes`` it isn't already varying
    over (idempotent pvary — a plain pvary/pcast raises on an
    already-varying axis)."""
    have = jax.typeof(x).vma
    need = tuple(a for a in axes if a not in have)
    if not need:
        return x
    return lax.pcast(x, need, to="varying")


def pipeline_spmd(body: tp.Callable, x_micro: jnp.ndarray,
                  pipe_axis: str, with_aux: bool = False):
    """Run ``body`` as one pipeline stage over rotating microbatches.

    Args:
      body: the stage function ``h -> h`` (this shard's slice of the layer
        stack); same input/output shape.  With ``with_aux`` the body
        returns ``(h, aux)`` where aux is a pytree of scalars (e.g. MoE
        load-balance losses).
      x_micro: ``[M, ...]`` stacked microbatch activations.  Every shard
        passes the same array; only stage 0 actually consumes it (the other
        shards' copies are dead code after the ``where`` and carry zero
        gradient).
      pipe_axis: mesh axis name the stages live on.
      with_aux: also return the per-tick aux summed over this stage's
        *valid* ticks (stage ``s`` processes microbatch ``t - s`` at tick
        ``t``; fill/drain bubble ticks run the body on garbage and their
        aux is masked to zero — with zero gradient — by the same
        ``where`` discipline as the inject/collect path).

    Returns:
      ``[M, ...]`` stage outputs — **valid on the last stage only**; other
      shards hold garbage.  Mask by ``lax.axis_index(pipe_axis)`` and
      ``lax.psum`` to share (see train/pp.py).  With ``with_aux``:
      ``(out, aux_sum)`` where aux_sum is the masked per-stage sum over
      its M valid ticks.
    """
    S = lax.axis_size(pipe_axis)
    stage = lax.axis_index(pipe_axis)
    M = x_micro.shape[0]
    # the carry becomes device-varying over pipe after the first ppermute;
    # mark the zero initializers as varying up front so the scan carry type
    # is stable (shard_map's varying-manual-axes tracking).  zeros_like
    # inherits x_micro's axes, which may already include pipe (e.g. when
    # the embed producing x_micro is gated on the stage index) — hence the
    # idempotent mark
    buf = pvary_missing(jnp.zeros_like(x_micro[0]), (pipe_axis,))
    out = pvary_missing(jnp.zeros_like(x_micro), (pipe_axis,))
    shift = [(i, (i + 1) % S) for i in range(S)]

    aux0 = None
    if with_aux:
        aux_shapes = jax.eval_shape(lambda h: body(h)[1], x_micro[0])
        # zeros tainted by x_micro (* 0, folded away) so the scan carry's
        # varying-axes type matches the in-loop accumulator from tick one
        taint = (x_micro * 0).sum()
        aux0 = jax.tree.map(
            lambda a: pvary_missing(
                jnp.zeros(a.shape, a.dtype) + taint.astype(a.dtype),
                (pipe_axis,)),
            aux_shapes)

    def tick(carry, t):
        buf, out, aux_acc = carry
        inject = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        h = jnp.where(stage == 0, inject, buf)
        if with_aux:
            h, aux = body(h)
            # this stage holds microbatch t - stage at tick t; anything
            # else is a fill/drain bubble whose aux must not contribute
            m_idx = t - stage
            live = (m_idx >= 0) & (m_idx < M)
            aux_acc = jax.tree.map(
                lambda acc, a: acc + jnp.where(live, a, 0), aux_acc, aux)
        else:
            h = body(h)
        # collect on the last stage: tick t finishes microbatch t - (S - 1)
        idx = jnp.clip(t - (S - 1), 0, M - 1)
        valid = (stage == S - 1) & (t >= S - 1)
        cur = lax.dynamic_index_in_dim(out, idx, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, h, cur), idx, 0)
        # hand the activation to the next stage; the wrap-around edge
        # (last -> 0) carries garbage that stage 0's inject overwrites
        buf = lax.ppermute(h, pipe_axis, shift)
        return (buf, out, aux_acc), None

    (_, out, aux_sum), _ = lax.scan(tick, (buf, out, aux0),
                                    jnp.arange(M + S - 1))
    if with_aux:
        return out, aux_sum
    return out
