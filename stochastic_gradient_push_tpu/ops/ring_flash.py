"""Ring attention with fused Pallas flash kernels per tick.

``parallel/ring_attention.py`` keeps the K/V rotation but computes each
tick's contribution with a materialized ``[block, block]`` fp32 score
matrix — fine at study scale, quadratic HBM at long context (a 16k-token
shard is a 1 GB score tensor per batch·head).  This module is the
long-context production path: the same ring schedule, but every tick's
block attention runs through the fused flash kernels
(ops/flash_attention.py), so per-device memory stays
O(flash_block²) regardless of shard length, and the MXU sees the same
tuned kernels the single-device path uses.

Two structural tricks make the composition exact:

* **LSE merging** (forward): each tick returns its block-normalized
  output plus the row logsumexp; ticks combine by
  ``lse ← logaddexp(lse, lse_t)`` with outputs reweighted by
  ``exp(lse_t − lse)`` — the online-softmax recurrence lifted to whole
  ticks.
* **Global-LSE backward**: flash-attention-2's backward needs only the
  FINAL row logsumexp and ``delta = rowsum(do · out)``; per-tick calls
  of the flash backward (one fused kernel at any shard length up to
  8192, the dq + dk/dv pair beyond: ``fused_backward_fits``) with the
  merged lse yield exactly that tick's gradient contribution.  dq
  accumulates locally; dk/dv accumulators ride around the ring WITH
  their k/v blocks and arrive home after a full rotation.

Causality needs no position plumbing: a tick is either fully visible
(``causal=False`` kernels), the aligned diagonal block
(``causal=True`` kernels), or fully masked (skipped) — the three-way
``lax.switch`` below.

On non-TPU backends the per-tick compute falls back to a pure-JAX
blockwise tick (the oracle the tests pin against); ``interpret=True``
forces the Pallas kernels through the Pallas interpreter so CPU tests
exercise the real kernel path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (
    flash_attention_backward,
    flash_attention_forward,
)
from .gossip_kernel import resolve_use_pallas

__all__ = ["ring_flash_attention"]

NEG_INF = -1e30

_FULL, _DIAG, _SKIP = 0, 1, 2


def _tick_fwd(q, k, v, causal: bool, use_pallas: bool, interpret: bool,
              block: int):
    """One tick's block attention → (normalized out, lse [b,h,t])."""
    if use_pallas:
        return flash_attention_forward(q, k, v, causal=causal,
                                       block_q=block, block_k=block,
                                       interpret=interpret,
                                       return_lse=True)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    den = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    out = (out / den[..., None]).astype(q.dtype)
    return out, m + jnp.log(den)


def _tick_bwd(q, k, v, out, lse, do, causal: bool, use_pallas: bool,
              interpret: bool, block: int):
    """One tick's (dq, dk, dv) under the GLOBAL lse/out (flash-2 rule)."""
    if use_pallas:
        return flash_attention_backward(q, k, v, out, lse, do,
                                        causal=causal, block_q=block,
                                        block_k=block,
                                        interpret=interpret)
    d = q.shape[-1]
    scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                   preferred_element_type=jnp.float32)
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[..., None])
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _tick_mode(my_rank, owner, causal: bool):
    if not causal:
        return jnp.int32(_FULL)
    return jnp.where(owner == my_rank, _DIAG,
                     jnp.where(owner < my_rank, _FULL, _SKIP))


def _ring_forward(q, k, v, axis_name, causal, use_pallas, interpret,
                  block):
    world = lax.axis_size(axis_name)
    my_rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % world) for i in range(world)]
    tick = functools.partial(_tick_fwd, use_pallas=use_pallas,
                             interpret=interpret, block=block)

    def merge(acc, lse, mode, k_blk, v_blk):
        def visible(causal_tick):
            out_t, lse_t = tick(q, k_blk, v_blk, causal_tick)
            lse_new = jnp.logaddexp(lse, lse_t)
            w1 = jnp.exp(lse - lse_new)
            w2 = jnp.exp(lse_t - lse_new)
            return (acc * w1[..., None]
                    + out_t.astype(jnp.float32) * w2[..., None], lse_new)

        return lax.switch(mode, [lambda: visible(False),
                                 lambda: visible(True),
                                 lambda: (acc, lse)])

    zeros_bht = jnp.sum(q.astype(jnp.float32) * 0.0, axis=-1)
    acc = jnp.zeros_like(q, jnp.float32)
    lse = zeros_bht + NEG_INF

    def body(carry, step):
        acc, lse, k_blk, v_blk = carry
        nk = lax.ppermute(k_blk, axis_name, perm)
        nv = lax.ppermute(v_blk, axis_name, perm)
        mode = _tick_mode(my_rank, (my_rank - step) % world, causal)
        acc, lse = merge(acc, lse, mode, k_blk, v_blk)
        return (acc, lse, nk, nv), None

    if world > 1:
        (acc, lse, k_last, v_last), _ = lax.scan(
            body, (acc, lse, k, v), jnp.arange(world - 1))
        mode = _tick_mode(my_rank, (my_rank + 1) % world, causal)
        acc, lse = merge(acc, lse, mode, k_last, v_last)
    else:
        acc, lse = merge(acc, lse, jnp.int32(_DIAG if causal else _FULL),
                         k, v)
    return (acc).astype(q.dtype), lse


def _ring_backward(q, k, v, out, lse, do, axis_name, causal, use_pallas,
                   interpret, block):
    world = lax.axis_size(axis_name)
    my_rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % world) for i in range(world)]
    tick = functools.partial(_tick_bwd, use_pallas=use_pallas,
                             interpret=interpret, block=block)

    def contribute(dq_acc, dk_acc, dv_acc, mode, k_blk, v_blk):
        def visible(causal_tick):
            dq_t, dk_t, dv_t = tick(q, k_blk, v_blk, out, lse, do,
                                    causal_tick)
            return (dq_acc + dq_t.astype(jnp.float32),
                    dk_acc + dk_t.astype(jnp.float32),
                    dv_acc + dv_t.astype(jnp.float32))

        return lax.switch(mode, [lambda: visible(False),
                                 lambda: visible(True),
                                 lambda: (dq_acc, dk_acc, dv_acc)])

    dq_acc = jnp.zeros_like(q, jnp.float32)
    dk_acc = jnp.zeros_like(k, jnp.float32)
    dv_acc = jnp.zeros_like(v, jnp.float32)

    def body(carry, step):
        dq_acc, k_blk, v_blk, dk_acc, dv_acc = carry
        mode = _tick_mode(my_rank, (my_rank - step) % world, causal)
        dq_acc, dk_acc, dv_acc = contribute(dq_acc, dk_acc, dv_acc, mode,
                                            k_blk, v_blk)
        # the dk/dv accumulators travel WITH their block
        nk = lax.ppermute(k_blk, axis_name, perm)
        nv = lax.ppermute(v_blk, axis_name, perm)
        ndk = lax.ppermute(dk_acc, axis_name, perm)
        ndv = lax.ppermute(dv_acc, axis_name, perm)
        return (dq_acc, nk, nv, ndk, ndv), None

    if world > 1:
        (dq_acc, k_last, v_last, dk_acc, dv_acc), _ = lax.scan(
            body, (dq_acc, k, v, dk_acc, dv_acc), jnp.arange(world - 1))
        mode = _tick_mode(my_rank, (my_rank + 1) % world, causal)
        dq_acc, dk_acc, dv_acc = contribute(dq_acc, dk_acc, dv_acc, mode,
                                            k_last, v_last)
        # blocks sit one hop short of home after world-1 rotations; the
        # final hop returns each accumulator to its block's owner
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    else:
        mode = jnp.int32(_DIAG if causal else _FULL)
        dq_acc, dk_acc, dv_acc = contribute(dq_acc, dk_acc, dv_acc, mode,
                                            k, v)
    return (dq_acc.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, use_pallas, interpret, block):
    out, _ = _ring_forward(q, k, v, axis_name, causal, use_pallas,
                           interpret, block)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, use_pallas, interpret,
                    block):
    out, lse = _ring_forward(q, k, v, axis_name, causal, use_pallas,
                             interpret, block)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, use_pallas, interpret, block,
                    residuals, g):
    q, k, v, out, lse = residuals
    return _ring_backward(q, k, v, out, lse, g, axis_name, causal,
                          use_pallas, interpret, block)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, axis_name: str, causal: bool = False,
                         block: int | None = None, interpret: bool = False,
                         use_pallas: bool | None = None):
    """Exact ring attention with flash-kernel ticks.

    Args:
      q, k, v: per-rank sequence blocks ``[batch, heads, block_len,
        head_dim]``; must be called inside ``shard_map``.
      axis_name: mesh axis the sequence is sharded over.
      causal: causal masking consistent with contiguous block layout.
      block: flash kernel block size within each tick; None = the
        measured auto rule (flash_attention.default_block) on the local
        shard length.
      interpret: run the Pallas kernels through the interpreter
        (CPU tests of the real kernel path).
      use_pallas: force the kernel choice; default auto — Pallas on TPU
        (or when ``interpret``), pure-JAX blockwise tick elsewhere.  The
        auto rule is the shared
        :func:`~.gossip_kernel.resolve_use_pallas`, one convention for
        every Pallas lane in ops/.
    """
    use_pallas = resolve_use_pallas(use_pallas, interpret)
    if block is None:
        from .flash_attention import default_block

        block = default_block(q.shape[2])
    return _ring_flash(q, k, v, axis_name, causal, use_pallas, interpret,
                       block)
