"""Ring attention: exact self-attention over a sequence-sharded mesh axis.

Long-context support the task treats as first-class.  The reference repo has
no attention model at all (SURVEY.md §5 "Long-context": its transformer
results came from an external fairseq fork), so this is a TPU-native
extension rather than a port: each rank holds one block of the sequence;
keys/values rotate around the ring with ``lax.ppermute`` while every rank
accumulates its queries' attention over all blocks with an online-softmax
running state (the flash-attention recurrence).  Peak memory per rank is
O(block²) instead of O(seq²), and the K/V transfer for step *i+1* overlaps
with the block-attention compute of step *i* — the same collective-compute
overlap the gossip layer exploits.

Causal masking notes: blocks are laid out contiguously (rank r owns tokens
[r·B, (r+1)·B)); at ring step s, rank r attends to the block originally
owned by rank (r - s) mod world.  A block is fully visible when its owner
index is below r, fully masked when above, and diagonally masked when it is
r's own block.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "blockwise_attention"]

NEG_INF = -1e30


def _block_attn(q, k, v, bias=None):
    """One (q-block × kv-block) attention contribution.

    Returns the unnormalized accumulator pieces: running max ``m``,
    numerator ``num = Σ exp(s - m)·v`` and denominator ``den = Σ exp(s-m)``.
    Shapes: q ``[B, H, Tq, D]``, k/v ``[B, H, Tk, D]``.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                                # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    num = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32))
    den = jnp.sum(p, axis=-1)
    return m, num, den


def _merge(state, m2, num2, den2):
    """Online-softmax merge of a new block into the running state."""
    m1, num1, den1 = state
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return (m,
            num1 * a1[..., None] + num2 * a2[..., None],
            den1 * a1 + den2 * a2)


def ring_attention(q, k, v, axis_name: str, causal: bool = False):
    """Exact attention with K/V blocks rotating over ``axis_name``.

    Args:
      q, k, v: per-rank blocks ``[batch, heads, block_len, head_dim]``.
      axis_name: mesh axis the sequence is sharded over.
      causal: apply a causal mask consistent with contiguous block layout.

    Returns per-rank attention output ``[batch, heads, block_len, head_dim]``.
    Must be called inside ``shard_map``.
    """
    world = lax.axis_size(axis_name)
    my_rank = lax.axis_index(axis_name)
    block_len = q.shape[2]
    qf = q.astype(jnp.float32)

    # ring permutation: pass K/V to the next rank each step
    perm = [(i, (i + 1) % world) for i in range(world)]

    def causal_bias(kv_owner):
        # owner below me: fully visible; above: fully masked; mine: diagonal
        q_pos = my_rank * block_len + jnp.arange(block_len)
        k_pos = kv_owner * block_len + jnp.arange(block_len)
        mask = q_pos[:, None] >= k_pos[None, :]
        return jnp.where(mask, 0.0, NEG_INF)[None, None]

    def attend(state, k_blk, v_blk, kv_owner):
        bias = causal_bias(kv_owner) if causal else None
        m2, num2, den2 = _block_attn(qf, k_blk, v_blk, bias)
        return _merge(state, m2, num2, den2)

    # derive the accumulators from q so they inherit ALL of its varying
    # mesh axes (shard_map vma rules: the scan carry type must match the
    # body outputs, which vary over every axis q does)
    zeros_bht = jnp.sum(qf * 0.0, axis=-1)
    init_state = (zeros_bht + NEG_INF,      # running max
                  jnp.zeros_like(qf),       # numerator
                  zeros_bht)                # denominator

    # send-then-attend: each iteration ISSUES the rotation of the block it
    # holds before attending it.  The ppermute has no data dependency on
    # the attend, so XLA's async collectives overlap the step-s+1 K/V
    # transfer with the step-s block attention (the double-buffering the
    # reference's gossip thread provided by hand, here by dependency
    # structure).  The last received block is attended outside the scan so
    # no dead final transfer is emitted.
    def body(carry, step):
        state, k_blk, v_blk = carry
        nk = lax.ppermute(k_blk, axis_name, perm)
        nv = lax.ppermute(v_blk, axis_name, perm)
        state = attend(state, k_blk, v_blk, (my_rank - step) % world)
        return (state, nk, nv), None

    if world > 1:
        (state, k_last, v_last), _ = lax.scan(
            body, (init_state, k, v), jnp.arange(world - 1))
        state = attend(state, k_last, v_last, (my_rank + 1) % world)
    else:
        state = attend(init_state, k, v, my_rank)
    m, num, den = state
    out = num / den[..., None]
    return out.astype(q.dtype)


def blockwise_attention(q, k, v, block_size: int, causal: bool = False):
    """Single-device memory-efficient attention (same online-softmax math,
    no mesh): the local building block and the test oracle's counterpart.

    Shapes: ``[batch, heads, seq, head_dim]``; ``seq % block_size == 0``.
    ``v`` may be narrower or wider than ``q`` and ``k``; the output is as
    wide as ``v`` and the scale is ``q``'s width ``** -0.5``.
    """
    b, h, t, d = q.shape
    if t % block_size:
        raise ValueError(f"seq {t} not divisible by block {block_size}")
    n_blocks = t // block_size
    qf = q.astype(jnp.float32)

    k_blocks = k.reshape(b, h, n_blocks, block_size, d)
    v_blocks = v.reshape(b, h, n_blocks, block_size, v.shape[-1])

    def body(state, blk_idx):
        k_blk = k_blocks[:, :, blk_idx]
        v_blk = v_blocks[:, :, blk_idx]
        if causal:
            q_pos = jnp.arange(t)
            k_pos = blk_idx * block_size + jnp.arange(block_size)
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                             NEG_INF)[None, None]
        else:
            bias = None
        m2, num2, den2 = _block_attn(qf, k_blk, v_blk, bias)
        return _merge(state, m2, num2, den2), None

    zeros_bht = jnp.sum(qf * 0.0, axis=-1)
    m = zeros_bht + NEG_INF
    num = jnp.zeros_like(qf) if v.shape[-1] == d \
        else zeros_bht[..., None] * jnp.zeros(v.shape[-1], jnp.float32)
    init = (m, num, zeros_bht)
    (m, num, den), _ = lax.scan(body, init, jnp.arange(n_blocks))
    return (num / den[..., None]).astype(q.dtype)
