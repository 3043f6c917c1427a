"""Pallas TPU flash-attention kernels: fused forward AND backward.

The transformer path's compute hot spot.  Every kernel has one schedule
shape: a 2-D grid over batch·head (``parallel``) and a list of tile
VISITS (``arbitrary``).  :func:`tile_visits` makes the list at trace time
from the sequence, the blocks and the mask — the (q-block, k-block) pairs
that hold an unmasked pair, a row of the major axis after another — and
the kernels take it by scalar prefetch
(``pltpu.PrefetchScalarGridSpec``): index maps read a visit's blocks, so
Pallas double-buffers the streamed k/v (or q/do) block fetches behind the
matmuls instead of parking whole ``[seq, d]`` operands in VMEM per cell
(the round-3 design, whose dk/dv kernel lost to XLA 122.8 ms vs 68.6 ms
at t=4096 in an earlier installation's capture), and the kernels find a
row's first and last visit by comparing with the list's neighbouring
entry.  A causal call therefore takes no grid step without a tile body
(136 visits a head at t8192 in blocks of 512, where the rectangle has
256; TPU v5e, PERF.md §6, PR 37), a call with no mask walks the whole
rectangle as a list, and a window or a document mask is another list.
Running state lives in fp32 VMEM scratch that persists across grid steps:
the forward carries the online-softmax ``(m, den, acc)`` triple, the
backward its gradient accumulators, and outputs are written once, on the
last visit that adds to them.

The forward's tile body keeps its per-row state off the cross-lane unit,
which — not the MXU, not the mask, not the operands' casts — set its pace
(a k-step with ``[rows, 1]`` state cost 4 lane broadcasts and 2 lane
reductions a row group; TPU v5e, PERF.md §6, PR 30): the running maximum
is held in all 128 lanes of a row and the running denominator as 128
lane-partial sums, so one row maximum is the only cross-lane reduction
of a k-step and the denominator's row sum is taken once a q-block, in
``_finalize``.  ``out`` and ``lse`` differ from a row sum taken every
tile by fp32 rounding of a reordered sum, nothing more.

Backward (``jax.custom_vjp``) is flash-attention-2's, in ONE kernel
wherever :func:`fused_backward_fits`:

* Fused kernel, visits k-block-major: per tile
  ``s = q·kᵀ``, the mask, ``p = exp(s - lse)``, ``dp = do·vᵀ`` and
  ``ds = p·(dp - delta)`` are computed once and feed all three products:
  ``dv += pᵀ·do`` and ``dk += dsᵀ·q`` into ``[block_k, d]`` accumulators
  written on the k-block's last q-step, ``dq[q-block rows] += ds·k`` into
  a ``[seq, d]`` accumulator that lives for the whole sweep of one bh and
  is written once.
  Five products a tile, every operand read once.  VMEM is O(block²)
  plus dq for one sequence, which is what the shape rule reckons: heads
  of one register take the default scope, wider heads ask for twice it.
* Beyond that (``seq`` over 8192, or heads so wide the larger scope
  cannot hold their dq and tiles) the dq + dk/dv PAIR: a dQ kernel,
  visits q-block-major as the forward's, streams k/v and accumulates
  ``dq``; a dK/dV kernel on the fused kernel's visits accumulates ``dk``
  and ``dv``.  Each recomputes ``p`` and ``ds`` — seven products a tile,
  operands read twice — but VMEM stays O(block²) at any length; what
  grows with the length is the visit list in SMEM (:func:`tile_visits`
  gives the limit).  Same operands, same casts, same order of
  accumulation: the two give the same bits.

Values may be narrower or wider than queries and keys (latent attention
takes q·k heads of 192 beside v heads of 128): ``v``, ``out``, ``do`` and
``dv`` are ``d_v`` wide, ``q``, ``k``, ``dq`` and ``dk`` ``d_qk``, and the
softmax scale is ``d_qk ** -0.5``: every kernel takes the two widths,
the fused backward too (latent attention's 192 / 128 at 8192 tokens is
fused, in the wider scope).  A call with ``d_v == d_qk`` up to 128 builds
the kernels it built before widths could differ.

The per-row residuals travel in compact ``[rows, 1]`` layouts: the
forward's logsumexp and ``delta = rowsum(do · o)``, the latter computed
once outside the kernels (a fused XLA elementwise-reduce) so ``o`` is not
an operand of any backward kernel.

On non-TPU backends ``flash_attention`` transparently falls back to the
pure-JAX blockwise implementation
(parallel/ring_attention.py::blockwise_attention); Pallas interpret mode
exercises every kernel in tests against that same oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.ring_attention import blockwise_attention
from ..telemetry import names

__all__ = ["flash_attention", "flash_attention_forward",
           "flash_attention_backward", "fused_backward_fits",
           "fused_backward_vmem", "tile_visits"]

NEG_INF = -1e30

# Mosaic requires the last two block dims be (8·k, 128·k) or full-size.
# Per-row scalars (logsumexp, delta) ride as a [rows, 1] column — the last
# dim is the ARRAY's full size (1), which Mosaic accepts, so each residual
# costs t floats instead of the 128·t a lane-broadcast layout would.
SCALAR_COLS = 1

# fp32 running-state scratch is a full [rows, 128] register column, so the
# forward's per-row state never takes a cross-lane move inside the k-sweep:
# the running maximum holds the same value in every lane of a row, the
# running denominator one partial sum a lane (the row's sum is taken once,
# on the last k-step)
_STATE_LANES = 128


def default_block(t: int) -> int:
    """Auto block size: the LARGEST block that tiles the sequence.  From
    captures of an earlier installation (TPU v5e, unverified on today's
    toolchain): a step-level A/B on the full d768/L12 LM train step
    (scanned+fenced, docs/tpu_runs/20260731T072937_lmblock) had block 512
    at 64.0 ms vs 82.7 (block 256) vs 127.5 (block 128) at t=1024, and
    block 512 also won the kernel-level fenced sweeps at t=2048 and
    t=4096 (docs/tpu_runs/20260731T071733_retry/flashblocks.txt).
    The streamed schedule keeps VMEM at O(block^2), so 512 is safe; the
    chip's compiler accepts it at t=1024 and t=4096
    (tests/test_chip_compile.py)."""
    for b in (512, 256, 128):
        if t % b == 0:
            return b
    return min(128, t)


# What the fused backward may keep in VMEM on top of its tiles where the
# heads take one register a row: half of the 16 MiB a v5e kernel scopes
# by default, which holds dq for 8192 tokens; the tiles' 512 × 512 fp32
# intermediates and operand blocks need most of the other half
# (tests/test_chip_compile.py compiles both sides of it).
FUSED_BWD_VMEM_BUDGET = 8 * 2 ** 20
# The scoped VMEM a call with heads over one register asks for, of the
# v5e's 128 MiB: twice the default, since a dq row takes two registers
# (dq for 8192 tokens at 192 lanes is 16 MiB).  The whole of what the
# kernel holds must fit it (:func:`fused_backward_vmem`).
FUSED_BWD_WIDE_VMEM = 32 * 2 ** 20
_LANES = 128


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct typed varying over every manual mesh axis any of
    ``like`` varies over — required for pallas_call outputs inside a
    vma-checked ``shard_map`` (shared by every kernel module)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


@functools.lru_cache(maxsize=None)
def tile_visits(t: int, block_q: int, block_k: int, causal: bool,
                major: str):
    """The tiles a kernel visits, in the order it visits them: the
    q-block and the k-block of each visit, two read-only ``int32`` arrays
    of one length.  A tile is visited where the mask leaves it any pair
    — causal: its last query row reaches its first key column; no mask:
    every tile.  ``major`` ``"q"`` walks q-blocks with each one's k-blocks
    ascending to the diagonal (forward, dq); ``"k"`` walks k-blocks with
    each one's q-blocks ascending from the diagonal (dk/dv, the fused
    backward).  Every block of the major axis has a visit.  Made at trace
    time, once a shape: a step's trace asks 72 times.  The kernels hold
    the two arrays in SMEM, 1 MiB on a v5e, so a call may make just under
    2**17 visits: 32768 tokens in blocks of 128 with no mask (65536
    visits) compile, 65536 do not (tests/test_chip_compile.py); at the
    auto block of 512 the limit lies beyond 131072 tokens."""
    keep = np.ones((t // block_q, t // block_k), bool)
    if causal:
        last_row = np.arange(t // block_q) * block_q + block_q - 1
        keep = last_row[:, None] >= np.arange(t // block_k) * block_k
    if major == "q":
        q_blocks, k_blocks = np.nonzero(keep)
    else:
        k_blocks, q_blocks = np.nonzero(keep.T)
    visits = q_blocks.astype(np.int32), k_blocks.astype(np.int32)
    for blocks in visits:
        blocks.flags.writeable = False
    return visits


def _visit(i, q_blocks, k_blocks, rows):
    """Grid step ``i``'s visit: its q-block, its k-block, and whether it
    is the first and the last visit of its row of ``rows`` (the major
    axis's array of the two), by the neighbouring entries."""
    n = rows.shape[0]
    row = rows[i]
    first = (i == 0) | (rows[jnp.maximum(i - 1, 0)] != row)
    last = (i == n - 1) | (rows[jnp.minimum(i + 1, n - 1)] != row)
    return q_blocks[i], k_blocks[i], first, last


def _visit_call(kernel, name: str, visits, operands, in_specs, out_specs,
                out_shape, scratch_shapes, interpret: bool,
                vmem_limit: int | None = None):
    """``kernel`` over ``operands`` ``[bh, ...]`` on grid (batch·head,
    visit): the two visit arrays go first, by scalar prefetch, so every
    index map is ``(bh, i, q_blocks, k_blocks)`` and the kernel takes the
    visit's number ``i``, then the arrays, then its refs.  Batch·head is
    ``parallel``; the visits revisit outputs and scratch, which requires
    ``arbitrary``.  The arrays are constants beside operands that vary
    over the mesh's axes, and the compiled call takes the mix inside a
    vma-checked ``shard_map`` (tests/test_chip_compile.py).  The
    INTERPRETER evaluates the kernel's equations on the enclosing trace's
    vma-typed values, where a kernel's literals and fresh scratch carry no
    axes and ``mul`` refuses them beside a varying block (casting the
    arrays to vary moves the refusal into the index maps'
    ``dynamic_slice``: PERF.md §6, PR 37); a ``cond``'s branches are not
    typed, so an interpreted body runs in one that is always taken — what
    the rectangle's gate on the whole tile body did unnoticed.
    ``vmem_limit`` is the scoped VMEM the compiled call asks for; ``None``
    leaves the compiler's default."""
    def cell(*refs):
        i = pl.program_id(1)
        pl.when(i >= 0 if interpret else True)(lambda: kernel(i, *refs))

    visits = [jnp.asarray(blocks) for blocks in visits]
    return pl.pallas_call(
        cell,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(operands[0].shape[0], visits[0].shape[0]),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=name)(*visits, *operands)


def _row_specs(d: int, block_q: int, block_k: int):
    """The three block specs every kernel's operands take: ``[block_q, d]``
    and the per-row scalars' ``[block_q, SCALAR_COLS]`` following the
    visit's q-block, ``[block_k, d]`` following its k-block."""
    def q_row(bh, i, q_blocks, k_blocks):
        return (bh, q_blocks[i], 0)

    def k_row(bh, i, q_blocks, k_blocks):
        return (bh, k_blocks[i], 0)

    return (pl.BlockSpec((None, block_q, d), q_row),
            pl.BlockSpec((None, block_k, d), k_row),
            pl.BlockSpec((None, block_q, SCALAR_COLS), q_row))


def _causal_mask(s, qi, kj, block_q: int, block_k: int):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _lanes(x, n: int):
    """``x`` is ``[rows, 128]`` with a row's lanes all equal: the same rows
    ``n`` lanes wide, by reusing the registers (no cross-lane move)."""
    reps = -(-n // _STATE_LANES)
    if reps > 1:
        x = pltpu.repeat(x, reps, 1)
    return x[:, :n]


def _lane_partials(p):
    """``[rows, cols]`` -> ``[rows, w]``: ``p``'s column groups of one
    register's lanes (``w`` = 128, or what a narrower block has) added
    elementwise.  Its sum over lanes is ``p``'s row sum."""
    w = math.gcd(p.shape[-1], _STATE_LANES)
    groups = [p[:, c:c + w] for c in range(0, p.shape[-1], w)]
    return sum(groups[1:], groups[0])


def _flash_fwd_kernel(i, q_blocks, k_blocks, q_ref, k_ref, v_ref, o_ref,
                      *rest, block_q: int, block_k: int, causal: bool,
                      return_lse: bool):
    """One (batch·head, visit) cell, visits q-block-major.  Refs: q
    [block_q, d_qk], o [block_q, d_v]; k [block_k, d_qk], v [block_k, d_v]
    (streamed); lse (when requested) [block_q, SCALAR_COLS]; scratch
    m/den [block_q, 128] and acc [block_q, d_v], all fp32, persistent across a q-block's visits; m
    holds a row's maximum in every lane and den lane partials
    (``_STATE_LANES``), so the row maximum is a visit's one cross-lane
    reduction."""
    if return_lse:
        lse_ref, m_ref, den_ref, acc_ref = rest
    else:
        m_ref, den_ref, acc_ref = rest
    qi, kj, first, last = _visit(i, q_blocks, k_blocks, q_blocks)

    @pl.when(first)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        den_ref[:] = jnp.zeros_like(den_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    q = q_ref[:].astype(jnp.float32) * (d ** -0.5)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bq, bk]
    if causal:
        s = _causal_mask(s, qi, kj, block_q, block_k)
    m_prev = m_ref[:]                                      # [bq, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, block_k))
    alpha = jnp.exp(m_prev - m_new)                        # [bq, 128]
    part = _lane_partials(p)                               # [bq, w]
    w = part.shape[-1]
    den_ref[:, :w] = den_ref[:, :w] * alpha[:, :w] + part
    acc_ref[:] = acc_ref[:] * _lanes(alpha, acc_ref.shape[-1]) \
        + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(last)
    def _finalize():
        den = jnp.sum(den_ref[:], -1, keepdims=True)       # [bq, 1]
        o_ref[:] = (acc_ref[:] / den).astype(o_ref.dtype)
        if return_lse:
            lse_ref[:] = m_ref[:, :1] + jnp.log(den)


def flash_attention_forward(q, k, v, causal: bool = False,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool = False,
                            return_lse: bool = False):
    """Pallas forward.  q/k: ``[batch, heads, seq, d_qk]``, v
    ``[batch, heads, seq, d_v]``; the output is ``d_v`` wide.

    With ``return_lse`` also returns the row logsumexp ``[b, h, seq]``
    (float32), the residual the fused backward kernels consume.
    """
    b, h, t, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"seq {t}")

    d_v = v.shape[-1]
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d_v)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        causal=causal, return_lse=return_lse)
    q_row, k_row, s_row = _row_specs(d, block_q, block_k)
    o_row, v_row, _ = _row_specs(d_v, block_q, block_k)
    out_specs = [o_row]
    out_shape = [_sds((b * h, t, d_v), q.dtype, qf)]
    if return_lse:
        out_specs.append(s_row)
        out_shape.append(_sds((b * h, t, SCALAR_COLS), jnp.float32,
                              qf))
    results = _visit_call(
        kernel, names.KERNEL_FLASH_FWD,
        tile_visits(t, block_q, block_k, causal, "q"), (qf, kf, vf),
        in_specs=[q_row, k_row, v_row],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATE_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATE_LANES), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        interpret=interpret)
    if return_lse:
        out, lse = results
        return out.reshape(b, h, t, d_v), lse[..., 0].reshape(b, h, t)
    out, = results
    return out.reshape(b, h, t, d_v)


def _tile_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
               block_q: int, block_k: int, causal: bool):
    """What every backward product of one visited tile is made from:
    ``p = exp(s - lse)`` and ``ds = p * (dp - delta)``, both
    ``[block_q, block_k]`` fp32, with the fp32 operands they came from
    (``q`` already scaled by ``d ** -0.5``)."""
    q = q_ref[:].astype(jnp.float32) * (q_ref.shape[-1] ** -0.5)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bq, bk]
    if causal:
        s = _causal_mask(s, qi, kj, block_q, block_k)
    p = jnp.exp(s - lse_ref[:])                            # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bq, bk]
    ds = p * (dp - delta_ref[:])
    return q, k, do, p, ds


def _dq_term(ds, k):
    """One tile's addend to dq, ``[block_q, d]``."""
    return jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * (k.shape[-1] ** -0.5)


def _dkv_terms(p, ds, q, do):
    """One tile's addends to dk and dv, ``[block_k, d]`` each."""
    dv = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bk, d]
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bk, d]
    return dk, dv


def _flash_bwd_kernel(i, q_blocks, k_blocks, k_ref, v_ref, q_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                      dv_acc, *dq_scratch, block_q: int, block_k: int,
                      causal: bool):
    """Fused backward cell (bh, visit), visits k-block-major.  Refs:
    k/dk [block_k, d_qk], v/dv [block_k, d_v]; q [block_q, d_qk], do
    [block_q, d_v] (streamed); lse/delta [block_q, SCALAR_COLS]; dq
    [t, d_qk], one block a bh.  Scratch, fp32: dk/dv accumulators as wide
    as dk/dv, alive over one k-block's visits, and the dq accumulator
    [t, d_qk], alive over all the visits of one bh — the
    dq block itself where dq is fp32, which is resident for just that
    sweep."""
    qi, kj, first, last = _visit(i, q_blocks, k_blocks, k_blocks)
    dq_acc = dq_scratch[0] if dq_scratch else dq_ref

    @pl.when(i == 0)
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, k, do, p, ds = _tile_p_ds(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
        block_q, block_k, causal)
    dk, dv = _dkv_terms(p, ds, q, do)
    dv_acc[:] += dv
    dk_acc[:] += dk
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    dq_acc[rows, :] += _dq_term(ds, k)

    @pl.when(last)
    def _finalize_dkv():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    if dq_scratch:
        @pl.when(i == k_blocks.shape[0] - 1)
        def _finalize_dq():
            dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dq_kernel(i, q_blocks, k_blocks, q_ref, k_ref, v_ref, do_ref,
                     lse_ref, delta_ref, dq_ref, acc_ref, *, block_q: int,
                     block_k: int, causal: bool):
    """dQ cell (bh, visit), visits q-block-major.  Refs: q/dq
    [block_q, d_qk], do [block_q, d_v]; k [block_k, d_qk], v
    [block_k, d_v] (streamed); lse/delta [block_q, SCALAR_COLS]; scratch
    acc [block_q, d_qk] fp32."""
    qi, kj, first, last = _visit(i, q_blocks, k_blocks, q_blocks)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    _, k, _, _, ds = _tile_p_ds(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
        block_q, block_k, causal)
    acc_ref[:] += _dq_term(ds, k)

    @pl.when(last)
    def _finalize():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(i, q_blocks, k_blocks, k_ref, v_ref, q_ref, do_ref,
                      lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      block_q: int, block_k: int, causal: bool):
    """dK/dV cell (bh, visit), visits k-block-major.  Refs: k/dk
    [block_k, d_qk], v/dv [block_k, d_v]; q [block_q, d_qk], do
    [block_q, d_v] (streamed); lse/delta [block_q, SCALAR_COLS]; scratch
    dk/dv accumulators as wide as dk/dv, fp32."""
    qi, kj, first, last = _visit(i, q_blocks, k_blocks, k_blocks)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, _, do, p, ds = _tile_p_ds(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
        block_q, block_k, causal)
    dk, dv = _dkv_terms(p, ds, q, do)
    dv_acc[:] += dv
    dk_acc[:] += dk

    @pl.when(last)
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _lanes_of(d: int) -> int:
    return -(-d // _LANES) * _LANES


def fused_backward_vmem(t: int, d_qk: int, d_v: int, block_q: int,
                        block_k: int, dtype) -> int:
    """Bytes of VMEM the fused backward holds at a shape, every row
    padded to whole 128-lane registers: dq for the whole sequence, eight
    bytes a lane (an fp32 accumulator under a two-deep 16-bit block, or a
    two-deep fp32 block accumulated in place); the streamed q, k, v, do,
    lse and delta blocks and the dk, dv blocks, two deep; the fp32 dk/dv
    accumulators; and four fp32 ``[block_q, block_k]`` tiles (``s``,
    ``p``, ``dp``, ``ds``).  Against the chip's compiler (a described v5e)
    it errs high by at most a MiB: 24 MiB reckoned where 23 compile at
    8192 tokens, q·k 192, v 128, bf16, blocks of 512."""
    size = jnp.dtype(dtype).itemsize
    w_qk, w_v = _lanes_of(d_qk), _lanes_of(d_v)
    dq = t * w_qk * 8
    streamed = 2 * size * (block_q + block_k) * (w_qk + w_v)
    residuals = 2 * 2 * block_q * _LANES * 4
    dkv_out = 2 * size * block_k * (w_qk + w_v)
    dkv_acc = 4 * block_k * (w_qk + w_v)
    tiles = 4 * 4 * block_q * block_k
    return dq + streamed + residuals + dkv_out + dkv_acc + tiles


def fused_backward_fits(t: int, d_qk: int, d_v: int | None = None,
                        block_q: int = 512, block_k: int = 512,
                        dtype=jnp.bfloat16) -> bool:
    """The backward's shape rule: one fused kernel while what it holds in
    VMEM fits the scope the call asks for, the dq + dk/dv pair beyond.
    Heads of one register (``d_qk`` and ``d_v`` up to 128) ask for none:
    dq for the whole sequence must stay inside
    :data:`FUSED_BWD_VMEM_BUDGET`, 8192 tokens at any dtype and block.
    Wider heads ask for :data:`FUSED_BWD_WIDE_VMEM`, which all of
    :func:`fused_backward_vmem` must fit: latent attention's 192 / 128 at
    8192 tokens does, at 16384 tokens or at heads of 384 lanes it does
    not.  ``d_v`` defaults to ``d_qk``."""
    d_v = d_qk if d_v is None else d_v
    if max(d_qk, d_v) <= _LANES:
        return t * _LANES * 8 <= FUSED_BWD_VMEM_BUDGET
    return fused_backward_vmem(t, d_qk, d_v, block_q, block_k,
                               dtype) <= FUSED_BWD_WIDE_VMEM


def flash_attention_backward(q, k, v, out, lse, do, causal: bool = False,
                             block_q: int = 128, block_k: int = 128,
                             interpret: bool = False):
    """Pallas backward: returns ``(dq, dk, dv)``, from one fused kernel
    where :func:`fused_backward_fits` and from the dq + dk/dv pair where
    the sequence is too long or the heads too wide for it.  ``v``,
    ``out`` and ``do`` may be narrower or wider than ``q`` and ``k``.

    ``lse`` is the forward's row logsumexp ``[b, h, seq]``; it and
    ``delta = rowsum(do · out)`` (computed here, once, as a fused XLA
    reduce) ship in the compact ``[rows, 1]`` layout, so no
    lane-broadcast scalar array ever exists in HBM and ``out`` is not an
    operand of any kernel.
    """
    b, h, t, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"seq {t}")

    d_v = v.shape[-1]
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d_v)
    dof = do.reshape(b * h, t, d_v)
    lsef = lse.reshape(b * h, t)[..., None]  # [b*h, t, SCALAR_COLS]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, t)[..., None]

    operands = (qf, kf, vf, dof, lsef, delta, causal, block_q, block_k,
                interpret)
    if fused_backward_fits(t, d, d_v, block_q, block_k, q.dtype):
        scope = None if max(d, d_v) <= _LANES else FUSED_BWD_WIDE_VMEM
        dq, dk, dv = _backward_fused(*operands, scope)
    else:
        dq, dk, dv = _backward_pair(*operands)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d_v))


def _backward_fused(qf, kf, vf, dof, lsef, delta, causal, block_q,
                    block_k, interpret, vmem_limit=None):
    """One kernel over the k-block-major visits: every visited tile's
    ``p`` and ``ds`` computed once, dk/dv written a k-block, dq carried in
    VMEM over all the visits of one bh and written once."""
    bh, t, d = qf.shape
    d_v = vf.shape[-1]
    q_row, k_row, s_row = _row_specs(d, block_q, block_k)
    o_row, v_row, _ = _row_specs(d_v, block_q, block_k)
    return _visit_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q,
                          block_k=block_k, causal=causal),
        names.KERNEL_FLASH_BWD,
        tile_visits(t, block_q, block_k, causal, "k"),
        (kf, vf, qf, dof, lsef, delta),
        in_specs=[k_row, v_row, q_row, o_row, s_row, s_row],
        out_specs=[
            pl.BlockSpec((None, t, d), lambda bh, i, *visits: (bh, 0, 0)),
            k_row,
            v_row,
        ],
        out_shape=[
            _sds((bh, t, d), qf.dtype, qf),
            _sds((bh, t, d), kf.dtype, kf),
            _sds((bh, t, d_v), vf.dtype, vf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
            # an fp32 dq accumulates in its own block: no scratch for it
        ] + ([] if qf.dtype == jnp.float32
             else [pltpu.VMEM((t, d), jnp.float32)]),
        interpret=interpret, vmem_limit=vmem_limit)


def _backward_pair(qf, kf, vf, dof, lsef, delta, causal, block_q, block_k,
                   interpret):
    """The dq kernel over the q-block-major visits, then the dk/dv kernel
    over the k-block-major ones: VMEM O(block²) at any length the visit
    lists fit SMEM at, every tile's ``p`` and ``ds`` computed twice."""
    bh, t, d = qf.shape
    d_v = vf.shape[-1]
    q_row, k_row, s_row = _row_specs(d, block_q, block_k)
    o_row, v_row, _ = _row_specs(d_v, block_q, block_k)
    dq = _visit_call(
        functools.partial(_flash_dq_kernel, block_q=block_q,
                          block_k=block_k, causal=causal),
        names.KERNEL_FLASH_DQ,
        tile_visits(t, block_q, block_k, causal, "q"),
        (qf, kf, vf, dof, lsef, delta),
        in_specs=[q_row, k_row, v_row, o_row, s_row, s_row],
        out_specs=q_row,
        out_shape=_sds((bh, t, d), qf.dtype, qf),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret)

    dk, dv = _visit_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal),
        names.KERNEL_FLASH_DKV,
        tile_visits(t, block_q, block_k, causal, "k"),
        (kf, vf, qf, dof, lsef, delta),
        in_specs=[k_row, v_row, q_row, o_row, s_row, s_row],
        out_specs=[k_row, v_row],
        out_shape=[
            _sds((bh, t, d), kf.dtype, kf),
            _sds((bh, t, d_v), vf.dtype, vf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        interpret=interpret)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    return flash_attention_forward(q, k, v, causal=causal,
                                   block_q=block_q, block_k=block_k)


def _flash_fwd(q, k, v, causal, block_q, block_k):
    out, lse = flash_attention_forward(q, k, v, causal=causal,
                                       block_q=block_q, block_k=block_k,
                                       return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    return flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                    block_q=block_q, block_k=block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int | None = None,
                    block_k: int | None = None):
    """Differentiable flash attention; Pallas on TPU, pure-JAX blockwise
    elsewhere.  ``block_q``/``block_k`` default to the measured
    :func:`default_block` rule for the sequence length."""
    t = q.shape[2]
    block_q = default_block(t) if block_q is None else block_q
    block_k = default_block(t) if block_k is None else block_k
    if jax.default_backend() != "tpu":
        return blockwise_attention(q, k, v, min(block_k, t),
                                   causal=causal)
    return _flash(q, k, v, causal, block_q, block_k)
