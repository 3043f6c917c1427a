"""Pallas TPU flash-attention kernels: fused forward AND backward.

The transformer path's compute hot spot.  Every kernel has one schedule
shape: a 3-D grid over batch·head (``parallel``), an output block, and a
MINOR dimension that walks the streamed axis with ``arbitrary`` semantics
— so Pallas double-buffers the streamed k/v (or q/do) block fetches behind
the matmuls instead of parking whole ``[seq, d]`` operands in VMEM per
cell (the round-3 design, whose dk/dv kernel lost to XLA 122.8 ms vs
68.6 ms at t=4096 in an earlier installation's capture).  Running state
lives in fp32 VMEM scratch that persists across grid steps: the forward
carries the online-softmax ``(m, den, acc)`` triple, the backward its
gradient accumulators, and outputs are written once, on the last step
that adds to them.

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

* Fused kernel, grid ``(bh, k-block, q-step)``: per visible tile
  ``s = q·kᵀ``, the mask, ``p = exp(s - lse)``, ``dp = do·vᵀ`` and
  ``ds = p·(dp - delta)`` are computed once and feed all three products:
  ``dv += pᵀ·do`` and ``dk += dsᵀ·q`` into ``[block_k, d]`` accumulators
  written on the k-block's last q-step, ``dq[q-block rows] += ds·k`` into
  a ``[seq, d]`` accumulator that lives for the whole sweep of one bh and
  is written once (both inner grid dims are ``arbitrary`` for its sake).
  Five products a tile, every operand read once.  VMEM is O(block²)
  plus dq for one sequence, which is what the shape rule budgets.
* Beyond the budget (``seq`` over 8192, heads wider than 128) the
  dq + dk/dv PAIR: a dQ kernel, grid ``(bh, q-block, k-step)``, streams
  k/v and accumulates ``dq``; a dK/dV kernel on the fused kernel's grid
  accumulates ``dk`` and ``dv``.  Each recomputes ``p`` and ``ds`` — seven
  products a tile, operands read twice — but VMEM stays O(block²) at any
  length.  Same operands, same casts, same order of accumulation: the
  two give the same bits.

The per-row residuals travel in compact ``[rows, 1]`` layouts: the
forward's logsumexp and ``delta = rowsum(do · o)``, the latter computed
once outside the kernels (a fused XLA elementwise-reduce) so ``o`` is not
an operand of any backward kernel.  Causal runs skip the empty
triangle two ways: masked minor steps are compute-gated with ``pl.when``,
and their index maps clamp into the visible range so no new block is ever
fetched for a skipped step.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.ring_attention import blockwise_attention
from ..telemetry import names

__all__ = ["flash_attention", "flash_attention_forward",
           "flash_attention_backward", "fused_backward_fits"]

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
    The 3-D-grid schedule keeps VMEM at O(block^2), so 512 is safe; the
    chip's compiler accepts it at t=1024 and t=4096
    (tests/test_chip_compile.py)."""
    for b in (512, 256, 128):
        if t % b == 0:
            return b
    return min(128, t)


# What the fused backward may keep in VMEM on top of its tiles: half of
# the 16 MiB a v5e kernel may scope, which holds dq for 8192 tokens; the
# tiles' 512 × 512 fp32 intermediates and operand blocks need most of the
# other half (tests/test_chip_compile.py compiles both sides of it).
FUSED_BWD_VMEM_BUDGET = 8 * 2 ** 20


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct typed varying over every manual mesh axis any of
    ``like`` varies over — required for pallas_call outputs inside a
    vma-checked ``shard_map`` (shared by every kernel module)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _compiler_params(interpret: bool, middle: str = "parallel"):
    """Minor grid dim walks the streamed axis: revisited outputs/scratch
    require ``arbitrary``; batch·head is parallel, and so is the middle
    dim unless state is carried across it (the fused backward's dq)."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", middle, "arbitrary"))


def _causal_mask(s, qi, kj, block_q: int, block_k: int):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _visible(qi, kj, block_q: int, block_k: int, causal: bool):
    """Whether tile (q-block ``qi``, k-block ``kj``) holds any unmasked
    pair."""
    return (qi * block_q + block_q - 1 >= kj * block_k) if causal \
        else (qi >= 0)


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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q: int,
                      block_k: int, causal: bool, return_lse: bool):
    """One (batch·head, q-block, k-step) cell.  Refs: q/o [block_q, d];
    k/v [block_k, d] (streamed); lse (when requested)
    [block_q, SCALAR_COLS]; scratch m/den [block_q, 128] and
    acc [block_q, d], all fp32, persistent across k-steps; m holds a
    row's maximum in every lane and den lane partials (``_STATE_LANES``),
    so the row maximum is a k-step's one cross-lane reduction."""
    if return_lse:
        lse_ref, m_ref, den_ref, acc_ref = rest
    else:
        m_ref, den_ref, acc_ref = rest
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        den_ref[:] = jnp.zeros_like(den_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(qi, kj, block_q, block_k, causal))
    def _compute():
        d = q_ref.shape[-1]
        q = q_ref[:].astype(jnp.float32) * (d ** -0.5)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k)
        m_prev = m_ref[:]                                  # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)                    # [bq, 128]
        part = _lane_partials(p)                           # [bq, w]
        w = part.shape[-1]
        den_ref[:, :w] = den_ref[:, :w] * alpha[:, :w] + part
        acc_ref[:] = acc_ref[:] * _lanes(alpha, d) + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        den = jnp.sum(den_ref[:], -1, keepdims=True)       # [bq, 1]
        o_ref[:] = (acc_ref[:] / den).astype(o_ref.dtype)
        if return_lse:
            lse_ref[:] = m_ref[:, :1] + jnp.log(den)


def flash_attention_forward(q, k, v, causal: bool = False,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool = False,
                            return_lse: bool = False):
    """Pallas forward.  q/k/v: ``[batch, heads, seq, head_dim]``.

    With ``return_lse`` also returns the row logsumexp ``[b, h, seq]``
    (float32), the residual the fused backward kernels consume.
    """
    b, h, t, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"seq {t}")

    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)

    def kv_map(bh, qi, kj):
        if causal:
            # masked steps re-point at the last visible block: same index
            # as the previous step ⇒ Pallas skips the fetch entirely
            kj = jnp.minimum(kj, (qi * block_q + block_q - 1) // block_k)
        return (bh, kj, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        causal=causal, return_lse=return_lse)
    out_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
    ]
    out_shape = [_sds((b * h, t, d), q.dtype, qf)]
    if return_lse:
        out_specs.append(pl.BlockSpec((None, block_q, SCALAR_COLS),
                                      lambda bh, qi, kj: (bh, qi, 0)))
        out_shape.append(_sds((b * h, t, SCALAR_COLS), jnp.float32,
                              qf))
    results = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d),
                         lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATE_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATE_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_FLASH_FWD,
    )(qf, kf, vf)
    if return_lse:
        out, lse = results
        return out.reshape(b, h, t, d), lse[..., 0].reshape(b, h, t)
    out, = results
    return out.reshape(b, h, t, d)


def _tile_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
               block_q: int, block_k: int, causal: bool):
    """What every backward product of one visible tile is made from:
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


def _flash_bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *dq_scratch,
                      block_q: int, block_k: int, causal: bool):
    """Fused backward cell (bh, k-block, q-step).  Refs: k/v/dk/dv
    [block_k, d]; q/do [block_q, d] (streamed); lse/delta
    [block_q, SCALAR_COLS]; dq [t, d], one block a bh.  Scratch, fp32:
    dk/dv accumulators [block_k, d], alive over one k-block's q-steps, and
    the dq accumulator [t, d], alive over the whole (k-block, q-step)
    sweep of one bh — the dq block itself where dq is fp32, which is
    resident for just that sweep."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)
    dq_acc = dq_scratch[0] if dq_scratch else dq_ref

    @pl.when((kj == 0) & (qi == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(qi, kj, block_q, block_k, causal))
    def _compute():
        q, k, do, p, ds = _tile_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
            block_q, block_k, causal)
        dk, dv = _dkv_terms(p, ds, q, do)
        dv_acc[:] += dv
        dk_acc[:] += dk
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[rows, :] += _dq_term(ds, k)

    @pl.when(qi == nq - 1)
    def _finalize_dkv():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    if dq_scratch:
        @pl.when((kj == nk - 1) & (qi == nq - 1))
        def _finalize_dq():
            dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, acc_ref, *, block_q: int, block_k: int,
                     causal: bool):
    """dQ cell (bh, q-block, k-step).  Refs: q/do/dq [block_q, d];
    k/v [block_k, d] (streamed); lse/delta [block_q, SCALAR_COLS];
    scratch acc [block_q, d] fp32."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(qi, kj, block_q, block_k, causal))
    def _compute():
        _, k, _, _, ds = _tile_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
            block_q, block_k, causal)
        acc_ref[:] += _dq_term(ds, k)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                      block_k: int, causal: bool):
    """dK/dV cell (bh, k-block, q-step).  Refs: k/v/dk/dv [block_k, d];
    q/do [block_q, d] (streamed); lse/delta [block_q, SCALAR_COLS];
    scratch dk/dv accumulators [block_k, d] fp32."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(qi, kj, block_q, block_k, causal))
    def _compute():
        q, _, do, p, ds = _tile_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, kj,
            block_q, block_k, causal)
        dk, dv = _dkv_terms(p, ds, q, do)
        dv_acc[:] += dv
        dk_acc[:] += dk

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def fused_backward_fits(t: int, d: int) -> bool:
    """The backward's shape rule: one fused kernel while dq for the whole
    sequence stays inside :data:`FUSED_BWD_VMEM_BUDGET`, the dq + dk/dv
    pair beyond.  A row of dq takes a 128-lane register at any head size
    up to 128, eight bytes a lane: an fp32 accumulator under a two-deep
    16-bit dq block, or a two-deep fp32 dq block accumulated in place.
    Wider heads stay with the pair: their tiles leave dq no such room
    (t4096 / d256 fp32 is refused fused and compiles as the pair)."""
    return d <= 128 and t * 128 * 8 <= FUSED_BWD_VMEM_BUDGET


def flash_attention_backward(q, k, v, out, lse, do, causal: bool = False,
                             block_q: int = 128, block_k: int = 128,
                             interpret: bool = False):
    """Pallas backward: returns ``(dq, dk, dv)``, from one fused kernel
    where :func:`fused_backward_fits` and from the dq + dk/dv pair where
    the sequence is too long for it.

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

    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    dof = do.reshape(b * h, t, d)
    lsef = lse.reshape(b * h, t)[..., None]  # [b*h, t, SCALAR_COLS]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, t)[..., None]

    backward = _backward_fused if fused_backward_fits(t, d) \
        else _backward_pair
    dq, dk, dv = backward(qf, kf, vf, dof, lsef, delta, causal, block_q,
                          block_k, interpret)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))


def _dkv_specs(t: int, d: int, block_q: int, block_k: int, causal: bool):
    """Grid and operand specs of the (bh, k-block, q-step) schedule, for
    operands in the order k, v, q, do, lse, delta; and the k-block spec
    dk and dv are written through."""
    def q_map(bh, kj, qi):
        if causal:
            # the first visible q-step for this k-block; earlier (masked)
            # steps alias it so no block is fetched for them
            qi = jnp.maximum(qi, (kj * block_k) // block_q)
        return (bh, qi, 0)

    k_col = pl.BlockSpec((None, block_k, d),
                         lambda bh, kj, qi: (bh, kj, 0))
    in_specs = [
        k_col,                                              # k
        k_col,                                              # v
        pl.BlockSpec((None, block_q, d), q_map),            # q
        pl.BlockSpec((None, block_q, d), q_map),            # do
        pl.BlockSpec((None, block_q, SCALAR_COLS), q_map),  # lse
        pl.BlockSpec((None, block_q, SCALAR_COLS), q_map),  # delta
    ]
    return (t // block_k, t // block_q), in_specs, k_col


def _backward_fused(qf, kf, vf, dof, lsef, delta, causal, block_q,
                    block_k, interpret):
    """One kernel over grid (bh, k-block, q-step): every visible tile's
    ``p`` and ``ds`` computed once, dk/dv written a k-block, dq carried in
    VMEM over the whole sweep and written once a bh — so both inner dims
    are ``arbitrary``."""
    bh, t, d = qf.shape
    inner, in_specs, k_col = _dkv_specs(t, d, block_q, block_k, causal)
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(bh,) + inner,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, t, d), lambda bh, kj, qi: (bh, 0, 0)),
            k_col,
            k_col,
        ],
        out_shape=[
            _sds((bh, t, d), qf.dtype, qf),
            _sds((bh, t, d), kf.dtype, kf),
            _sds((bh, t, d), vf.dtype, vf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            # an fp32 dq accumulates in its own block: no scratch for it
        ] + ([] if qf.dtype == jnp.float32
             else [pltpu.VMEM((t, d), jnp.float32)]),
        compiler_params=_compiler_params(interpret, middle="arbitrary"),
        interpret=interpret,
        name=names.KERNEL_FLASH_BWD,
    )(kf, vf, qf, dof, lsef, delta)


def _backward_pair(qf, kf, vf, dof, lsef, delta, causal, block_q, block_k,
                   interpret):
    """The dq kernel, grid (bh, q-block, k-step), then the dk/dv kernel,
    grid (bh, k-block, q-step): VMEM O(block²) at any length, every
    tile's ``p`` and ``ds`` computed twice."""
    bh, t, d = qf.shape

    def kv_map(bh, qi, kj):
        if causal:
            kj = jnp.minimum(kj, (qi * block_q + block_q - 1) // block_k)
        return (bh, kj, 0)

    q_row = pl.BlockSpec((None, block_q, d),
                         lambda bh, qi, kj: (bh, qi, 0))
    s_row = pl.BlockSpec((None, block_q, SCALAR_COLS),
                         lambda bh, qi, kj: (bh, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[
            q_row,                                          # q
            pl.BlockSpec((None, block_k, d), kv_map),       # k
            pl.BlockSpec((None, block_k, d), kv_map),       # v
            q_row,                                          # do
            s_row,                                          # lse
            s_row,                                          # delta
        ],
        out_specs=q_row,
        out_shape=_sds((bh, t, d), qf.dtype, qf),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_FLASH_DQ,
    )(qf, kf, vf, dof, lsef, delta)

    inner, in_specs, k_col = _dkv_specs(t, d, block_q, block_k, causal)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(bh,) + inner,
        in_specs=in_specs,
        out_specs=[k_col, k_col],
        out_shape=[
            _sds((bh, t, d), kf.dtype, kf),
            _sds((bh, t, d), vf.dtype, vf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_FLASH_DKV,
    )(kf, vf, qf, dof, lsef, delta)
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
