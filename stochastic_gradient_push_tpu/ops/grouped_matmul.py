"""Grouped matrix products for the top-k expert layer (models/moe.py):
``rows`` ``[M, K]`` sorted by expert, ``blocks`` ``[G, K, N]`` one an
expert held, ``sizes`` ``[G]`` consecutive rows each: ``out[rows of e] =
rows[rows of e] @ blocks[e]``.  Rows past the last group belong to no
expert: **no product is computed for them** and their output is not
written (callers mask it).

Three Pallas TPU kernels under one ``custom_vjp``, after the design of
the megablox kernels JAX ships
(``jax.experimental.pallas.ops.tpu.megablox``, which declares no ``vma``
on its outputs and so cannot run inside this package's vma-checked
``shard_map``):

* ``grouped_matmul`` — forward, and with the blocks contracted on their
  last dimension the rows' gradient.  The grid's inner dimension walks a
  list of *visits* made in XLA from ``sizes``: (group, row tile) pairs in
  row order, a tile that two groups share visited once for each.  The
  list's length is the grid's bound, a traced value: tiles past the held
  rows are never entered.  A revisited output tile stays in VMEM and the
  second group's rows are written over the first's under a row mask.
* ``grouped_matmul_dw`` — the blocks' gradient ``rows[e]^T @ d_out[e]``:
  the same visits, innermost, accumulated in a float32 scratch that is
  zeroed at a group's first visit and stored at its last; an empty group
  is visited once so that its gradient is written as zero.

The contraction is taken whole (``K`` is a layer width: 2048 or 1792 at
the published sizes), so no grid dimension walks it.  Picked by
:func:`kernel_fits` from platform and shapes; everywhere else
``lax.ragged_dot`` computes the same products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import names
from .flash_attention import _sds

__all__ = ["grouped_matmul", "grouped_dot", "kernel_fits"]

_LANES = 128
ROW_TILE = 512        # rows a visit takes
COL_TILE = 512        # output columns a grid step takes
# what a kernel may scope: the widest call holds a [512, 3584] and a
# [512, 3584] bf16 operand twice over beside its float32 product
_VMEM_LIMIT = 48 * 2 ** 20


def kernel_fits(platform: str, rows, blocks) -> bool:
    """The rule that picks the kernels, from what the code can observe and
    nothing else: a TPU, bf16 or float32 operands of one dtype, rows by
    whole tiles and widths by whole 128-lane registers.  Everything else
    (the CPU, a toy width) takes ``lax.ragged_dot``."""
    m, k = rows.shape
    n = blocks.shape[-1]
    return (platform == "tpu" and rows.dtype == blocks.dtype
            and rows.dtype in (jnp.bfloat16, jnp.float32)
            and m % ROW_TILE == 0 and k % _LANES == 0 and n % _LANES == 0)


def _col_tile(n: int) -> int:
    return next(t for t in (COL_TILE, 256, _LANES) if n % t == 0)


def _visits(sizes, m: int, tm: int, visit_empty: bool):
    """The (group, row tile) pairs the kernels walk, in row order:
    ``offsets`` ``[G + 1]`` (group ``e`` is rows ``offsets[e] ..
    offsets[e + 1]``), ``groups`` and ``tiles`` (one entry a visit, one
    spare) and the number of visits.  A group takes every tile it has a
    row in; with ``visit_empty`` a group with no rows takes one."""
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = starts // tm
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - first,
                      1 if visit_empty else 0)
    most = m // tm + 2 * g + 1
    groups = jnp.repeat(jnp.arange(g, dtype=jnp.int32), count,
                        total_repeat_length=most)
    before = jnp.cumsum(count) - count
    tiles = first[groups] + jnp.arange(most, dtype=jnp.int32) \
        - before[groups]
    tiles = jnp.clip(tiles, 0, m // tm - 1).astype(jnp.int32)
    return offsets.astype(jnp.int32), groups, tiles, count.sum()


def _inside(offsets, group, tile, tm: int, shape, axis: int):
    """Which rows of row tile ``tile`` are group ``group``'s, over a
    block of ``shape`` whose ``axis`` walks the tile's rows."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, shape, axis)
    return (rows >= offsets[group]) & (rows < offsets[group + 1])


def _gmm_kernel(offsets, groups, tiles, lhs_ref, rhs_ref, out_ref, *,
                tm: int, transpose_rhs: bool):
    i = pl.program_id(1)
    group, tile = groups[i], tiles[i]
    inside = _inside(offsets, group, tile, tm, out_ref.shape, 0)
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    product = lax.dot_general(lhs_ref[...], rhs_ref[...], contract,
                              preferred_element_type=jnp.float32)
    revisit = (i > 0) & (tiles[jnp.maximum(i - 1, 0)] == tile)

    @pl.when(revisit)
    def _():
        out_ref[...] = jnp.where(
            inside, product, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(revisit))
    def _():
        out_ref[...] = jnp.where(inside, product, 0).astype(out_ref.dtype)


def _params(interpret: bool, semantics: tuple):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _gmm(lhs, rhs, sizes, transpose_rhs: bool = False,
         interpret: bool = False):
    """``lhs`` ``[M, K]`` by ``rhs`` ``[G, K, N]`` (``[G, N, K]`` with
    ``transpose_rhs``) to ``[M, N]`` in ``lhs``'s dtype."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tn = ROW_TILE, _col_tile(n)
    offsets, groups, tiles, visits = _visits(sizes, m, tm, False)
    rhs_block = (None, tn, k) if transpose_rhs else (None, k, tn)
    rhs_at = (lambda j, i, o, g, t: (g[i], j, 0)) if transpose_rhs \
        else (lambda j, i, o, g, t: (g[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, o, g, t: (t[i], 0)),
                pl.BlockSpec(rhs_block, rhs_at)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, o, g, t: (t[i], j))),
        out_shape=_sds((m, n), lhs.dtype, lhs, rhs),
        compiler_params=_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
        name=names.KERNEL_GROUPED_MATMUL,
    )(offsets, groups, tiles, lhs, rhs)


def _tgmm_kernel(offsets, groups, tiles, lhs_t_ref, grad_ref, out_ref,
                 acc_ref, *, tm: int):
    i = pl.program_id(2)
    group, tile = groups[i], tiles[i]
    first = (i == 0) | (groups[jnp.maximum(i - 1, 0)] != group)
    last = (i == pl.num_programs(2) - 1) | (groups[i + 1] != group)

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # both operands to the group's own rows: the tile's other rows are
    # another group's or, past the held rows, what no product wrote (an
    # empty group's one visit can land on such a tile), and 0 times that
    # need not be 0
    lhs_t = jnp.where(_inside(offsets, group, tile, tm, lhs_t_ref.shape, 1),
                      lhs_t_ref[...], 0)
    grad = jnp.where(_inside(offsets, group, tile, tm, grad_ref.shape, 0),
                     grad_ref[...], 0)
    acc_ref[...] += jnp.dot(lhs_t, grad, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tgmm(lhs, grad, sizes, interpret: bool = False):
    """``lhs`` ``[M, K]`` and ``grad`` ``[M, N]`` to ``[G, K, N]``: each
    group's ``lhs^T @ grad`` over its own rows, in ``lhs``'s dtype."""
    m, k = lhs.shape
    n, g = grad.shape[1], sizes.shape[0]
    # the whole width of the rows where it is a layer's: every (k, n)
    # tile pair reads all the rows again, and at 512 by 512 that traffic
    # outran the products (3.1 ms a call against 1.2: PERF.md §6, PR 33)
    tm, tn = ROW_TILE, _col_tile(n)
    tk = k if k <= 2048 else _col_tile(k)
    offsets, groups, tiles, visits = _visits(sizes, m, tm, True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // tk, n // tn, visits),
            in_specs=[
                pl.BlockSpec((tk, tm),
                             lambda a, b, i, o, gr, t: (a, t[i])),
                pl.BlockSpec((tm, tn),
                             lambda a, b, i, o, gr, t: (t[i], b))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda a, b, i, o, gr, t: (gr[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=_sds((g, k, n), lhs.dtype, lhs, grad),
        compiler_params=_params(
            interpret, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=names.KERNEL_GROUPED_MATMUL_DW,
    )(offsets, groups, tiles, lhs.T, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(rows, blocks, sizes, interpret: bool = False):
    """``rows[group e] @ blocks[e]`` for every group of ``sizes``
    consecutive rows, as the Pallas kernels; the output's rows past the
    last group are not written."""
    return _gmm(rows, blocks, sizes, interpret=interpret)


def _grouped_fwd(rows, blocks, sizes, interpret):
    return _gmm(rows, blocks, sizes, interpret=interpret), \
        (rows, blocks, sizes)


def _grouped_bwd(interpret, residuals, d_out):
    rows, blocks, sizes = residuals
    d_rows = _gmm(d_out, blocks, sizes, transpose_rhs=True,
                  interpret=interpret)
    d_blocks = _tgmm(rows, d_out, sizes, interpret=interpret)
    return d_rows, d_blocks, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_dot(rows, blocks, sizes):
    """The grouped product by whichever path :func:`kernel_fits` picks."""
    if kernel_fits(jax.default_backend(), rows, blocks):
        return grouped_matmul(rows, blocks, sizes)
    return lax.ragged_dot(rows, blocks, sizes)
