"""The gated delta rule (Gated DeltaNet, Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464) in its chunked form.

Per head, with a state ``S`` of ``[d_k, d_v]`` and ``S_0 = 0``::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The state is corrected by what it already holds for the key, so step by
step it is ``T`` sequential updates.  Cut into chunks of ``C`` steps, with
``g`` the cumulative sum of ``log alpha`` inside a chunk,
``G_ij = exp(g_i - g_j)`` for ``i >= j`` and ``S`` the state entering the
chunk, the corrections of one chunk are one unit-lower-triangular solve::

    A  = strictly_lower(diag(beta) (K K^T * G))
    T  = (I + A)^-1
    W  = T diag(beta) (K * exp(g))
    U  = T diag(beta) V
    V' = U - W S                         what each step writes, net
    O  = (Q * exp(g)) S + lower(Q K^T * G) V'
    S  <- exp(g_C) S + (K * exp(g_C - g))^T V'

The products take operands in ``operand_dtype`` with float32
accumulation; the gates, the cumulative sums, the decay masks (the mask
goes in before the exponential: above the diagonal the difference is
positive and as large as the chunk's whole decay), the solve and the
carried state stay float32.

Two paths compute it, picked by :func:`kernel_fits` from the platform and
the shapes and nothing else.  :func:`rule_xla` is plain XLA: every
``[C, C]`` block, ``W`` and ``U`` for all chunks at once (the solve is
``lax.linalg.triangular_solve``), the states entering the chunks by a
scan over them, and autodiff through all of it — the path of every
backend but the TPU and of every shape the kernels do not tile, and the
kernels' oracle in the tests.  On a TPU the rule is a Pallas kernel pair
under one ``jax.custom_vjp`` (:func:`rule_kernel`): one grid step a head
and block of chunks (:func:`chunks_a_step`), the blocks in order, the
head's float32 state in VMEM; no ``[C, C]`` block, ``W``, ``U``, ``V'``
or state is an XLA operation.  Inside a block, what does not wait on the
carried state is made for all its chunks stage by stage: each chunk's
``T`` is a chain of dependent products, and a chain alone leaves the MXU
waiting on every result (on the chip the forward took 4.8 ms a call at
the Olmo hybrid cell's sizes so, against 2.4 with four chunks' chains
side by side).

``T`` is made inside the kernel, float32, by merging inverted diagonal
blocks (the recursive form of blocked forward substitution): with ``T_s``
the inverse of ``I + A`` cut to diagonal blocks of ``s`` steps, and
``A_s`` the part of ``A`` that joins each pair of them into one of ``2 s``
(rows in the second block, columns in the first)::

    T_1 = I,   T_2s = T_s - T_s A_s T_s

— ``log2 C`` levels, two ``[C, C]`` products a level but the first, and
no power of ``A``.  It is exact where ``A`` is, and where the blocks'
products round it rounds as a forward substitution does.

The backward walks the chunks from the last with the gradient ``dS`` of
the state a chunk leaves in VMEM.  Per chunk, from ``dO``::

    dV' = lower(P)^T dO + K_C dS          (P = Q K^T * G, K_C = K * exp(g_C - g))
    dS  <- exp(g_C) dS + (Q * exp(g))^T dO - W^T dV'
    dW  = -dV' S^T,   dU = dV'
    dR  = T^T [dW | dU]                  (the solve's transpose)
    dA  = -strictly_lower(dR_k W^T + dR_v U^T)

and from those ``dq``, ``dk``, ``dv``, ``d beta`` and the per-step ``dg``
as autodiff would chain them; ``dg`` arrives as one row a chunk, its
terms along the steps of both indices of every ``[C, C]`` block (``dD *
D``'s row sums less its column sums), and the transpose of the cumulative
sum is XLA's.  Every decay factor lies in ``(0, 1]``, so no two terms of a
sum are larger than what they add up to by more than the data makes them.
The residuals are the kernels' inputs, the states entering the chunks
(``[B H, T / C · d_k, d_v]`` float32: 142 MB a layer in the Olmo hybrid
cell) and each chunk's ``T`` (``[B H, T, C]`` float32); the backward
makes ``G``, ``K K^T``, ``Q K^T``, ``W``, ``U`` and ``V'`` again.  The
states could be made again instead, by a forward walk in front of every
backward one: that costs a forward call's time, several times what the
142 MB take to write and read.

Dtypes, the mask before the exponential and the chunk length are
:func:`rule_xla`'s.  The two paths differ by the order of float32 sums,
by the solve (``T`` times the right-hand sides, where XLA solves by its
own blocked substitution; both float32 at ``HIGHEST``), and in three
roundings to ``operand_dtype`` of the backward: ``dO`` (rounded on its
way into the kernel), ``dV'`` into ``dW`` and ``S``'s gradient (XLA's
transposed products round it too; the kernel keeps the float32 ``dV'``
as ``dU``), and ``dS`` into ``dV'`` and ``dK_C``, where autodiff rounds
the scan's carried cotangent at the same products.
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

__all__ = ["delta_rule_chunked", "rule_xla", "rule_kernel", "delta_forward",
           "delta_backward", "kernel_fits", "chunks_a_step"]

# What the carried state (and its gradient) may take of VMEM: float32
# [d_k, d_v], twice over in the forward's entering-state block.  The
# Olmo hybrid cell's heads (keys of 96, values of 192) take 72 KiB.
STATE_VMEM_BUDGET = 2 ** 20

# Chunks a grid step takes, at most (:func:`chunks_a_step`).
CHUNKS_A_STEP = 4


def kernel_fits(platform: str, chunk: int, key_dim: int,
                value_dim: int) -> bool:
    """The rule that picks the kernel pair, from what the code can observe
    and nothing else: a TPU; a chunk of whole 8-row sublane tiles whose
    ``[C, C]`` blocks fit one 128-lane register row; keys and values of
    whole sublane tiles (the state is ``[d_k, d_v]``, and either size is
    a product's contracted side); a state inside
    :data:`STATE_VMEM_BUDGET`.  Everything else (the CPU, a sequence
    shorter than a chunk of eight) takes :func:`rule_xla`."""
    return (platform == "tpu" and chunk % 8 == 0 and 8 <= chunk <= 128
            and key_dim % 8 == 0 and value_dim % 8 == 0
            and key_dim * value_dim * 4 <= STATE_VMEM_BUDGET)


def _chunk_states(w, u, kd, decay, operand_dtype):
    """The scan over chunks: ``w``, ``u``, ``kd`` ``[B, N, H, C, *]``
    float32, ``decay`` ``[B, N, H]`` (``exp(g_C)``).  Returns the states
    entering the chunks ``[B, N, H, K, V]`` and ``V'`` ``[B, N, H, C, V]``,
    float32."""
    f32 = jnp.float32

    def chunk(state, inputs):
        w_c, u_c, kd_c, decay_c = inputs
        written = u_c - jnp.einsum(
            "bhck,bhkv->bhcv", w_c.astype(operand_dtype),
            state.astype(operand_dtype), preferred_element_type=f32)
        new = decay_c[..., None, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", kd_c.astype(operand_dtype),
            written.astype(operand_dtype), preferred_element_type=f32)
        return new, (state, written)

    along = lambda x: x.swapaxes(0, 1)
    # zeros typed like the inputs (inside a shard_map: varying over its
    # axes, as the carry the body returns)
    zeros = jnp.zeros_like(w[:, 0, :, 0, :, None] * u[:, 0, :, 0, None, :])
    _, (entering, written) = lax.scan(
        chunk, zeros, (along(w), along(u), along(kd), along(decay)))
    return along(entering), along(written)


def rule_xla(q, k, v, log_alpha, beta, size: int, operand_dtype):
    """The chunked rule as XLA operations and autodiff.  ``q``, ``k``
    ``[B, T, H, K]``, ``v`` ``[B, T, H, V]``, ``log_alpha``, ``beta``
    ``[B, T, H]``, ``T`` a multiple of ``size``.  Returns ``o``
    ``[B, T, H, V]`` float32."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    n = t // size

    def heads_first(x):             # [B, T, H, ...] -> [B, N, H, C, ...]
        return x.reshape(bsz, n, size, h, *x.shape[3:]).swapaxes(2, 3)

    q, k, v = (heads_first(x.astype(operand_dtype)) for x in (q, k, v))
    g = jnp.cumsum(heads_first(log_alpha.astype(f32)), axis=-1)
    beta = heads_first(beta.astype(f32))

    causal = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(causal, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                  # [B, N, H, C, C]
    kk = jnp.einsum("bnhik,bnhjk->bnhij", k, k, preferred_element_type=f32)
    a = jnp.where(jnp.tril(causal, -1), beta[..., None] * kk * decay, 0.0)
    rhs = beta[..., None] * jnp.concatenate(
        [k.astype(f32) * jnp.exp(g)[..., None], v.astype(f32)], axis=-1)
    solved = lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    w, u = solved[..., :dk], solved[..., dk:]

    kd = k.astype(f32) * jnp.exp(g[..., -1:] - g)[..., None]
    entering, written = _chunk_states(w, u, kd, jnp.exp(g[..., -1]),
                                      operand_dtype)

    scores = jnp.einsum("bnhik,bnhjk->bnhij", q, k,
                        preferred_element_type=f32) * decay
    o = jnp.einsum("bnhck,bnhkv->bnhcv",
                   (q.astype(f32) * jnp.exp(g)[..., None]).astype(
                       operand_dtype),
                   entering.astype(operand_dtype), preferred_element_type=f32)
    o = o + jnp.einsum("bnhij,bnhjv->bnhiv", scores.astype(operand_dtype),
                       written.astype(operand_dtype),
                       preferred_element_type=f32)
    return o.swapaxes(2, 3).reshape(bsz, t, h, -1)


# -- the kernel pair ----------------------------------------------------------

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(x, y, dims, dtype=jnp.float32):
    """``x`` and ``y`` in ``dtype``, contracted on ``dims`` (``_NN``:
    ``x y``, ``_NT``: ``x y^T``, ``_TN``: ``x^T y``), float32
    accumulation; a float32 product at ``HIGHEST``, as XLA's solve and
    its gradient take theirs; any other at ``DEFAULT``, named, so that
    no ``default_matmul_precision`` around the caller asks Mosaic for a
    float32 contraction of 16-bit operands, which it refuses."""
    highest = jnp.dtype(dtype) == jnp.float32
    return lax.dot_general(
        x.astype(dtype), y.astype(dtype), (dims, ((), ())),
        precision=(lax.Precision.HIGHEST if highest
                   else lax.Precision.DEFAULT),
        preferred_element_type=jnp.float32)


def _steps(size: int):
    """Row and column step of a ``[C, C]`` block."""
    return (lax.broadcasted_iota(jnp.int32, (size, size), 0),
            lax.broadcasted_iota(jnp.int32, (size, size), 1))


def _column(row, down, along):
    """A ``[1, C]`` row as a ``[C, 1]`` column, exactly: one select and a
    sum over the lanes (one nonzero a row)."""
    return jnp.sum(jnp.where(down == along, row, 0.0), axis=1, keepdims=True)


def _row(column, down, along):
    """A ``[C, 1]`` column as a ``[1, C]`` row, exactly."""
    return jnp.sum(jnp.where(down == along, column, 0.0), axis=0,
                   keepdims=True)


def _inverses(blocks, down, along):
    """``(I + A)^-1`` of each strictly lower ``[C, C]`` ``A`` of
    ``blocks``, float32, by merging inverted diagonal blocks (the module's
    docstring): at level ``s`` the blocks of ``s`` steps pair into blocks
    of ``2 s``, and ``A_s`` is ``A`` where the row lies in a pair's second
    block and the column in its first.  The levels are a chain of
    dependent products; the chains of several chunks go level by level
    side by side, in program order, so that the MXU takes one chunk's
    product while another's result is on its way."""
    size = blocks[0].shape[0]

    def joins(level: int):
        return (((down >> (level + 1)) == (along >> (level + 1)))
                & ((down >> level) & 1 == 1) & ((along >> level) & 1 == 0))

    eye = jnp.where(down == along, 1.0, 0.0)
    ts = [eye - jnp.where(joins(0), a, 0.0) for a in blocks]
    level = 1
    while 1 << level < size:
        joined = [_dot(jnp.where(joins(level), a, 0.0), t, _NN)
                  for a, t in zip(blocks, ts)]
        ts = [t - _dot(t, j, _NN) for t, j in zip(ts, joined)]
        level += 1
    return ts


def _chunk(q, k, gb, size: int):
    """What both kernels make of a head's chunk: the decay ``G`` (mask
    first), ``K K^T``, ``Q K^T``, ``g`` and ``beta`` as columns, the
    chunk's end, and the step indices; ``gb`` ``[2, C]`` holds ``g`` and
    ``beta`` as rows."""
    down, along = _steps(size)
    g_row, beta_row = gb[0:1, :], gb[1:2, :]
    g, beta = _column(g_row, down, along), _column(beta_row, down, along)
    # g_C [1, 1] by a sum over the lanes, not a slice: a slice keeps its
    # lane's offset, and Mosaic does not broadcast such a value down the
    # sublanes and along the lanes at once
    end = jnp.sum(jnp.where(along[:1] == size - 1, g_row, 0.0), axis=1,
                  keepdims=True)
    decay = jnp.exp(jnp.where(down >= along, g - g_row, -jnp.inf))
    kk = _dot(k, k, _NT, k.dtype)
    qk = _dot(q, k, _NT, q.dtype)
    return dict(down=down, along=along, g=g, beta=beta, end=end,
                decay=decay, kk=kk, qk=qk)


def _solved(c, k, v, t):
    """``W`` and ``U`` of a chunk, float32, from its ``T``."""
    w = _dot(t, c["beta"] * jnp.exp(c["g"]) * k.astype(jnp.float32), _NN)
    return w, _dot(t, c["beta"] * v.astype(jnp.float32), _NN)


def _body(interpret: bool, kernel):
    """``kernel(first, *refs)``, ``first`` true at a head's first grid
    step.  An interpreted body runs inside a ``pl.when`` that is always
    taken: the interpreter binds a kernel's equations on the enclosing
    trace's vma-typed values and refuses a literal beside a varying block,
    and a ``cond``'s branches are not typed
    (``flash_attention._visit_call``); it reads the grid's indices outside
    any branch.  The compiled kernel has no gate."""
    def cell(*refs):
        step = pl.program_id(1)
        pl.when(step >= 0 if interpret else True)(
            lambda: kernel(step == 0, *refs))
    return cell


def _delta_fwd_kernel(first, q_ref, k_ref, v_ref, gb_ref, o_ref, ent_ref,
                      t_ref, state_ref):
    """One (batch·head, block of chunks) step; the blocks in order.  Refs
    for ``J`` chunks of ``C`` steps: q, k ``[J C, K]``, v ``[J C, V]`` in
    the operand dtype; gb ``[J, 2, C]``: ``g`` and ``beta`` as rows; o
    ``[J C, V]`` float32; ent ``[J K, V]``, the states entering the
    chunks; t ``[J C, C]``, the chunks' ``(I + A)^-1``.  Scratch: the
    head's state ``[K, V]`` float32, alive over its chunks.  What no
    state touches — ``G``, the products of the chunk with itself, ``T``,
    ``W``, ``U`` — is made for every chunk of the block first: the
    chunks' inverses, each a chain of dependent products, interleave."""
    @pl.when(first)
    def _first_chunk():
        state_ref[:] = jnp.zeros(state_ref.shape, jnp.float32)

    f32 = jnp.float32
    count, size = gb_ref.shape[0], gb_ref.shape[2]
    dtype, dk = q_ref.dtype, q_ref.shape[1]
    rows = [slice(j * size, (j + 1) * size) for j in range(count)]
    qs, ks, vs = ([ref[r, :] for r in rows] for ref in (q_ref, k_ref, v_ref))
    cs = [_chunk(q, k, gb_ref[j], size)
          for j, (q, k) in enumerate(zip(qs, ks))]
    down, along = cs[0]["down"], cs[0]["along"]
    ts = _inverses([jnp.where(down > along, c["beta"] * c["kk"] * c["decay"],
                              0.0) for c in cs], down, along)
    solved = [_solved(c, k, v, t) for c, k, v, t in zip(cs, ks, vs, ts)]
    state = state_ref[:]
    for j, (r, q, k, c, t, (w, u)) in enumerate(
            zip(rows, qs, ks, cs, ts, solved)):
        t_ref[r, :] = t
        ent_ref[j * dk:(j + 1) * dk, :] = state
        written = (u - _dot(w, state, _NN, dtype)).astype(dtype)
        o_ref[r, :] = (
            _dot(q.astype(f32) * jnp.exp(c["g"]), state, _NN, dtype)
            + _dot(c["qk"] * c["decay"], written, _NN, dtype))
        to_end = k.astype(f32) * jnp.exp(c["end"] - c["g"])
        state = (jnp.exp(c["end"]) * state
                 + _dot(to_end, written, _TN, dtype))
    state_ref[:] = state


def _delta_bwd_chunk(q, k, v, do, gb, state, t, dleft):
    """One chunk of the backward: ``(dq, dk, dv, dg, d beta, dS)`` from
    ``dO`` and the gradient ``dleft`` of the state the chunk leaves; ``dg``
    and ``d beta`` as ``[1, C]`` rows, ``dS`` of the state entering it."""
    f32 = jnp.float32
    dtype, size = q.dtype, q.shape[0]
    c = _chunk(q, k, gb, size)
    down, along, beta, decay = c["down"], c["along"], c["beta"], c["decay"]
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    keep, head = jnp.exp(c["end"]), jnp.exp(c["g"])         # exp(g_C), exp(g)
    to_end = jnp.exp(c["end"] - c["g"])                     # exp(g_C - g)
    w, u = _solved(c, k, v, t)
    written = u - _dot(w, state, _NN, dtype)                # V'
    scores = c["qk"] * decay                                # P

    # the chunk's output and the state it leaves, back to V' and S
    dhead_q = _dot(do, state, _NT, dtype)                   # d(Q exp(g))
    dscores = jnp.where(down >= along, _dot(do, written, _NT, dtype), 0.0)
    dto_end = _dot(written, dleft, _NT, dtype)              # d(K exp(g_C-g))
    dwritten = (_dot(scores, do, _TN, dtype)
                + _dot(kf * to_end, dleft, _NN, dtype))
    dstate = (keep * dleft + _dot(qf * head, do, _TN, dtype)
              - _dot(w, dwritten, _TN, dtype))
    # through V' = U - W S and the solve
    dw = -_dot(dwritten, state, _NT, dtype)
    drk = _dot(t, dw, _TN)                                  # T^T dW
    drv = _dot(t, dwritten, _TN)                            # T^T dU
    da = jnp.where(down > along,
                   -(_dot(drk, w, _NT) + _dot(drv, u, _NT)), 0.0)
    dkk = da * beta * decay
    dqk = dscores * decay
    dq = _dot(dqk, k, _NN, dtype) + dhead_q * head
    dk = (_dot(dkk, k, _NN, dtype) + _dot(dkk, k, _TN, dtype)
          + _dot(dqk, q, _TN, dtype) + drk * (beta * head)
          + dto_end * to_end)
    dv = beta * drv

    # d beta and dg: a column a step (rows of the [C, C] blocks and the
    # lanes of the [C, d] ones), and dD * D's sums over its rows
    sum_rows = lambda x: jnp.sum(x, axis=1, keepdims=True)
    rk_k = sum_rows(drk * kf)
    dbeta = sum_rows(da * c["kk"] * decay) + head * rk_k \
        + sum_rows(drv * vf)
    dd = dkk * c["kk"] + dqk * c["qk"]                      # dD * D
    dto_end_k = to_end * sum_rows(dto_end * kf)
    dg = (sum_rows(dd) + head * (beta * rk_k + sum_rows(dhead_q * qf))
          - dto_end_k)
    # the chunk's end: exp(g_C) of the state kept and of every K_C row
    left = jnp.sum(jnp.sum(dleft * state, axis=1, keepdims=True), axis=0,
                   keepdims=True)
    at_end = jnp.sum(dto_end_k, axis=0, keepdims=True) + keep * left
    dg_row = (_row(dg, down, along) - jnp.sum(dd, axis=0, keepdims=True)
              + jnp.where(along[:1] == size - 1, at_end, 0.0))
    return dq, dk, dv, dg_row, _row(dbeta, down, along), dstate


def _delta_bwd_kernel(first, q_ref, k_ref, v_ref, gb_ref, ent_ref, t_ref,
                      do_ref, dq_ref, dk_ref, dv_ref, dgb_ref, dstate_ref):
    """One (batch·head, block of chunks) step of the backward; the blocks,
    and the chunks in each, from the last.  Refs as the forward's, and do
    ``[J C, V]`` in the operand dtype; dq, dk, dv in their primals' shapes
    and dtype; dgb ``[J, 2, C]``: ``dg`` and ``d beta`` as rows.  Scratch:
    the gradient of the head's state ``[K, V]`` float32.  No chain of
    dependent products as long as the forward's ``T``: staged across the
    block's chunks as the forward's are, it was no faster on the chip."""
    @pl.when(first)
    def _last_chunk():
        dstate_ref[:] = jnp.zeros(dstate_ref.shape, jnp.float32)

    count, size = gb_ref.shape[0], gb_ref.shape[2]
    dk = q_ref.shape[1]
    dleft = dstate_ref[:]
    for j in reversed(range(count)):
        rows = slice(j * size, (j + 1) * size)
        dq, dkey, dv, dg, dbeta, dleft = _delta_bwd_chunk(
            q_ref[rows, :], k_ref[rows, :], v_ref[rows, :], do_ref[rows, :],
            gb_ref[j], ent_ref[j * dk:(j + 1) * dk, :], t_ref[rows, :],
            dleft)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dkey.astype(dk_ref.dtype)
        dv_ref[rows, :] = dv.astype(dv_ref.dtype)
        dgb_ref[j, 0:1, :] = dg
        dgb_ref[j, 1:2, :] = dbeta
    dstate_ref[:] = dleft


def chunks_a_step(chunks: int) -> int:
    """Chunks a grid step takes: the most, up to :data:`CHUNKS_A_STEP`,
    that divide a head's chunks."""
    return max(j for j in range(1, CHUNKS_A_STEP + 1) if chunks % j == 0)


def _layout(q, v, gb, backward: bool):
    """The grid (batch·head, block of chunks; the backward walks the
    blocks from the last) and the specs of what the kernels read and
    write: q, k (and their gradients) ``[B H, T, K]``; v, o ``[B H, T,
    V]``; gb ``[B H, N, 2, C]``; the entering states ``[B H, N K, V]``;
    each chunk's ``T`` ``[B H, N C, C]``.  One head a step: a block of
    several pads ``V`` to whole registers in VMEM, and Mosaic refuses a
    head's view of it."""
    bh, t, dk = q.shape
    n, size = gb.shape[1], gb.shape[3]
    dv = v.shape[2]
    if t != n * size:
        raise ValueError(f"{t} steps are not {n} chunks of {size}")
    count = chunks_a_step(n)
    blocks = n // count
    at = (lambda c: blocks - 1 - c) if backward else (lambda c: c)
    rows = lambda height, width: pl.BlockSpec(
        (None, count * height, width), lambda i, c: (i, at(c), 0))
    specs = dict(keys=rows(size, dk), values=rows(size, dv),
                 gb=pl.BlockSpec((None, count, 2, size),
                                 lambda i, c: (i, at(c), 0, 0)),
                 states=rows(dk, dv), inverses=rows(size, size))
    shapes = dict(states=(bh, n * dk, dv), inverses=(bh, n * size, size))
    scratch = pltpu.VMEM((dk, dv), jnp.float32)
    return (bh, blocks), specs, shapes, scratch


def _compiler_params(interpret: bool):
    """The state is carried over a head's chunks: the chunk axis is
    ``arbitrary``."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


# jitted: every layer of a model calls the kernels at the same shapes, so
# that the layers share one trace and lowering a program (as
# ``ssd.py``'s pair does: traced a call, they cost the granite cell's
# set-up 108 s)
_kernel_jit = functools.partial(jax.jit, static_argnames=("interpret",))


@_kernel_jit
def delta_forward(q, k, v, gb, interpret: bool = False):
    """Pallas forward.  ``q``, ``k`` ``[B H, T, K]`` and ``v``
    ``[B H, T, V]`` in the operand dtype; ``gb`` ``[B H, T / C, 2, C]``
    float32, ``g`` (the cumulative log decay inside each chunk) and
    ``beta``.  Returns ``o`` ``[B H, T, V]`` float32, the states entering
    the chunks ``[B H, T / C, K, V]`` and each chunk's ``(I + A)^-1``
    ``[B H, T / C, C, C]``, both float32."""
    f32 = jnp.float32
    grid, specs, shapes, scratch = _layout(q, v, gb, backward=False)
    return pl.pallas_call(
        _body(interpret, _delta_fwd_kernel),
        grid=grid,
        in_specs=[specs["keys"], specs["keys"], specs["values"],
                  specs["gb"]],
        out_specs=[specs["values"], specs["states"], specs["inverses"]],
        out_shape=[_sds(v.shape, f32, q, k, v, gb),
                   _sds(shapes["states"], f32, q, k, v, gb),
                   _sds(shapes["inverses"], f32, q, k, v, gb)],
        scratch_shapes=[scratch],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_DELTA_FWD,
    )(q, k, v, gb)


@_kernel_jit
def delta_backward(q, k, v, gb, entering, inverses, do,
                   interpret: bool = False):
    """Pallas backward: ``(dq, dk, dv, dgb)`` from ``do`` ``[B H, T, V]``
    (rounded to the operand dtype on its way in) and the forward's
    residuals, each gradient in its primal's shape and dtype; ``dgb``
    holds ``dg`` (per step, before the cumulative sum's transpose) and
    ``d beta``."""
    grid, specs, _, scratch = _layout(q, v, gb, backward=True)
    like = (q, k, v, gb, entering, inverses, do)
    return pl.pallas_call(
        _body(interpret, _delta_bwd_kernel),
        grid=grid,
        in_specs=[specs["keys"], specs["keys"], specs["values"],
                  specs["gb"], specs["states"], specs["inverses"],
                  specs["values"]],
        out_specs=[specs["keys"], specs["keys"], specs["values"],
                   specs["gb"]],
        out_shape=[_sds(q.shape, q.dtype, *like),
                   _sds(k.shape, k.dtype, *like),
                   _sds(v.shape, v.dtype, *like),
                   _sds(gb.shape, gb.dtype, *like)],
        scratch_shapes=[scratch],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_DELTA_BWD,
    )(q, k, v, gb, entering, inverses, do.astype(q.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pair(q, k, v, gb, interpret):
    return delta_forward(q, k, v, gb, interpret=interpret)[0]


def _pair_fwd(q, k, v, gb, interpret):
    o, entering, inverses = delta_forward(q, k, v, gb, interpret=interpret)
    return o, (q, k, v, gb, entering, inverses)


def _pair_bwd(interpret, residuals, do):
    return delta_backward(*residuals, do, interpret=interpret)


_pair.defvjp(_pair_fwd, _pair_bwd)


def rule_kernel(q, k, v, log_alpha, beta, size: int, operand_dtype,
                interpret: bool = False):
    """The chunked rule by the kernel pair, differentiable; arguments and
    result as :func:`rule_xla`'s.  XLA lays ``q``, ``k``, ``v`` out head
    by head (with their cast), makes ``g`` by the cumulative sum, and lays
    ``o`` back."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    n = t // size
    heads = lambda x: x.astype(operand_dtype).swapaxes(1, 2).reshape(
        bsz * h, t, x.shape[-1])
    chunks = lambda x: x.astype(f32).swapaxes(1, 2).reshape(bsz * h, n, size)
    gb = jnp.stack([jnp.cumsum(chunks(log_alpha), axis=-1), chunks(beta)],
                   axis=2)                                  # [B H, N, 2, C]
    o = _pair(heads(q), heads(k), heads(v), gb, interpret)
    return o.reshape(bsz, h, t, -1).swapaxes(1, 2)


def delta_rule_chunked(q, k, v, log_alpha, beta, chunk: int,
                       operand_dtype=jnp.float32):
    """``q``, ``k`` ``[B, T, H, K]``; ``v`` ``[B, T, H, V]``;
    ``log_alpha`` (``<= 0``) and ``beta`` ``[B, T, H]``.  Returns ``o``
    ``[B, T, H, V]`` float32.  A length ``chunk`` does not divide is padded
    with steps of ``k = 0``, ``beta = 0`` and ``alpha = 1``, which leave the
    state alone."""
    t = q.shape[1]
    size = min(chunk, t)
    pad = -t % size
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, log_alpha, beta))
    if kernel_fits(jax.default_backend(), size, q.shape[3], v.shape[3]):
        o = rule_kernel(q, k, v, log_alpha, beta, size, operand_dtype)
    else:
        o = rule_xla(q, k, v, log_alpha, beta, size, operand_dtype)
    return o[:, :t]
