"""The selective state-space recurrence of Mamba-2 in its chunked form
(state-space duality, Dao & Gu 2024, arXiv:2405.21060).

Per head, with a state ``S`` of ``[head_dim, d_state]`` and ``S_0 = 0``::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t

Step by step that is ``T`` sequential updates of the state.  Cut into
chunks of ``L`` steps it is three batched matrix products and a short
recurrence: inside a chunk, ``y`` is a causal ``[L, L]`` score block
``(C B^T) * decay`` times ``dt * x``; each chunk's contribution to the
state is one product over its steps; the states entering the chunks follow
by a scan over the ``T / L`` chunks; and what the entering state gives
each step is one more product.  The matrix units take the products, with
operands in ``operand_dtype`` and float32 accumulation; the decays, the
cumulative sums and the carried state stay float32.

Two paths compute it, picked by :func:`kernel_fits` from the platform and
the shapes and nothing else.  :func:`chunks_xla` is plain ``jax.numpy``
and autodiff: the path of every backend but the TPU and of every shape
the kernels do not tile, and the kernels' oracle in the tests.  On a TPU
the whole chunked computation is a Pallas kernel pair under one
``jax.custom_vjp`` (:func:`chunks_kernel`), walking the chunks in order
with the state of every head in VMEM.  Per chunk and head, with ``cum``
the cumulative log decay inside the chunk and ``E`` the entering state::

    S = C B^T                          once a group and chunk, kept in VMEM
    D = exp(cum_l - cum_s) * [l >= s]  float32, the mask before the exponential
    W = (S * D) -> operand_dtype       the one rounding, where XLA's path has it
    Y = W (dt x) + exp(cum) * (C E^T)
    E' = exp(cum_L) E + (dt x)^T (B * exp(cum_L - cum))

and backward, walking the chunks from the last, from ``dY`` and the
gradient ``dE'`` of the state the chunk left: ``dW = dY (dt x)^T``,
``d(dt x) = W^T dY + (B * exp(cum_L - cum)) dE'^T``, ``dS = sum_h dW * D``
over the group's heads (in VMEM: a chunk's head blocks are the grid's
minor axis), ``dC = dS B + (exp(cum) * dY) E``, ``dB = dS^T C +
exp(cum_L - cum) * ((dt x) dE')``, ``dE = exp(cum_L) dE' + (exp(cum) *
dY)^T C``.  ``D`` and ``W`` are made again from ``cum``, ``B``, ``C``: the
residuals are the kernels' inputs, ``y`` and the entering states
(``[B, T / L, H, P, N]`` float32), nothing of size ``L^2``; no ``[L, L]``
block, chunk state or carried term is an XLA operation.

``d cum`` takes no block of its own.  Every term of ``y_l`` carries the
factor ``exp(cum_l)``, every use of ``(dt x)_s`` the factor
``exp(-cum_s)``, and the whole of ``E'`` the factor ``exp(cum_L)``, so ::

    d cum_l = sum_p dY[l, p] y[l, p] - sum_p (dt x)[l, p] d(dt x)[l, p]
              + [l = L] sum_pn dE'[p, n] E'[p, n]

— sums over a head's own lanes of arrays that exist anyway (as ``delta =
rowsum(do * o)`` in the flash backward), taken by XLA; only the last term
is taken in the kernel, as lane-partial sums.  The first two cancel pair
by pair for the steps of one chunk (a diagonal term is in both, and at the
published decays it is all there is), so they have to be sums over the
very values the kernels' products took and gave: ``dY`` as rounded on its
way in and ``d(dt x)`` before it is rounded; ``lax.reduce_precision``
where a convert to the operand dtype and back is one XLA may drop; and the
backward's ``W^T`` the forward's ``W`` bit for bit (its ``S^T`` is the
forward's ``S`` turned, not ``B C^T`` summed in another order).  On the
chip, anything less read 24 % off in ``d dt`` (PERF.md §6, PR 32).

Dtypes, the mask before the exponential, the chunk length and the single
rounding of ``W`` are :func:`chunks_xla`'s.  The two paths differ by the
order of float32 sums and in three roundings to ``operand_dtype``: ``dW``
(XLA's transposed product rounds it; the kernel keeps float32), the
chunk's decay to its end (the kernel rounds ``B * exp(cum_L - cum)`` where
XLA rounds ``dt x * exp(cum_L - cum)``: the same product, the factor on
the operand whose steps lie along the lanes), and ``d cum`` (above: through
the rounded ``W`` where autodiff goes through the unrounded ``S * D``).

How the kernels keep off the cross-lane unit (it, not the MXU, set the
flash forward's pace: PERF.md §6, PR 30).  A ``[L, L]`` block is worked in
``[128, 128]`` sub-blocks, those above the diagonal skipped.  ``cum``
arrives twice, as rows (steps on lanes) and as columns (steps on
sublanes); one lane broadcast of a head's column is the cross-lane work
of a head and chunk.  Every block is built in the orientation its
product takes it — the backward makes ``D^T`` and ``dW^T = (dt x) dY^T``
directly, the latter as a product with its operands exchanged, turns ``S``
once a group, and takes ``B^T`` and ``C^T`` beside ``B`` and ``C`` — so
the one operand transposed a head is ``B^T * exp(cum_L - cum)`` into
``d(dt x)``.
Heads narrower than a register's 128 lanes share one: a state is held
transposed, ``[N, heads * P]``, so a register's heads lie side by side in
every product's lanes, each head's result is valid in its own lanes, and
a lane select puts a register together; where a product sums over the
lanes, the other heads' lanes of one operand are zeroed.  The MXU does
either at no cost (at 64 lanes it is half filled anyway) and every load
and store stays lane-dense.
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

__all__ = ["ssd_chunked", "chunks_xla", "chunks_kernel", "ssd_forward",
           "ssd_backward", "kernel_fits", "head_block"]

_LANES = 128

# What a grid step's operand and result blocks (two-deep) may take of the
# 16 MiB a v5e kernel may scope; the states of every head (2 MiB at the
# published sizes), the [L, L] float32 scratch blocks and Mosaic's own
# temporaries need the rest.  At the published sizes (chunk 256, heads of
# 64 over a state of 128, bf16) it admits 16 heads a step: 5.2 MiB.  On
# the chip 8, 16 and 32 heads a step took 0.451, 0.434 and 0.434 ms
# forward and 1.078, 1.051, 1.038 ms backward a call (PERF.md §6, PR 32).
BLOCKS_VMEM_BUDGET = 6 * 2 ** 20


def head_block(chunk: int, d_state: int, head_dim: int, heads: int,
               groups: int, itemsize: int) -> int | None:
    """Heads a grid step of the kernels takes: the most that lie in one
    group, fill whole 128-lane registers and keep the step's blocks inside
    :data:`BLOCKS_VMEM_BUDGET`; ``None`` where no count does."""
    if heads % groups or _LANES % head_dim:
        return None
    per_group = heads // groups
    # the backward's blocks, the larger set: dt*x and dy in, d(dt*x)
    # (float32) out, the entering state (float32) in
    a_head = 2 * head_dim * (chunk * (2 * itemsize + 4) + d_state * 4)
    for hb in range(per_group, 0, -1):
        if (per_group % hb == 0 and hb * head_dim % _LANES == 0
                and hb * a_head <= BLOCKS_VMEM_BUDGET):
            return hb
    return None


def kernel_fits(platform: str, chunk: int, d_state: int, head_dim: int,
                heads: int, groups: int, itemsize: int) -> bool:
    """The rule that picks the kernel pair, from what the code can
    observe and nothing else: a TPU; a chunk and a state of whole 128-lane
    registers; and heads that :func:`head_block` can lay lane-dense inside
    its VMEM budget.  Everything else (the CPU, a toy chunk, a sequence
    shorter than a chunk) takes :func:`chunks_xla`."""
    return (platform == "tpu" and chunk % _LANES == 0
            and d_state % _LANES == 0
            and head_block(chunk, d_state, head_dim, heads, groups,
                           itemsize) is not None)


def chunks_xla(xdt, cum, b, c):
    """The chunked computation as XLA products.  ``xdt`` ``[B, C, L, G, R,
    P]`` float32; ``cum`` ``[B, C, G, R, L]`` float32; ``b``, ``c``
    ``[B, C, L, G, N]`` in the operand dtype.  Returns ``y``
    ``[B, C, L, G, R, P]`` float32."""
    f32, operand_dtype = jnp.float32, b.dtype
    size = cum.shape[-1]
    # inside a chunk: y_l = sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) xdt_s.
    # The mask goes in before the exponential: above the diagonal the
    # difference is positive and as large as the chunk's whole decay
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weights = (scores[:, :, :, None] * decay).astype(operand_dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", weights,
                   xdt.astype(operand_dtype), preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)               # [B, C, G, R, L]
    fed = (xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None]) \
        .astype(operand_dtype)
    chunk_states = jnp.einsum("bcsgrp,bcsgn->bcgrpn", fed, b,
                              preferred_element_type=f32)

    # the state entering each chunk: the short recurrence, float32
    def enter(state, inputs):
        decay_c, own = inputs
        return decay_c[..., None, None] * state + own, state

    chunk_decay = jnp.exp(cum[..., -1])                 # [B, C, G, R]
    _, entering = lax.scan(
        enter, jnp.zeros_like(chunk_states[:, 0]),
        (chunk_decay.swapaxes(0, 1), chunk_states.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                  # [B, C, G, R, P, N]

    # what the entering state gives each step of its chunk
    carried = jnp.einsum("bclgn,bcgrpn->bclgrp", c,
                         entering.astype(operand_dtype),
                         preferred_element_type=f32)
    return y + carried * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]


# -- the kernel pair ----------------------------------------------------------

def _visible(blocks: int):
    """Sub-blocks ``(i, j)`` of a chunk's ``[L, L]`` block that hold a
    causal pair: on and under the diagonal."""
    return [(i, j) for i in range(blocks) for j in range(i + 1)]


def _block(i: int, j: int) -> int:
    """Where ``_visible`` lists sub-block ``(i, j)``."""
    return i * (i + 1) // 2 + j


def _rows(i: int, sub: int):
    return slice(i * sub, (i + 1) * sub)


def _registers(x_ref, sub: int):
    """Of each 128-lane register of the step's block: its place among all
    the heads' registers (the states' scratch is indexed by it) and its
    lanes in the block."""
    count = x_ref.shape[1] // sub
    return [(pl.program_id(2) * count + k, _rows(k, sub))
            for k in range(count)]


def _nt(x, y):
    """``x y^T``, float32 accumulation."""
    return lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _scores(c_ref, b_ref, s_ref, sub: int):
    """``S = C B^T`` of the step's group and chunk into ``s_ref``, one
    visible sub-block after another."""
    for k, (i, j) in enumerate(_visible(c_ref.shape[0] // sub)):
        s_ref[k] = _nt(c_ref[_rows(i, sub), :], b_ref[_rows(j, sub), :])


def _decay(col_of, row_of, i: int, j: int, transposed: bool = False):
    """``D_ij = exp(cum_l - cum_s) * [l >= s]`` for ``l`` in sub-block
    ``i`` and ``s`` in sub-block ``j``, float32, the mask before the
    exponential; or its transpose (``s`` on sublanes, ``l`` on lanes).
    ``col_of[i]`` is ``cum`` of sub-block ``i`` down the sublanes, the
    same in every lane; ``row_of[i]`` the same values along the lanes."""
    diff = row_of[i] - col_of[j] if transposed else col_of[i] - row_of[j]
    if i == j:
        down = lax.broadcasted_iota(jnp.int32, diff.shape, 0)
        along = lax.broadcasted_iota(jnp.int32, diff.shape, 1)
        diff = jnp.where(along >= down if transposed else down >= along,
                         diff, -jnp.inf)
    return jnp.exp(diff)


def _head_cum(row_ref, col_ref, head: int, sub: int):
    """One head's ``cum`` as ``_decay`` takes it: per sub-block, down the
    sublanes (the lane broadcast: the one cross-lane move a head takes)
    and along the lanes."""
    blocks = row_ref.shape[1] // sub
    col_of = [jnp.broadcast_to(col_ref[_rows(i, sub), head:head + 1],
                               (sub, sub)) for i in range(blocks)]
    row_of = [row_ref[head:head + 1, _rows(i, sub)] for i in range(blocks)]
    return col_of, row_of


def _own_lanes(x, slot: int, head_dim: int):
    """``x`` with the lanes of every head but the ``slot``-th of its
    register zeroed; ``x`` itself where a head has the register alone."""
    if x.shape[-1] == head_dim:
        return x
    # 16-bit rows lie two to a sublane: masked as the 32-bit words they
    # are packed in, which spares unpacking them for the select
    bits = x if x.dtype.itemsize == 4 else pltpu.bitcast(x, jnp.uint32)
    lane = lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    own = (lane >= slot * head_dim) & (lane < (slot + 1) * head_dim)
    bits = jnp.where(own, bits, jnp.zeros_like(bits))
    return bits if x.dtype.itemsize == 4 else pltpu.bitcast(bits, x.dtype)


def _merge(parts, head_dim: int):
    """One register from its heads' results: ``parts[q]`` is valid in the
    ``q``-th head's lanes."""
    out = parts[-1]
    lane = lax.broadcasted_iota(jnp.int32, out.shape, 1)
    for slot in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (slot + 1) * head_dim, parts[slot], out)
    return out


def _to_end(row_ref, bt_ref, head: int, dtype):
    """Of one head: ``exp(cum_L)`` ``[1, 1]``; ``exp(cum_L - cum_s)`` along
    the lanes ``[1, L]``; and ``B^T`` times it, rounded, ``[N, L]``: the
    operand that makes the chunk's own part of the state it leaves."""
    row = row_ref[head:head + 1, :]
    end = row[:, row.shape[1] - 1:]
    te = jnp.exp(end - row)
    return jnp.exp(end), te, (bt_ref[:] * te).astype(dtype)


def _ssd_fwd_kernel(x_ref, row_ref, col_ref, b_ref, c_ref, bt_ref,
                    y_ref, ent_ref, s_ref, state_ref, *, sub: int,
                    head_dim: int, steps_a_group: int):
    """One (batch, chunk, head block) step; the chunks in order.  Refs:
    x/y ``[L, hb * P]``; ``cum`` as rows ``[hb, L]`` and as columns
    ``[L, hb]``; b/c ``[L, N]`` and ``B^T`` ``[N, L]`` (float32) of the
    block's group; ent ``[registers, N, 128]``, the states entering the
    chunk, transposed, a register's heads side by side.  Scratch,
    float32: ``S`` ``[visible, sub, sub]``, made on a group's first step
    and used by all of them; the state of every head
    ``[H * P / 128, N, 128]``, alive over a batch row's chunks.  A row of
    sub-blocks goes into its product side by side, so the sum over them
    is the MXU's."""
    @pl.when(pl.program_id(2) % steps_a_group == 0)
    def _group_scores():
        _scores(c_ref, b_ref, s_ref, sub)

    dtype = x_ref.dtype
    blocks = x_ref.shape[0] // sub
    in_register = sub // head_dim
    for register, (at, lanes) in enumerate(_registers(x_ref, sub)):
        @pl.when(pl.program_id(1) == 0)
        def _first_chunk():
            state_ref[at] = jnp.zeros(state_ref.shape[1:], jnp.float32)

        state = state_ref[at]                               # [N, 128]
        ent_ref[register] = state
        x = x_ref[:, lanes]
        from_state = jnp.dot(c_ref[:], state.astype(dtype),
                             preferred_element_type=jnp.float32)
        ys, states = [], []
        for slot in range(in_register):
            head = register * in_register + slot
            col_of, row_of = _head_cum(row_ref, col_ref, head, sub)
            y = []
            for i in range(blocks):
                w = jnp.concatenate(
                    [s_ref[_block(i, j)] * _decay(col_of, row_of, i, j)
                     for j in range(i + 1)], axis=1).astype(dtype)
                y.append(jnp.dot(w, x[:(i + 1) * sub],
                                 preferred_element_type=jnp.float32)
                         + jnp.exp(col_of[i]) * from_state[_rows(i, sub)])
            ys.append(y)
            keep, _, bt = _to_end(row_ref, bt_ref, head, dtype)
            states.append(keep * state + jnp.dot(
                bt, x, preferred_element_type=jnp.float32))
        for i in range(blocks):
            y_ref[_rows(i, sub), lanes] = _merge([y[i] for y in ys],
                                                 head_dim)
        state_ref[at] = _merge(states, head_dim)


def _ssd_bwd_kernel(x_ref, dy_ref, row_ref, col_ref, b_ref, c_ref, bt_ref,
                    ct_ref, ent_ref, dx_ref, end_ref, db_ref, dc_ref,
                    dbt_ref, st_ref, dst_ref, dstate_ref, *, sub: int,
                    head_dim: int, steps_a_group: int):
    """One (batch, chunk, head block) step of the backward; the chunks
    from the last.  Refs as the forward's, and dy/dx ``[L, hb * P]`` (dx
    float32), ``C^T`` ``[N, L]``; end ``[hb, 128]``: lane partials of
    ``sum dE' E'``; db/dc ``[L, N]`` and ``dB^T`` ``[N, L]`` float32, one
    block a group, summed over its steps in place.  Scratch, float32:
    ``S^T`` and ``dS^T`` (summed over the group's heads)
    ``[visible, sub, sub]``; the gradient of every head's state
    ``[H * P / 128, N, 128]``."""
    step = pl.program_id(2) % steps_a_group

    @pl.when(step == 0)
    def _group_scores():
        # S as the forward made it, turned: the backward's W^T has to be
        # the forward's W bit for bit (ssd_backward, on d cum), which
        # B C^T, summed in another order, would not give
        _scores(c_ref, b_ref, st_ref, sub)
        for k in range(st_ref.shape[0]):
            st_ref[k] = st_ref[k].T
        dst_ref[:] = jnp.zeros_like(dst_ref)
        dc_ref[:] = jnp.zeros_like(dc_ref)
        dbt_ref[:] = jnp.zeros_like(dbt_ref)

    dtype = x_ref.dtype
    blocks = x_ref.shape[0] // sub
    in_register = sub // head_dim
    for register, (at, lanes) in enumerate(_registers(x_ref, sub)):
        @pl.when(pl.program_id(1) == 0)
        def _last_chunk():
            dstate_ref[at] = jnp.zeros(dstate_ref.shape[1:], jnp.float32)

        dleft = dstate_ref[at]                  # dE' of these heads [N, 128]
        dleft_in = dleft.astype(dtype)          # as the products take it
        state = ent_ref[register]
        x, dy = x_ref[:, lanes], dy_ref[:, lanes]
        dxs, scales, keeps = [], [], []
        for slot in range(in_register):
            head = register * in_register + slot
            col_of, row_of = _head_cum(row_ref, col_ref, head, sub)
            own_dy = _own_lanes(dy, slot, head_dim)
            # the chunk's own part of the state it left: B^T, decayed to
            # the chunk's end, times dt*x
            keep, te, bt = _to_end(row_ref, bt_ref, head, dtype)
            own_dleft = _own_lanes(dleft_in, slot, head_dim)
            from_left = lax.dot_general(
                bt, dleft_in, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [L, 128]
            dbt_ref[:] += _nt(own_dleft, x) * te
            own = jnp.dot(bt, x, preferred_element_type=jnp.float32)
            # sum dE' E': dE' as the products above took it (rounded)
            # against the chunk's own part, so that this and d(dt x)'s
            # row sums cancel in d cum as they do in exact arithmetic
            end_ref[head:head + 1, :] = _own_lanes(jnp.sum(
                dleft * (keep * state)
                + own_dleft.astype(jnp.float32) * own, 0, keepdims=True),
                slot, head_dim)
            dx = []
            for j in range(blocks):
                wt = []
                for i in range(j, blocks):
                    k = _block(i, j)
                    dt = _decay(col_of, row_of, i, j, transposed=True)
                    dst_ref[k] += _nt(x[_rows(j, sub)],
                                      own_dy[_rows(i, sub)]) * dt
                    wt.append(st_ref[k] * dt)
                dx.append(jnp.dot(jnp.concatenate(wt, axis=1).astype(dtype),
                                  dy[j * sub:],
                                  preferred_element_type=jnp.float32)
                          + from_left[_rows(j, sub)])
            dxs.append(dx)
            keeps.append(jnp.broadcast_to(keep, (1, sub)))
            scales.append(jnp.concatenate(
                [jnp.exp(col_of[i]) for i in range(blocks)], axis=0))
        # what the entering state gave y: exp(cum) * (C E^T)
        g = (dy.astype(jnp.float32) * _merge(scales, head_dim)).astype(dtype)
        dc_ref[:] += _nt(g, state.astype(dtype))
        dstate_ref[at] = _merge(keeps, head_dim) * dleft + jnp.dot(
            ct_ref[:], g, preferred_element_type=jnp.float32)
        for j in range(blocks):
            dx_ref[_rows(j, sub), lanes] = _merge(
                [dx[j] for dx in dxs], head_dim)

    @pl.when(step == steps_a_group - 1)
    def _group_gradients():
        db = [None] * blocks
        for k, (i, j) in enumerate(_visible(blocks)):
            dst = dst_ref[k].astype(dtype)                  # [s, l]
            dc_ref[_rows(i, sub), :] += lax.dot_general(
                dst, b_ref[_rows(j, sub), :], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            part = jnp.dot(dst, c_ref[_rows(i, sub), :],
                           preferred_element_type=jnp.float32)
            db[j] = part if db[j] is None else db[j] + part
        for j in range(blocks):
            db_ref[_rows(j, sub), :] = db[j]


def _layout(xdt, cum, b, c, hb: int | None, backward: bool):
    """The grid (batch, chunk, head block; the backward walks the chunks
    from the last), what both kernels read, laid out for it, and its
    specs: ``dt x`` (and ``y``, ``dy``) ``[B, T, H * P]``; ``cum`` as rows
    ``[B, C, H / hb, hb, L]`` and as columns ``[B, H / hb, T, hb]``;
    ``b``, ``c`` ``[B, T, G * N]`` and transposed ``[B, C, G * N, L]``;
    the states ``[B, C, H * P / 128, N, 128]``."""
    bsz, t, hp = xdt.shape
    nc, h, size = cum.shape[1:]
    p, g, n = hp // h, b.shape[2], b.shape[3]
    if hb is None:
        hb = head_block(size, n, p, h, g, xdt.dtype.itemsize)
    sub = min(size, _LANES)
    if (hb is None or (h // g) % hb or t != nc * size or size % sub
            or sub % p or hb * p % sub):
        raise ValueError(
            f"the kernels do not tile {h} heads of {p} in {g} groups over "
            f"chunks of {size} ({t} steps, {hb} heads a step)")
    steps_a_group = h // g // hb
    registers = hb * p // sub
    at = (lambda c: nc - 1 - c) if backward else (lambda c: c)
    rows = cum.reshape(bsz, nc, h // hb, hb, size)
    cols = rows.transpose(0, 2, 1, 4, 3).reshape(bsz, h // hb, t, hb)
    flat = lambda v: v.reshape(bsz, t, -1)
    turned = lambda v: v.reshape(bsz, nc, size, g * n).swapaxes(2, 3)
    # B^T float32: it is scaled by the decay before it is an operand
    arrays = dict(xdt=xdt, rows=rows, cols=cols, b=flat(b),
                  c=flat(c), bt=turned(b).astype(jnp.float32),
                  ct=turned(c))
    specs = dict(
        wide=pl.BlockSpec((None, size, hb * p),
                          lambda i, c, k: (i, at(c), k)),
        rows=pl.BlockSpec((None, None, None, hb, size),
                          lambda i, c, k: (i, at(c), k, 0, 0)),
        ends=pl.BlockSpec((None, None, None, hb, sub),
                          lambda i, c, k: (i, at(c), k, 0, 0)),
        cols=pl.BlockSpec((None, None, size, hb),
                          lambda i, c, k: (i, k, at(c), 0)),
        group=pl.BlockSpec((None, size, n),
                           lambda i, c, k: (i, at(c), k // steps_a_group)),
        turned=pl.BlockSpec((None, None, n, size),
                            lambda i, c, k: (i, at(c), k // steps_a_group,
                                             0)),
        states=pl.BlockSpec((None, None, registers, n, sub),
                            lambda i, c, k: (i, at(c), k, 0, 0)))
    static = dict(sub=sub, head_dim=p, steps_a_group=steps_a_group)
    visible = len(_visible(size // sub))
    scratch = dict(
        block=pltpu.VMEM((visible, sub, sub), jnp.float32),
        states=pltpu.VMEM((h * p // sub, n, sub), jnp.float32))
    states = (bsz, nc, h * p // sub, n, sub)
    return ((bsz, nc, h // hb), arrays, specs, static, scratch, states)


def _compiler_params(interpret: bool):
    """The state is carried over a batch row's chunks and a group's
    scores over its head blocks: both inner grid dims are
    ``arbitrary``."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))


# jitted: every layer of a model calls the kernels at the same shapes, and
# a kernel's body (16 heads unrolled) is seconds to trace and lower; as one
# jitted function it is traced and lowered once a program (the granite
# cell's step: 27 kernel calls, 108 s to trace and lower without, PERF.md §6)
_kernel_jit = functools.partial(
    jax.jit, static_argnames=("head_block", "interpret"))


@_kernel_jit
def ssd_forward(xdt, cum, b, c, head_block: int | None = None,
                interpret: bool = False):
    """Pallas forward.  ``xdt`` ``[B, T, H * P]`` and ``b``, ``c``
    ``[B, T, G, N]`` in the operand dtype; ``cum`` ``[B, T / L, H, L]``
    float32.  Returns ``y`` ``[B, T, H * P]`` float32 and the states
    entering the chunks, laid out for the backward
    (``[B, T / L, H * P / 128, N, 128]`` float32)."""
    grid, arrays, specs, static, scratch, states = _layout(
        xdt, cum, b, c, head_block, backward=False)
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, **static),
        grid=grid,
        in_specs=[specs["wide"], specs["rows"], specs["cols"],
                  specs["group"], specs["group"], specs["turned"]],
        out_specs=[specs["wide"], specs["states"]],
        out_shape=[_sds(xdt.shape, jnp.float32, xdt),
                   _sds(states, jnp.float32, xdt)],
        scratch_shapes=[scratch["block"], scratch["states"]],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_SSD_FWD,
    )(*(arrays[k] for k in ("xdt", "rows", "cols", "b", "c", "bt")))


def _over_lanes(heads: int, head_dim: int):
    """``[H, H * P]``, one where the lane is the head's.  A head's value
    over its lanes is a product with it, and a sum over a head's lanes a
    product with its transpose: at precision ``HIGHEST`` exact for the
    one, float32 for the other, and both in the ``[T, H * P]`` layout the
    kernels read and write — a ``[T, H, P]`` view of such an array is,
    tiled for the TPU, another array, which XLA copies to and from
    (0.18 ms a copy of 67 MB: PERF.md §6, PR 32)."""
    return jnp.repeat(jnp.eye(heads, dtype=jnp.float32), head_dim, axis=1)


def _rounded(v, dtype):
    """``v`` in float32 with ``dtype``'s precision, by an operation XLA
    keeps: a convert to the operand dtype and back is one it may drop when
    it fuses, and ``d cum``'s row sums (:func:`ssd_backward`) need the
    very values the kernels' products took."""
    kept = jnp.finfo(dtype)
    return lax.reduce_precision(v.astype(jnp.float32), kept.nexp, kept.nmant)


def _wide(v, lanes):
    return jnp.dot(v, lanes, precision=lax.Precision.HIGHEST)


def _narrow(v, lanes):
    return jnp.dot(v, lanes.T, precision=lax.Precision.HIGHEST)


@_kernel_jit
def ssd_backward(xdt, cum, b, c, y, entering, dy,
                 head_block: int | None = None, interpret: bool = False):
    """Pallas backward: ``(d xdt, d cum, d b, d c)`` from ``dy``
    ``[B, T, H * P]`` (rounded to the operand dtype on its way into the
    products, as XLA's default product rounds it) and the forward's ``y``
    and entering states, each gradient in its primal's shape and dtype.
    ``d cum``'s row sums and the sum of ``dB``'s two parts are XLA's."""
    f32 = jnp.float32
    bsz, nc, h, size = cum.shape
    grid, arrays, specs, static, scratch, _ = _layout(
        xdt, cum, b, c, head_block, backward=True)
    group = _sds(arrays["b"].shape, f32, xdt)
    dx, ends, db, dc, dbt = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, **static),
        grid=grid,
        in_specs=[specs["wide"], specs["wide"], specs["rows"],
                  specs["cols"], specs["group"], specs["group"],
                  specs["turned"], specs["turned"], specs["states"]],
        out_specs=[specs["wide"], specs["ends"], specs["group"],
                   specs["group"], specs["turned"]],
        out_shape=[
            _sds(xdt.shape, f32, xdt),
            _sds(arrays["rows"].shape[:-1] + (static["sub"],), f32, xdt),
            group, group, _sds(arrays["bt"].shape, f32, xdt),
        ],
        scratch_shapes=[scratch["block"], scratch["block"],
                        scratch["states"]],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names.KERNEL_SSD_BWD,
    )(xdt, dy.astype(xdt.dtype),
      *(arrays[k] for k in ("rows", "cols", "b", "c", "bt", "ct")),
      entering)
    # d cum (the module's docstring): every y_l carries exp(cum_l), every
    # use of xdt_s carries exp(-cum_s), the state a chunk leaves
    # exp(cum_L).  The two row sums cancel pair by pair for the steps of
    # one chunk, so they are taken from what the products took and gave:
    # dy as rounded on its way in, dx before it is rounded
    dcum = _narrow(_rounded(dy, xdt.dtype) * y - xdt.astype(f32) * dx,
                   _over_lanes(h, static["head_dim"]))      # [B, T, H]
    dcum = dcum.reshape(bsz, nc, size, h).swapaxes(2, 3)
    dcum = dcum.at[..., -1].add(ends.sum(-1).reshape(bsz, nc, h))
    db = db + dbt.swapaxes(2, 3).reshape(db.shape)
    return (dx.astype(xdt.dtype), dcum,
            db.astype(b.dtype).reshape(b.shape),
            dc.astype(c.dtype).reshape(c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pair(xdt, cum, b, c, interpret):
    return ssd_forward(xdt, cum, b, c, interpret=interpret)[0]


def _pair_fwd(xdt, cum, b, c, interpret):
    y, entering = ssd_forward(xdt, cum, b, c, interpret=interpret)
    return y, (xdt, cum, b, c, y, entering)


def _pair_bwd(interpret, residuals, dy):
    return ssd_backward(*residuals, dy, interpret=interpret)


_pair.defvjp(_pair_fwd, _pair_bwd)


def chunks_kernel(x, dt, a, b, c, interpret: bool = False):
    """The chunked computation by the kernel pair, differentiable.  ``x``
    ``[B, C, L, H, P]``, ``dt`` ``[B, C, L, H]`` float32, ``a`` ``[H]``,
    ``b``, ``c`` ``[B, C, L, G, N]`` in the operand dtype; returns ``y``
    ``[B, C, L, H, P]`` float32.  What XLA does around the kernels it
    does on ``[B, T, H * P]`` arrays (:func:`_over_lanes` says why)."""
    f32 = jnp.float32
    bsz, nc, size, h, p = x.shape
    t = nc * size
    cum = jnp.cumsum((dt * a.astype(f32)).swapaxes(2, 3), axis=-1)
    xdt = _rounded(x.reshape(bsz, t, h * p).astype(f32)
                   * _wide(dt.reshape(bsz, t, h), _over_lanes(h, p)),
                   b.dtype)
    y = _pair(xdt.astype(b.dtype), cum, b.reshape(bsz, t, *b.shape[3:]),
              c.reshape(bsz, t, *c.shape[3:]), interpret)
    return y.reshape(x.shape)


def ssd_chunked(x, dt, a, b, c, chunk: int, operand_dtype=jnp.float32):
    """``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]`` (positive, after its
    softplus); ``a`` ``[H]`` (negative); ``b``, ``c`` ``[B, T, G, N]``,
    each group of ``H / G`` heads sharing its ``B_t``, ``C_t``.  Returns
    ``y`` ``[B, T, H, P]`` float32.  A length ``chunk`` does not divide
    is padded with steps of ``dt = 0``, which leave the state alone."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    r = h // g
    size = min(chunk, t)
    pad = -t % size
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // size
    chunked = lambda v: v.reshape(bsz, nc, size, *v.shape[2:])

    dt = chunked(dt.astype(f32))
    b = chunked(b.astype(operand_dtype))
    c = chunked(c.astype(operand_dtype))
    if kernel_fits(jax.default_backend(), size, n, p, h, g,
                   b.dtype.itemsize):
        y = chunks_kernel(chunked(x), dt, a, b, c)
    else:
        # per-step log decay, <= 0, as [B, chunks, G, R, L]; cum_l is the
        # sum over the chunk's steps up to and including l
        log_decay = (dt * a.astype(f32)).reshape(bsz, nc, size, g, r)
        cum = jnp.cumsum(log_decay.transpose(0, 1, 3, 4, 2), axis=-1)
        xdt = chunked(x).astype(f32) * dt[..., None]
        y = chunks_xla(xdt.reshape(bsz, nc, size, g, r, p), cum, b, c)
    return y.reshape(bsz, nc * size, h, p)[:, :t]
