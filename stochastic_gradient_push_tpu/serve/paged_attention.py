"""Paged-attention decode kernel: GQA, KV-head sharded, page-table fed.

Single-token decode against a paged KV cache.  Queries arrive as
``[batch, q_heads, head_dim]`` (one new token per sequence); keys and
values live in the page pool ``[kv_heads, num_pages, page_size,
head_dim]`` and each sequence names its pages through an int32
``page_indices`` row (padded with 0) plus a ``lengths`` scalar.

The Pallas kernel carries the flash-attention forward's state
(ops/flash_attention.py) on a 3-D grid whose two major dims are parallel
(batch, kv-head) and whose MINOR dim walks the sequence's pages with
``arbitrary`` semantics: the online-softmax ``(m, den, acc)`` triple in
fp32 VMEM scratch across page steps.  The k/v block fetched at minor
step ``j`` is ``pages[page_indices[b, j]]`` — a data-dependent block
index, which is what ``pltpu.PrefetchScalarGridSpec`` exists for
(scalar operands land in SMEM before the grid starts, and the index
maps read them to steer the double-buffered block fetches; the flash
kernels take their tile visits the same way).
Pages past a sequence's length are compute-gated with ``pl.when`` and
their fetches are aliased back to the sequence's first page, so padded
``page_indices`` rows never cost bandwidth.

GQA: ``q_heads = kv_heads * group``; the kernel blocks queries as
``[group, head_dim]`` per kv head, so grouped queries share one
streamed k/v fetch.  :func:`sharded_paged_decode` shards the kv-head
axis over a mesh ``model`` axis via shard_map (SNIPPETS.md [1]): q
``P(None, "model", None)``, pages ``P("model", None, None, None)``,
page table replicated — decode is embarrassingly parallel over kv
heads, no collective in the kernel.

Backend selection rides the same ``resolve_use_pallas`` carrier as the
gossip kernel, so CPU CI exercises the real kernel under the Pallas
interpreter while the dense reference (:func:`paged_attention_reference`)
stays the parity oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import NEG_INF, _sds
from ..ops.gossip_kernel import resolve_use_pallas
from ..telemetry import names

__all__ = ["MODEL_AXIS", "paged_attention_decode",
           "paged_attention_reference", "sharded_paged_decode"]

# the decode mesh's model-parallel axis (kv heads shard over it); a
# module-level *_AXIS constant so sgplint's SGPL001 vocabulary knows it
MODEL_AXIS = "model"

# fp32 running-state scratch keeps a full lane (column 0 meaningful),
# same layout rule as the flash kernels
_STATE_LANES = 128


def _check_shapes(q, k_pages, v_pages, page_indices, lengths):
    if q.ndim != 3:
        raise ValueError(f"q must be [batch, q_heads, head_dim], got "
                         f"{q.shape}")
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k/v pages must both be [kv_heads, num_pages, page_size, "
            f"head_dim], got {k_pages.shape} vs {v_pages.shape}")
    b, h, d = q.shape
    hkv = k_pages.shape[0]
    if k_pages.shape[-1] != d:
        raise ValueError(f"head_dim mismatch: q has {d}, pages have "
                         f"{k_pages.shape[-1]}")
    if h % hkv:
        raise ValueError(f"q_heads {h} not a multiple of kv_heads {hkv}")
    if page_indices.ndim != 2 or page_indices.shape[0] != b:
        raise ValueError(f"page_indices must be [batch, max_pages], got "
                         f"{page_indices.shape} for batch {b}")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be [batch], got {lengths.shape}")
    return b, h, d, hkv


def paged_attention_reference(q, k_pages, v_pages, page_indices, lengths):
    """Dense oracle: gather every named page, run masked softmax
    attention in fp32.  O(batch · max_pages · page_size) memory — the
    thing the paged kernel avoids — but bit-for-bit the semantics the
    kernel must reproduce."""
    b, h, d, hkv = _check_shapes(q, k_pages, v_pages, page_indices,
                                 lengths)
    group = h // hkv
    n_pages = page_indices.shape[1]
    page = k_pages.shape[2]
    t = n_pages * page

    # [kv_heads, batch, max_pages, page, d] -> [batch, kv_heads, t, d]
    k = jnp.moveaxis(k_pages[:, page_indices], 1, 0)
    k = k.reshape(b, hkv, t, d).astype(jnp.float32)
    v = jnp.moveaxis(v_pages[:, page_indices], 1, 0)
    v = v.reshape(b, hkv, t, d).astype(jnp.float32)

    qg = q.reshape(b, hkv, group, d).astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k)
    pos = jnp.arange(t, dtype=jnp.int32)
    mask = pos[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bhtd->bhgd", p, v)
    return o.reshape(b, h, d).astype(q.dtype)


def _paged_decode_kernel(pi_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, den_ref, acc_ref, *, page_size: int):
    """One (batch, kv-head, page-step) cell.  Scalar-prefetch refs:
    pi [batch, max_pages], len [batch] (SMEM).  Block refs: q/o
    [group, d]; k/v [page_size, d] (streamed page); scratch m/den
    [group, 128] and acc [group, d], fp32, persistent across pages."""
    bi, j = pl.program_id(0), pl.program_id(2)
    n_pages = pl.num_programs(2)
    length = len_ref[bi]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        den_ref[:] = jnp.zeros_like(den_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * page_size < length)
    def _compute():
        d = q_ref.shape[-1]
        q = q_ref[:].astype(jnp.float32) * (d ** -0.5)      # [g, d]
        k = k_ref[:].astype(jnp.float32)                    # [page, d]
        v = v_ref[:].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [g, page]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[:, :1]                               # [g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        den_new = den_ref[:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        den_ref[:] = jnp.broadcast_to(den_new, den_ref.shape)

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[:] = (acc_ref[:] / den_ref[:, :1]).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_indices, lengths,
                         interpret: bool):
    b, h, d, hkv = _check_shapes(q, k_pages, v_pages, page_indices,
                                 lengths)
    group = h // hkv
    n_pages = page_indices.shape[1]
    page = k_pages.shape[2]
    qg = q.reshape(b, hkv, group, d)

    def page_map(bi, hi, j, pi_ref, len_ref):
        # past-the-end steps re-point at the sequence's first page:
        # same block index as an earlier step ⇒ no fetch for gated
        # cells, and padded page_indices entries are never read
        last = jnp.maximum(
            (len_ref[bi] + page - 1) // page - 1, 0)
        return (hi, pi_ref[bi, jnp.minimum(j, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((None, None, group, d),
                         lambda bi, hi, j, pi, ln: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, page, d), page_map),
            pl.BlockSpec((None, None, page, d), page_map),
        ],
        out_specs=pl.BlockSpec(
            (None, None, group, d),
            lambda bi, hi, j, pi, ln: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, _STATE_LANES), jnp.float32),
            pltpu.VMEM((group, _STATE_LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=page),
        grid_spec=grid_spec,
        out_shape=_sds((b, hkv, group, d), q.dtype, qg),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=names.KERNEL_PAGED_ATTENTION,
    )(page_indices.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(b, h, d)


def paged_attention_decode(q, k_pages, v_pages, page_indices, lengths,
                           *, use_pallas: bool | None = None,
                           interpret: bool = False):
    """Single-step paged decode.  ``q``: ``[batch, q_heads, head_dim]``;
    ``k_pages``/``v_pages``: ``[kv_heads, num_pages, page_size,
    head_dim]``; ``page_indices``: int32 ``[batch, max_pages]`` (0-
    padded); ``lengths``: int32 ``[batch]``, each ≥ 1 and counting the
    token being decoded (its k/v must already be written to its page).

    Backend rides :func:`ops.gossip_kernel.resolve_use_pallas`: the
    explicit flag wins; ``None`` means Pallas on TPU or whenever
    ``interpret`` is set (the CPU-CI carrier), else the dense oracle.
    """
    _check_shapes(q, k_pages, v_pages, page_indices, lengths)
    if resolve_use_pallas(use_pallas, interpret):
        return _paged_decode_pallas(q, k_pages, v_pages, page_indices,
                                    lengths, interpret=interpret)
    return paged_attention_reference(q, k_pages, v_pages, page_indices,
                                     lengths)


def sharded_paged_decode(mesh: Mesh, q, k_pages, v_pages, page_indices,
                         lengths, *, axis: str = MODEL_AXIS,
                         use_pallas: bool | None = None,
                         interpret: bool = False):
    """KV-head-sharded decode over ``mesh[axis]`` (SNIPPETS.md [1]):
    queries shard ``P(None, axis, None)``, pages ``P(axis, ...)``, the
    page table and lengths replicate, and each shard runs the paged
    kernel on its head slice — no collectives.  Contiguous GQA grouping
    keeps q-head and kv-head shard boundaries aligned as long as
    ``kv_heads % mesh.shape[axis] == 0``."""
    b, h, d, hkv = _check_shapes(q, k_pages, v_pages, page_indices,
                                 lengths)
    ways = mesh.shape[axis]
    if hkv % ways:
        raise ValueError(f"kv_heads {hkv} not divisible by mesh axis "
                         f"'{axis}' size {ways}")
    fn = functools.partial(paged_attention_decode,
                           use_pallas=use_pallas, interpret=interpret)
    shard = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, axis, None), P(axis, None, None, None),
                  P(axis, None, None, None), P(), P()),
        out_specs=P(None, axis, None))
    return shard(q, k_pages, v_pages,
                 page_indices.astype(jnp.int32),
                 lengths.astype(jnp.int32))
