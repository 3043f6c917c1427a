"""Split Pallas gossip transport: start (remote DMA) / wait (decode+axpy).

The schedule-level half of hiding the gossip exchange shipped with the
overlap phase schedule (``collectives.overlap_launch``); this module
closes the kernel-level half.  The XLA path round-trips every encoded
payload through HBM three times per edge: ``ppermute`` ships the wire
bytes, a separate decode pass materializes the full-precision payload,
and a separate axpy folds it into the accumulator.  The original fused
kernel (PR 15) collapsed those into one ``pallas_call`` — but starting
AND waiting the remote DMA inside one op meant overlap launches could
never ride it (the transport the overlap schedule hides behind compute
was serialized inside the kernel).  This revision splits the op:

* :func:`gossip_edge_start` — one ``pallas_call`` serving ALL edges of
  a payload (the per-edge messages ride a leading ``E`` axis; one
  program, ``E × num_chunks`` grid steps): grid step 0 runs the entry
  barrier with every destination AND source on the ``collective_id``-
  keyed barrier semaphore, then each step issues one
  ``pltpu.make_async_remote_copy`` per wire part (the int8 scale
  side-lane is its own part) straight from the sender's HBM into the
  destination rank's landing buffer, *pipelined depth-2*: the DMA for
  chunk ``g+1`` is issued before chunk ``g`` is waited, so the wire
  stays busy while completions drain.  The call returns an opaque
  :class:`TransportHandle` carrying the landed ENCODED buffers — the
  cross-call data dependency XLA schedules around;
* :func:`gossip_edge_wait` — a purely local ``pallas_call`` (no axis,
  no barrier, no collective_id) that pulls each landed chunk into VMEM,
  decodes it there (f32 passthrough, bf16 widen, int8 per-block dequant
  against the scale side-lane), and accumulates ``acc += decode(chunk)``
  across all ``E`` edges into the output block.  Mosaic's automatic
  grid pipeline double-buffers the decode against the next chunk's
  HBM→VMEM fetch.  The DECODED payload never materializes in HBM.

**Handle contract (compiled mode, honestly stated).**  Mosaic in this
jax version keys DMA semaphores to kernel scratch — they must drain
before a ``pallas_call`` returns, and no semaphore can cross a call
boundary.  So the start op completes its own transfers internally (the
depth-2 chunk pipeline above is where the wire overlap inside the op
lives) and the handle's "semaphore state" is definitionally drained at
hand-off: what crosses the call boundary is the landed encoded buffer
state.  The async win is scheduling-level and real — ``overlap_launch``
issues the start at the TOP of the step, XLA hoists it behind the
forward/backward compute, and ``post_step`` consumes the handle via the
wait at the bottom — exactly the start/done split the XLA lane's
collective-permute pair gets, now with in-VMEM decode on the landing
side.  On the interpret CI mesh the Pallas interpreter discharges each
remote copy synchronously, so split and fused numerics are identical.
The compiled pair first ran on four TPU v5e chips in PR 21
(``chip_smoke.py --chips 4``): one round at ResNet-50's payload is
bit-identical to the XLA lane on the f32 and the int8 wire.  Its speed
is not measured (ROADMAP S4); the default lane stays ``xla`` until it
is.

:func:`gossip_edge_axpy` remains as the fused convenience spelling —
now literally ``gossip_edge_wait(gossip_edge_start(...), acc)`` — so
single-shot callers and the parity suite exercise the same two kernels
the split path runs.

Selection follows the ``ops/ring_flash.py`` convention through the
shared :func:`resolve_use_pallas` rule — Pallas on TPU (or under
``interpret=True``, which runs the identical kernels through the Pallas
interpreter so the world-8 CPU test mesh exercises the real remote-DMA
path), XLA ``ppermute`` everywhere else — and the XLA fallback stays
selectable at runtime (``--gossip_kernel xla``) and bit-compared in CI.
``resolve_gossip_kernel`` maps the CLI flag onto a :class:`KernelLane`
and rejects ``pallas`` on a backend that cannot lower Mosaic remote DMA
with a typed :class:`KernelBackendError` instead of a Mosaic crash.

Numerics: the kernel branch reuses the exact send pipeline of the XLA
path — the sender multiply, fault keep-masks, EF residual injection and
the codec ``encode`` all happen before the payload reaches the kernel,
so the error-feedback residual telescopes against the same sent bytes
— and the in-VMEM decode performs the same elementwise ops in the same
order as ``WireCodec.decode``, so interpret-mode output is bit-aligned
with the XLA path (pinned by tests and the wirecheck kernel lane).  The
push-sum weight lane (scalar leaves) never enters the kernel: it ships
exact f32 over ``lax.ppermute`` in both lanes, bit-identical by
construction.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import names

__all__ = ["KernelBackendError", "KernelLane", "GOSSIP_KERNELS",
           "DEFAULT_CHUNK_ELEMS", "COLLECTIVE_ID_SLOTS",
           "TransportHandle", "empty_transport_handle",
           "resolve_use_pallas", "resolve_gossip_kernel",
           "gossip_edge_start", "gossip_edge_wait",
           "gossip_edge_axpy", "main"]

# CLI vocabulary for --gossip_kernel
GOSSIP_KERNELS = ("auto", "pallas", "xla")

# elements of decoded payload per remote-copy chunk: 64k f32 elements is
# a 256 KB VMEM working set per buffered part — deep enough to amortize
# DMA issue cost, shallow enough to leave VMEM for the train step
DEFAULT_CHUNK_ELEMS = 64 * 1024

# TPU vector lane width: float chunks are laid out in whole lanes
_LANES = 128
# an int8 VMEM tile is 32 sublanes deep
_INT8_SUBLANES = 32

# ceiling on chunks (grid steps) per call; larger payloads get
# proportionally larger chunks
_MAX_CHUNKS = 256

# barrier-semaphore id pool the collective layer cycles per transport
# bucket: Mosaic keys barrier/collective state by collective_id, so two
# pallas_calls that could execute concurrently must not share one.
# Buckets launched in the same round are deliberately concurrent (that
# is the pipelining), so each bucket gets its own id from this pool
# (collectives.py passes collective_id = bucket_index %
# COLLECTIVE_ID_SLOTS); successive rounds of the SAME bucket are
# ordered by their handle data dependency
COLLECTIVE_ID_SLOTS = 16


class KernelBackendError(RuntimeError):
    """``--gossip_kernel pallas`` on a backend that cannot run it."""


def resolve_use_pallas(flag: bool | None, interpret: bool) -> bool:
    """The shared kernel-selection auto rule (ops/ring_flash.py and the
    gossip kernel resolve through this one function): an explicit flag
    wins; ``None`` means Pallas on TPU — or whenever ``interpret`` is
    set, which routes the identical kernel through the Pallas
    interpreter (the CPU test path) — and the non-kernel fallback
    elsewhere."""
    if flag is None:
        return bool(interpret) or jax.default_backend() == "tpu"
    return bool(flag)


@dataclasses.dataclass(frozen=True)
class KernelLane:
    """Resolved Pallas lane for the gossip collective: carried by the
    algorithm/collective layers wherever the kernel branch is active
    (absence — ``None`` — is the XLA ppermute lane)."""

    interpret: bool = False
    chunk_elems: int = DEFAULT_CHUNK_ELEMS

    @property
    def name(self) -> str:
        return "pallas"


def resolve_gossip_kernel(flag: str | None,
                          interpret: bool = False) -> KernelLane | None:
    """Map the ``--gossip_kernel`` flag onto a lane.

    ``"xla"``/``None`` → ``None`` (the ppermute path).  ``"auto"`` →
    a :class:`KernelLane` exactly when :func:`resolve_use_pallas` says
    the kernel can run (TPU, or ``interpret``).  ``"pallas"`` → a lane,
    or a typed :class:`KernelBackendError` on a backend where the
    Mosaic remote-DMA kernel cannot lower — failing at resolve time
    with a readable message instead of a Mosaic crash at first step.
    """
    if flag is None or flag == "xla":
        return None
    if flag == "auto":
        if resolve_use_pallas(None, interpret):
            return KernelLane(interpret=bool(interpret))
        return None
    if flag == "pallas":
        if not resolve_use_pallas(None, interpret):
            raise KernelBackendError(
                "gossip_kernel='pallas' needs a TPU backend: the fused "
                "gossip kernel's remote DMA only lowers through Mosaic "
                f"(current backend: {jax.default_backend()!r}).  Use "
                "gossip_kernel=auto for the XLA ppermute fallback, or "
                "interpret=True (tests) to run the kernel through the "
                "Pallas interpreter")
        return KernelLane(interpret=bool(interpret))
    raise ValueError(
        f"unknown gossip_kernel {flag!r}; one of {GOSSIP_KERNELS}")


# -- chunk layout -----------------------------------------------------------


def _chunk_layout(n_decoded: int, block: int | None, chunk_elems: int):
    """(chunk_rows R, elems per chunk C, num chunks NB) for a payload of
    ``n_decoded`` elements.  With an int8 ``block`` a chunk is a whole
    number of codec blocks so every scale stays chunk-local; the chunk
    target grows when the payload would otherwise exceed the semaphore
    ceiling."""
    if int(n_decoded) < 1:
        raise ValueError(
            f"payload must have at least one element, got {n_decoded} "
            "(scalar/empty leaves take the exact-f32 ppermute lane, "
            "never the kernel)")
    if int(chunk_elems) < 1:
        raise ValueError(f"chunk_elems must be >= 1, got {chunk_elems}")
    blk = int(block) if block else 1
    rows_total = max(1, -(-n_decoded // blk))   # ceil: codec blocks
    # the chunk shrinks to the payload: padding is bounded by one
    # chunk's ragged tail (plus lane alignment), not by the chunk target
    rows_per_chunk = max(1, min(int(chunk_elems) // blk, rows_total))
    if -(-rows_total // rows_per_chunk) > _MAX_CHUNKS:
        rows_per_chunk = -(-rows_total // _MAX_CHUNKS)
    # whole tiles once a chunk spans one: 128 lanes of floats (the
    # chunk tiles as [c // 128, 128], _tile), 32 sublanes of int8 rows
    align = _INT8_SUBLANES if block else _LANES
    if rows_per_chunk > align:
        rows_per_chunk = -(-rows_per_chunk // align) * align
    nb = -(-rows_total // rows_per_chunk)
    return rows_per_chunk, rows_per_chunk * blk, nb


def _tile(kind: str, rows: int, c: int, block: int | None):
    """The ``[R, L]`` tile one decoded chunk is laid out as, on the wire
    side and the accumulator side alike.  Mosaic wants the last two dims
    of every VMEM block to be the array's own or ``(8, 128)``-divisible,
    and slices HBM refs only on untiled leading dims — so a chunk is
    never a row of a ``[NB, c]`` array: int8 keeps the codec's
    ``[rows, block]`` (scales ride as a ``[rows, 1]`` column), the float
    lanes fold ``c`` into whole 128-lane rows (a sub-lane remainder
    payload stays one short row)."""
    if kind == "int8":
        return rows, int(block)
    lanes = _LANES if c % _LANES == 0 else c
    return c // lanes, lanes


def _pad_rows(a, rows: int):
    """Zero-pad the leading dim to ``rows`` (symmetric codecs keep
    decode(0) == 0, so padding never leaks into the axpy)."""
    if a.shape[0] == rows:
        return a
    pad = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


# -- the transport handle ---------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TransportHandle:
    """Opaque result of :func:`gossip_edge_start`: the landed encoded
    receive buffers (each ``[E, NB, R, L]``, see :func:`_tile`) plus the
    static layout the wait side needs to pull, decode and fold them.  A
    pytree, so it
    rides FIFO slots, ``lax.cond`` branches and jit boundaries; between
    a start and its wait the buffers hold WIRE bytes — nothing outside
    :func:`gossip_edge_wait` / :meth:`decode_edges` may interpret them.

    ``meta`` = (kind, n_decoded, rows, chunk_elems, num_chunks,
    num_edges, interpret) — all static, so handles from different
    schedule phases of one round are structurally identical (required
    for the phase ``lax.switch``)."""

    recv: tuple
    meta: tuple

    def tree_flatten(self):
        return (tuple(self.recv),), self.meta

    @classmethod
    def tree_unflatten(cls, meta, children):
        (recv,) = children
        return cls(recv=tuple(recv), meta=meta)

    @property
    def num_edges(self) -> int:
        return self.meta[5]

    @property
    def n_decoded(self) -> int:
        return self.meta[1]

    def decode_edges(self):
        """Per-edge decoded payload ``[E, n]`` in f32 — the pure-jnp
        twin of the wait kernel's in-VMEM decode, same elementwise op
        order, for landing sites that cannot (or need not) run the
        kernel: drains, health views, interpret-mode checks.  Fold the
        edges sequentially (``for e: acc += dec[e]``) to stay
        bit-aligned with the kernel's per-edge accumulation."""
        kind, n, _rows, _c, _nb, ne, _interp = self.meta
        if kind == "int8":
            q, scale = self.recv
            return (q.astype(jnp.float32) * scale).reshape(ne, -1)[:, :n]
        return self.recv[0].reshape(ne, -1)[:, :n].astype(jnp.float32)


def empty_transport_handle(spec, n_decoded: int, num_edges: int,
                           interpret: bool = False,
                           chunk_elems: int = DEFAULT_CHUNK_ELEMS
                           ) -> TransportHandle:
    """A zero handle with exactly the structure a matching
    :func:`gossip_edge_start` call would return — the thinning skip
    branch's ``lax.cond`` arm must hand back the same pytree as the
    launch arm, and waiting a zero handle lands a zero contribution
    (decode(0) == 0 for every codec)."""
    kind = spec.kind
    block = spec.block if kind == "int8" else None
    rows, c, nb = _chunk_layout(n_decoded, block, chunk_elems)
    tile = _tile(kind, rows, c, block)
    if kind == "int8":
        recv = (jnp.zeros((num_edges, nb) + tile, jnp.int8),
                jnp.zeros((num_edges, nb, rows, 1), jnp.float32))
    elif kind == "bf16":
        recv = (jnp.zeros((num_edges, nb) + tile, jnp.bfloat16),)
    else:
        recv = (jnp.zeros((num_edges, nb) + tile, jnp.float32),)
    return TransportHandle(
        recv=recv, meta=(kind, int(n_decoded), rows, c, nb,
                         int(num_edges), bool(interpret)))


# -- the start kernel (transport only) --------------------------------------


def _edge_start_kernel(nparts: int, nb: int, ne: int, compiled: bool,
                      tbl_ref, *refs):
    """Transport program over a flat ``E*NB`` grid: grid step ``g``
    covers chunk ``g % NB`` of edge ``g // NB``.

    Ref layout: ``refs = (*part_refs, *out_refs, *send_sems,
    *recv_sems)`` — parts and outs full-shape in ANY (the kernel only
    touches them through DMA), two DMA semaphore slots per part: the
    pipeline never has more than two chunks in flight, and the chip's
    semaphore memory holds a few hundred in all, not one per chunk.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    part_refs = refs[:nparts]
    out_refs = refs[nparts:2 * nparts]
    send_sems = refs[2 * nparts:3 * nparts]
    recv_sems = refs[3 * nparts:4 * nparts]

    g = pl.program_id(0)
    total = ne * nb

    def chunk_dmas(gg):
        # descriptors for flat step gg; remaking the same descriptor to
        # wait it is the Mosaic idiom (the semaphores carry identity)
        e = gg // nb
        k = gg - e * nb
        slot = gg % 2
        dmas = []
        for i in range(nparts):
            dmas.append(pltpu.make_async_remote_copy(
                src_ref=part_refs[i].at[pl.ds(e, 1), pl.ds(k, 1)],
                dst_ref=out_refs[i].at[pl.ds(e, 1), pl.ds(k, 1)],
                send_sem=send_sems[i].at[slot],
                recv_sem=recv_sems[i].at[slot],
                device_id=tbl_ref[e, 0],
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            ))
        return dmas

    if compiled:
        # entry barrier (compiled mode only — the interpreter's
        # discharge is synchronous and cannot signal remote
        # semaphores): before the FIRST remote copy, handshake with
        # every rank we write into (dst_e) and every rank that writes
        # into us (src_e, each permutation's inverse at this rank), so
        # no sender DMAs into landing buffers before its receiver has
        # entered the kernel and owns that memory.  Each rank receives
        # exactly 2E signals (from ITS src and dst per edge) and waits
        # the semaphore back down to zero, per the Mosaic barrier
        # contract.
        @pl.when(g == 0)
        def _entry_barrier():
            bsem = pltpu.get_barrier_semaphore()
            for e in range(ne):
                pltpu.semaphore_signal(
                    bsem, inc=1, device_id=tbl_ref[e, 0],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_signal(
                    bsem, inc=1, device_id=tbl_ref[e, 1],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(bsem, 2 * ne)

        # depth-2 chunk pipeline: step g waits chunk g but has already
        # issued chunk g+1, so one transfer is always in flight while
        # the previous drains (the Mosaic depth of the ROADMAP item)
        @pl.when(g == 0)
        def _prime():
            for dma in chunk_dmas(g):
                dma.start()

        @pl.when(g + 1 < total)
        def _issue_ahead():
            for dma in chunk_dmas(g + 1):
                dma.start()
    else:
        # interpret mode: discharge is synchronous (start performs the
        # copy), so the pipeline shape is irrelevant — issue the step's
        # own chunk and fall through to the shared wait
        for dma in chunk_dmas(g):
            dma.start()

    # both modes drain chunk g here — remade descriptors wait via
    # semaphore identity, so this tail pairs with whichever branch
    # issued the start
    dmas = chunk_dmas(g)
    for dma in dmas:
        dma.wait()


def _wire_rows(part):
    """A chunked part ``[E, NB, ...]`` as the lane-dense
    ``[E, NB, Rp, 128]`` rows the transport moves (zero-padded).
    Mosaic slices an HBM ref for DMA only where the slice matches the
    ref's tiled layout, and a minor dim short of 128 lanes (int8's
    ``[rows, block]``, the ``[rows, 1]`` scale column) is padded there;
    whole rows of 128 are not.  A no-op view for the float lanes."""
    e, nb = part.shape[:2]
    flat = part.reshape(e, nb, -1)
    pad = -flat.shape[2] % _LANES
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, pad)))
    return flat.reshape(e, nb, -1, _LANES)


def _from_wire_rows(rows, shape):
    """Inverse of :func:`_wire_rows`: the landed rows back in the
    part's own ``shape``."""
    e, nb = shape[:2]
    m = int(np.prod(shape[2:]))
    return rows.reshape(e, nb, -1)[:, :, :m].reshape(shape)


def _edge_start_call(interpret: bool, collective_id: int, ne: int,
                     nb: int, tbl, parts_chunks):
    """Build and invoke the transport pallas_call: inputs are the
    per-edge chunked parts (each ``[E, NB, Rp, 128]``), outputs the
    landed encoded buffers of identical shape on the destination
    ranks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .flash_attention import _sds

    nparts = len(parts_chunks)
    # interpret=True is the HLO interpreter: its discharge of a remote
    # copy is synchronous and it cannot signal remote semaphores, so it
    # runs the barrier-free program.  The Mosaic TPU interpreter
    # (pltpu.InterpretParams) simulates DMA and semaphores across the
    # mesh and runs the compiled-mode program, barrier included.
    hlo_interpreter = isinstance(interpret, bool) and interpret
    kernel = functools.partial(_edge_start_kernel, nparts, nb, ne,
                               not hlo_interpreter)
    return pl.pallas_call(
        kernel,
        # the landed buffers vary over the gossip axis like the
        # axis_index-derived table, whatever the parts' own type
        out_shape=tuple(_sds(p.shape, p.dtype, tbl, p)
                        for p in parts_chunks),
        grid=(ne * nb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] +
                 [pl.BlockSpec(memory_space=pl.ANY)] * nparts,
        out_specs=tuple([pl.BlockSpec(memory_space=pl.ANY)] * nparts),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,))] * (2 * nparts),
        # collective_id keys the entry-barrier semaphore and
        # coordinates the remote-DMA buffer addresses across the SPMD
        # programs on a real mesh.  Two calls that could execute
        # concurrently must not share an id (Mosaic keys barrier state
        # by it): the collective layer cycles ids per transport bucket
        # (COLLECTIVE_ID_SLOTS) — same-bucket rounds are already
        # ordered by their handle data dependency, and TPU's single
        # compute stream executes custom calls sequentially in schedule
        # order, which backstops any id reuse across the pool boundary
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
        interpret=interpret,
        name=names.KERNEL_GOSSIP_START,
    )(tbl, *parts_chunks)


def gossip_edge_start(parts, dests, axis_name: str, spec,
                      n_decoded: int | None = None,
                      interpret: bool = False,
                      chunk_elems: int = DEFAULT_CHUNK_ELEMS,
                      collective_id: int = 0) -> TransportHandle:
    """Issue the transport for every edge of one payload; returns the
    :class:`TransportHandle` whose wait decodes and accumulates.

    ``parts`` are the encoded wire parts (from ``WireCodec.encode``;
    the sender multiply, fault masks and EF injection already applied
    upstream), each stacked over a leading edge axis ``E`` — one
    pallas_call serves all ``peers_per_itr`` edges.  ``dests`` is the
    matching ``[E, world]`` static destination table (each row a
    permutation; a single ``[world]`` row means ``E == 1``).
    ``n_decoded`` is the decoded payload length the wait side trims to
    (defaults to the encoded capacity).  Must be called inside
    ``shard_map`` with ``axis_name`` bound; all ranks execute the same
    program (the remote DMA is SPMD).

    ``collective_id`` keys the kernel's entry-barrier semaphore; call
    sites that could execute concurrently must pass distinct ids (the
    collective layer cycles ``bucket_index % COLLECTIVE_ID_SLOTS``).
    """
    if spec is None:
        raise ValueError("codec exposes no in-kernel decode spec; the "
                         "caller must take the XLA ppermute path")
    kind = spec.kind
    if kind not in ("f32", "bf16", "int8"):
        raise ValueError(f"unknown decode spec kind {kind!r}")

    table = np.asarray(dests, dtype=np.int32)
    if table.ndim == 1:
        table = table[None]
    ne = table.shape[0]
    # normalize single-edge parts to the stacked [E=1, ...] convention
    expect_ndim = {"int8": (3, 2)}.get(kind, (2,))
    norm = []
    for i, p in enumerate(parts):
        want = expect_ndim[i] if i < len(expect_ndim) else expect_ndim[-1]
        norm.append(p[None] if p.ndim == want - 1 else p)
    parts = tuple(norm)
    if any(p.shape[0] != ne for p in parts):
        raise ValueError(
            f"parts lead with {[p.shape[0] for p in parts]} edges but "
            f"dests has {ne} rows — every part must stack one message "
            "per edge")

    # every row must be a permutation: the barrier handshakes with each
    # permutation's inverse at this rank, which only exists for a
    # bijection (SGPV101, re-checked at the call boundary)
    world = table.shape[1]
    full = np.empty((ne, world, 2), dtype=np.int32)
    for e in range(ne):
        row = table[e]
        if not np.array_equal(np.sort(row), np.arange(world)):
            raise ValueError(
                "dests must be a permutation of the axis ranks (every "
                f"rank receives exactly one stream); got {row.tolist()}")
        inv = np.empty_like(row)
        inv[row] = np.arange(world, dtype=np.int32)
        full[e] = np.stack([row, inv], axis=1)
    # this rank's [E, 2] (dst_e, src_e) table, into SMEM
    tbl = jnp.asarray(np.transpose(full, (1, 0, 2)),
                      jnp.int32)[jax.lax.axis_index(axis_name)]

    if kind == "int8":
        q, scale = parts
        n = int(n_decoded) if n_decoded is not None \
            else q.shape[1] * q.shape[2]
        rows, c, nb = _chunk_layout(n, spec.block, chunk_elems)
        q_chunks = jax.vmap(
            lambda a: _pad_rows(a, nb * rows).reshape(nb, rows,
                                                      a.shape[1]))(q)
        s_chunks = jax.vmap(
            lambda a: _pad_rows(a, nb * rows).reshape(nb, rows, 1))(scale)
        parts_chunks = (q_chunks, s_chunks)
    else:
        (w,) = parts
        n = int(n_decoded) if n_decoded is not None else w.shape[1]
        rows, c, nb = _chunk_layout(n, None, chunk_elems)
        tile = _tile(kind, rows, c, None)
        parts_chunks = (jax.vmap(
            lambda a: _pad_rows(a.reshape(-1), nb * c).reshape(
                (nb,) + tile))(w),)

    recv = _edge_start_call(interpret, int(collective_id), ne, nb, tbl,
                            tuple(_wire_rows(p) for p in parts_chunks))
    if not isinstance(recv, (tuple, list)):
        recv = (recv,)
    return TransportHandle(
        recv=tuple(_from_wire_rows(r, p.shape)
                   for r, p in zip(recv, parts_chunks)),
        meta=(kind, n, rows, c, nb, ne, bool(interpret)))


# -- the wait kernel (decode + axpy, purely local) --------------------------


def _edge_wait_kernel(kind: str, ne: int, out_dtype, acc_ref, *refs):
    """One grid step (k, e): decode edge e's chunk k in VMEM and fold it
    into output block k.  The e axis is minormost, so the output block
    stays resident across its E revisits; Mosaic's grid pipeline
    double-buffers each chunk fetch against the previous decode."""
    from jax.experimental import pallas as pl

    e = pl.program_id(1)
    part_refs = refs[:-1]
    out_ref = refs[-1]

    # in-VMEM decode on the chunk's own [R, L] tile (no in-kernel
    # reshape); elementwise op order matches WireCodec.decode exactly
    # (bit parity with the XLA lane)
    if kind == "int8":
        q = part_refs[0][0, 0].astype(jnp.float32)     # [R, block]
        scale = part_refs[1][0, 0]                     # [R, 1]
        dec = (q * scale).astype(out_dtype)[None]
    else:  # "f32" passthrough / "bf16" widen — one astype covers both
        dec = part_refs[0][0, 0].astype(out_dtype)[None]

    @pl.when(e == 0)
    def _init():
        out_ref[...] = acc_ref[...] + dec

    if ne > 1:
        @pl.when(e > 0)
        def _fold():
            out_ref[...] = out_ref[...] + dec


def _edge_wait_call(kind: str, interpret: bool, acc_chunks, recv, ne: int):
    """Build and invoke the landing pallas_call: purely local (HBM→VMEM
    pulls of landed chunks + decode + axpy), no collective semantics."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .flash_attention import _sds

    nb = acc_chunks.shape[0]
    kernel = functools.partial(_edge_wait_kernel, kind, ne,
                               acc_chunks.dtype)
    # every block's last two dims are its array's own (_tile)
    acc_spec = pl.BlockSpec((1,) + acc_chunks.shape[1:],
                            lambda k, e: (k, 0, 0),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=_sds(acc_chunks.shape, acc_chunks.dtype,
                       acc_chunks, *recv),
        grid=(nb, ne),
        in_specs=[acc_spec] + [
            pl.BlockSpec((1, 1) + r.shape[2:], lambda k, e: (e, k, 0, 0),
                         memory_space=pltpu.VMEM) for r in recv],
        out_specs=acc_spec,
        interpret=interpret,
        name=names.KERNEL_GOSSIP_WAIT,
    )(acc_chunks, *recv)


def gossip_edge_wait(handle: TransportHandle, acc, weight=None):
    """Land a started transport: ``acc + Σ_e w·decode(recv[e])`` as one
    local pallas_call over the handle's chunks × edges.

    Purely local — no axis name, no barrier, no collective_id: the
    remote transfers completed inside :func:`gossip_edge_start`; this
    op owns the HBM→VMEM pull, the in-VMEM decode and the mixing axpy.
    ``weight`` is the receive-side axpy scalar; the column-stochastic
    round bakes the mixing weight into the sender multiply, so the
    default ``None`` (identity) is the production path."""
    kind, n, _rows, c, nb, ne, interpret = handle.meta
    if acc.size != n:
        raise ValueError(
            f"accumulator has {acc.size} elements but the transport "
            f"handle landed a {n}-element payload")
    acc_chunks = _pad_rows(acc.reshape(-1), nb * c).reshape(
        (nb,) + handle.recv[0].shape[2:])
    out = _edge_wait_call(kind, interpret, acc_chunks, handle.recv, ne)
    out = out.reshape(-1)[:n].reshape(acc.shape)
    if weight is not None:
        out = acc + (out - acc) * jnp.asarray(weight, acc.dtype)
    return out


def gossip_edge_axpy(acc, parts, dests, axis_name: str, spec,
                     interpret: bool = False,
                     chunk_elems: int = DEFAULT_CHUNK_ELEMS, weight=None,
                     collective_id: int = 0):
    """``acc + w·decode(permute(parts))`` — the fused spelling: a
    :func:`gossip_edge_start` immediately consumed by its
    :func:`gossip_edge_wait`.

    Drop-in replacement for the XLA seam
    ``acc + codec.decode(tuple(lax.ppermute(p, axis, pairs) for p in
    parts), like)`` inside :func:`..parallel.collectives._round_fn` —
    synchronous callers (and the parity suite) exercise exactly the two
    kernels the split overlap path runs, so one pin covers both.
    """
    if spec is not None and spec.kind in ("f32", "bf16"):
        # single-edge parts may be leaf-shaped (the f32 lane ships the
        # message as-is; bf16 encode keeps the leaf shape): flatten to
        # the stacked [E=1, n] transport convention
        parts = tuple(p.reshape(1, -1) for p in parts)
    handle = gossip_edge_start(parts, dests, axis_name, spec,
                               n_decoded=acc.size, interpret=interpret,
                               chunk_elems=chunk_elems,
                               collective_id=collective_id)
    return gossip_edge_wait(handle, acc, weight=weight)


# -- CI selftest (scripts/gossipkernel.py) ----------------------------------


def _selftest() -> int:
    """Interpret-mode kernel acceptance on the world-8 virtual CPU mesh:
    the fused spelling must match the XLA decode+axpy bit-for-bit on
    the f32 passthrough and to f32 tolerance on int8, including a
    chunked (multi-grid-step) payload with a ragged tail; the split
    start/wait pair must equal the fused spelling bit-for-bit; and one
    edge-folded (E=2) call must equal two sequential single-edge calls.
    """
    import sys

    from jax.sharding import PartitionSpec as P

    from ..parallel import wire
    from ..parallel.mesh import GOSSIP_AXIS, make_gossip_mesh

    world = 8
    if jax.device_count() < world:
        print(f"gossip-kernel selftest FAILED: needs {world} devices, "
              f"have {jax.device_count()} (run via "
              "scripts/gossipkernel.py)", file=sys.stderr)
        return 1
    failures: list[str] = []
    mesh = make_gossip_mesh(world)
    dests = np.asarray([(r + 1) % world for r in range(world)])
    dests2 = np.asarray([(r + 3) % world for r in range(world)])
    rng = np.random.default_rng(0)
    # ragged: 3 chunks at chunk_elems=128 with a 44-element tail
    n = 300
    x = rng.normal(size=(world, n)).astype(np.float32)
    codec = wire.Int8Codec(64)

    def both_lanes(xr):
        xr = xr.reshape(-1)
        acc = xr * 0.25
        pairs = [(s, int(dests[s])) for s in range(world)]
        # f32 passthrough lane
        k_f32 = gossip_edge_axpy(acc, (xr,), dests, GOSSIP_AXIS,
                                 wire.F32.kernel_spec(), interpret=True,
                                 chunk_elems=128)
        x_f32 = acc + jax.lax.ppermute(xr, GOSSIP_AXIS, pairs)
        # int8 lane (shared encode, in-kernel vs XLA decode)
        parts = codec.encode(xr)
        k_i8 = gossip_edge_axpy(acc, parts, dests, GOSSIP_AXIS,
                                codec.kernel_spec(), interpret=True,
                                chunk_elems=128)
        x_i8 = acc + codec.decode(
            tuple(jax.lax.ppermute(p, GOSSIP_AXIS, pairs)
                  for p in parts), xr)
        # split lane: start at the "top", wait at the "bottom" — must
        # equal the fused spelling bit-for-bit (it IS the same pair of
        # kernels, handed off through the TransportHandle)
        h = gossip_edge_start((xr,), dests, GOSSIP_AXIS,
                              wire.F32.kernel_spec(), n_decoded=n,
                              interpret=True, chunk_elems=128,
                              collective_id=1)
        s_f32 = gossip_edge_wait(h, acc)
        # bucketed/edge-folded lane: ONE kernel program serving two
        # edges vs two sequential single-edge calls
        stacked = jnp.stack([xr, xr * 0.5])
        h2 = gossip_edge_start((stacked,), np.stack([dests, dests2]),
                               GOSSIP_AXIS, wire.F32.kernel_spec(),
                               n_decoded=n, interpret=True,
                               chunk_elems=128, collective_id=2)
        folded = gossip_edge_wait(h2, acc)
        seq = gossip_edge_axpy(acc, (xr,), dests, GOSSIP_AXIS,
                               wire.F32.kernel_spec(), interpret=True,
                               chunk_elems=128, collective_id=3)
        seq = gossip_edge_axpy(seq, (xr * 0.5,), dests2, GOSSIP_AXIS,
                               wire.F32.kernel_spec(), interpret=True,
                               chunk_elems=128, collective_id=4)
        return tuple(t[None] for t in (k_f32, x_f32, k_i8, x_i8,
                                       s_f32, folded, seq))

    # interpret mode: see train/step.py::shard_train_step on check_vma
    fn = jax.jit(jax.shard_map(both_lanes, mesh=mesh,
                               in_specs=P(GOSSIP_AXIS),
                               out_specs=(P(GOSSIP_AXIS),) * 7,
                               check_vma=False))
    k_f32, x_f32, k_i8, x_i8, s_f32, folded, seq = map(
        np.asarray, jax.block_until_ready(fn(x)))
    if not np.array_equal(k_f32, x_f32):
        failures.append(
            f"f32 passthrough lane diverged from XLA ppermute "
            f"(max |d| {np.abs(k_f32 - x_f32).max():.2e}); the fused "
            "transport must be bit-identical")
    d8 = np.abs(k_i8 - x_i8).max()
    if d8 > 1e-6:
        failures.append(
            f"int8 in-kernel dequant drifted {d8:.2e} from the XLA "
            "decode (same scales, same op order — should be aligned)")
    if not np.array_equal(s_f32, k_f32):
        failures.append(
            "split start/wait diverged from the fused spelling (max |d| "
            f"{np.abs(s_f32 - k_f32).max():.2e}); the handle hand-off "
            "must be a pure re-association of the same two kernels")
    d_fold = np.abs(folded - seq).max()
    if d_fold > 1e-6:
        failures.append(
            f"edge-folded (E=2) call drifted {d_fold:.2e} from two "
            "sequential single-edge calls — the fold must accumulate "
            "edges in order")
    # a zero handle lands a zero contribution (the thinning skip branch)
    zero_h = empty_transport_handle(codec.kernel_spec(), n, 1,
                                    interpret=True, chunk_elems=128)
    z = np.asarray(gossip_edge_wait(zero_h, jnp.asarray(x[0])))
    if not np.array_equal(z, x[0]):
        failures.append("waiting an empty_transport_handle must be the "
                        "identity on the accumulator")
    # resolver contract: typed rejection instead of a Mosaic crash
    try:
        resolve_gossip_kernel("pallas", interpret=False)
        if jax.default_backend() != "tpu":
            failures.append("resolve_gossip_kernel('pallas') on a "
                            "non-TPU backend did not raise")
    except KernelBackendError:
        pass
    if resolve_gossip_kernel("auto", interpret=True) is None:
        failures.append("auto+interpret must resolve to the kernel lane")
    if resolve_gossip_kernel("xla") is not None:
        failures.append("'xla' must resolve to the ppermute lane")

    if failures:
        for f in failures:
            print(f"gossip-kernel selftest FAILED: {f}", file=sys.stderr)
        return 1
    print(f"gossip-kernel selftest: OK (world {world}, payload {n} over "
          f"3 chunks: f32 lane bit-identical, int8 lane max |d| "
          f"{d8:.1e}; split start/wait == fused, E=2 fold == sequential "
          f"(|d| {d_fold:.1e}), zero-handle wait is identity; "
          "pallas-on-cpu rejected with a typed error)")
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="gossipkernel",
        description="Split Pallas gossip transport: CI selftest")
    ap.add_argument("--selftest", action="store_true",
                    help="run the interpret-mode kernel self-check")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    ap.error("choose --selftest")
    return 2
