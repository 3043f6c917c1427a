"""Good twin of ``bad_dma_kernel.py``: the shipped transport idioms.

Mirrors ``ops/gossip_kernel.py``: descriptors collected into a list,
all started, all waited; an entry barrier whose wait amount matches its
signal count; a re-made descriptor waited through the make-again
pattern; and ``collective_id`` derived from the slot pool (one pinned
literal at a single site is also fine — only cross-site reuse fires).
Zero findings expected.
"""

import functools

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

COLLECTIVE_ID_SLOTS = 16


def _edge_kernel(nparts, x_ref, y_ref, send_sem, recv_sem, bsem_unused):
    # entry barrier: both neighbours signalled, both signals awaited
    bsem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bsem, inc=1, device_id=0)
    pltpu.semaphore_signal(bsem, inc=1, device_id=1)
    pltpu.semaphore_wait(bsem, 2)

    rdmas = []
    for part in range(nparts):
        rdmas.append(pltpu.make_async_remote_copy(
            src_ref=x_ref, dst_ref=y_ref, send_sem=send_sem,
            recv_sem=recv_sem, device_id=part))
    for r in rdmas:
        r.start()
    for r in rdmas:
        r.wait()


def _local_stage_kernel(x_ref, y_ref, sem):
    # the make-twice pattern: start on one descriptor, wait on a
    # re-made twin with identical arguments
    pltpu.make_async_copy(x_ref, y_ref, sem).start()
    pltpu.make_async_copy(x_ref, y_ref, sem).wait()


def edge_transport(x, leaf_slot):
    staged = pl.pallas_call(_local_stage_kernel, out_shape=x)(x)
    return pl.pallas_call(
        functools.partial(_edge_kernel, 2),
        out_shape=x,
        compiler_params=pltpu.CompilerParams(
            collective_id=leaf_slot % COLLECTIVE_ID_SLOTS),
    )(staged)


def pinned_probe(x):
    # a single pinned literal site is legitimate (tests pin slot
    # semantics this way); only cross-site reuse is a hazard
    return pl.pallas_call(
        _local_stage_kernel,
        out_shape=x,
        compiler_params=pltpu.CompilerParams(collective_id=3),
    )(x)
