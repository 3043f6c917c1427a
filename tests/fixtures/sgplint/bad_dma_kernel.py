"""SGPL013: Pallas DMA/semaphore hygiene violations.

Three kernel-local hazards (a DMA started but never waited, a wait
that only happens on one control path, a barrier-semaphore arity
mismatch) plus the whole-program one: the same ``collective_id``
integer literal at two call sites aliases two logically distinct
collectives onto one hardware slot — the PR 15 review finding.
``ok_dma_kernel.py`` mirrors the shipped ``ops/gossip_kernel.py``
idioms and stays silent.
"""

import functools

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _leaky_kernel(nsteps, x_ref, y_ref, send_sem, recv_sem):
    rdma = pltpu.make_async_remote_copy(  # EXPECT: SGPL013
        src_ref=x_ref, dst_ref=y_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=1)
    rdma.start()
    # no rdma.wait(): the copy can still be in flight when the kernel
    # exits and its buffers are reused
    y_ref[...] = y_ref[...] * nsteps


def _conditional_wait_kernel(k, x_ref, y_ref, sem):
    cp = pltpu.make_async_copy(x_ref, y_ref, sem)  # EXPECT: SGPL013
    cp.start()
    if k == 0:
        cp.wait()  # waits on one control path only


def _barrier_arity_kernel(x_ref, y_ref):
    bsem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bsem, inc=1, device_id=0)
    pltpu.semaphore_signal(bsem, inc=1, device_id=1)
    pltpu.semaphore_wait(bsem, 3)  # EXPECT: SGPL013
    y_ref[...] = x_ref[...]


def bad_transport(x):
    a = pl.pallas_call(
        functools.partial(_leaky_kernel, 4),
        out_shape=x,
        compiler_params=pltpu.CompilerParams(collective_id=7),  # EXPECT: SGPL013
    )(x)
    b = pl.pallas_call(
        _conditional_wait_kernel,
        out_shape=x,
        compiler_params=pltpu.CompilerParams(collective_id=7),  # EXPECT: SGPL013
    )(a)
    return pl.pallas_call(_barrier_arity_kernel, out_shape=x)(b)
