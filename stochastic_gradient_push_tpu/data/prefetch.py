"""Host->device batch prefetch: overlap the transfer with compute.

The train loop dispatches a step and blocks until it completes; the next
batch's host->device copy then runs in the gap and serializes PCIe
traffic behind compute (bench.py pins its data on device so that it
measures the step alone).  :class:`DevicePrefetcher` wraps any
``(images, labels)`` loader and device_puts batches on a background
thread with a small queue, so batch k+1's transfer rides inside step k's
compute window (``device_put`` is async; the queue depth bounds host
memory).

Scope (ROADMAP's deferred "chunk-level device-put prefetch", now behind
a flag): single-process meshes, non-scanned path (``scan_steps == 1`` —
scan chunks are host-stacked before transfer, which would force the
arrays back to host).  The Trainer enables it via
``TrainerConfig.prefetch``; measured on-chip before being defaulted
(docs/MFU_ANALYSIS.md round-5 section).
"""

from __future__ import annotations

import queue
import threading
import typing as tp

import jax
from jax.sharding import NamedSharding

__all__ = ["DevicePrefetcher"]

_STOP = object()


class DevicePrefetcher:
    """Iterate ``loader``, device_putting each ``(x, y)`` ``depth`` ahead.

    Delegates ``len``/``set_epoch``/``fast_forward`` so it can stand in
    for the wrapped loader anywhere in the train loop.  Iteration errors
    on the worker thread re-raise on the consumer.
    """

    def __init__(self, loader, mesh, spec, depth: int = 2):
        self.loader = loader
        self.sharding = NamedSharding(mesh, spec)
        self.depth = max(1, int(depth))

    def __len__(self) -> int:
        return len(self.loader)

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def fast_forward(self, n: int) -> None:
        if hasattr(self.loader, "fast_forward"):
            self.loader.fast_forward(n)

    def __iter__(self) -> tp.Iterator:
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def put(item) -> bool:
            # bounded put: an abandoned consumer (epoch cap) sets `stop`
            # from the generator's finally, so the worker exits instead
            # of blocking on a full queue forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def work():
            try:
                for x, y in self.loader:
                    if not put((jax.device_put(x, self.sharding),
                                jax.device_put(y, self.sharding))):
                        return
            except BaseException as e:  # sgplint: disable=SGPL007
                # (deliberate transport: surfaces on the consumer side,
                # which re-raises it — see the isinstance check below)
                put(e)
                return
            put(_STOP)

        t = threading.Thread(target=work, daemon=True,
                             name="device-prefetch")
        t.start()
        try:
            while True:
                item = q.get()
                if item is _STOP:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
