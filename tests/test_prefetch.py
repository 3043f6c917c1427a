"""DevicePrefetcher (data/prefetch.py): overlap H2D with compute."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.data import (DistributedSampler,
                                               ShardedLoader)
from stochastic_gradient_push_tpu.data.prefetch import DevicePrefetcher
from stochastic_gradient_push_tpu.parallel import GOSSIP_AXIS, \
    make_gossip_mesh


def _loader(world=8, batch=2, n=64):
    rng = np.random.default_rng(0)
    images = rng.normal(size=(n, 4, 4, 3)).astype(np.float32)
    labels = rng.integers(0, 4, n).astype(np.int32)
    sampler = DistributedSampler(n, world)
    return ShardedLoader(images, labels, batch, sampler), sampler


def test_prefetch_yields_same_batches_sharded():
    world = 8
    mesh = make_gossip_mesh(world)
    loader, sampler = _loader(world)
    sampler.set_epoch(0)
    plain = [(np.asarray(x), np.asarray(y)) for x, y in loader]
    sampler.set_epoch(0)
    pf = DevicePrefetcher(loader, mesh, P(GOSSIP_AXIS))
    assert len(pf) == len(loader)
    fetched = list(pf)
    assert len(fetched) == len(plain)
    for (x0, y0), (x1, y1) in zip(plain, fetched):
        # already on device with the gossip sharding
        assert isinstance(x1, jax.Array) and len(x1.sharding.device_set) \
            == world
        np.testing.assert_array_equal(x0, np.asarray(x1))
        np.testing.assert_array_equal(y0, np.asarray(y1))


def test_prefetch_early_abandon_does_not_deadlock():
    world = 8
    mesh = make_gossip_mesh(world)
    loader, sampler = _loader(world, n=128)
    sampler.set_epoch(0)
    pf = iter(DevicePrefetcher(loader, mesh, P(GOSSIP_AXIS), depth=1))
    next(pf)
    pf.close()  # the generator's finally stops the worker thread
    # a second pass works fine after abandonment
    sampler.set_epoch(0)
    n = sum(1 for _ in DevicePrefetcher(loader, mesh, P(GOSSIP_AXIS)))
    assert n == len(loader)


def test_prefetch_propagates_loader_errors():
    import pytest

    mesh = make_gossip_mesh(8)

    class Boom:
        def __iter__(self):
            yield (np.zeros((8, 1, 4, 4, 3), np.float32),
                   np.zeros((8, 1), np.int32))
            raise RuntimeError("loader died")

        def __len__(self):
            return 2

    pf = DevicePrefetcher(Boom(), mesh, P(GOSSIP_AXIS))
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="loader died"):
        next(it)
