"""Wall-clock-asynchronous AD-PSGD (train/async_bilat.py).

The executable counterpart of the reference's separate averaging process
(ad_psgd.py:120-133): averaging displacements are computed host-side
from step-k params and adopted at step k+δ with δ set by real timing.
"""

import numpy as np
import pytest

from stochastic_gradient_push_tpu.topology import (
    DynamicBipartiteExponentialGraph, build_pairing_schedule)
from stochastic_gradient_push_tpu.train.async_bilat import (
    AsyncBilateralAverager)


def _pairing(world=4):
    return build_pairing_schedule(
        DynamicBipartiteExponentialGraph(world, peers_per_itr=1))


def test_displacement_is_half_the_pair_gap():
    """One averaging round moves each rank halfway to its partner —
    the bilateral update x <- (x + x_partner)/2 (≙ ad_psgd.py:358-361),
    expressed as an additive displacement so intermediate SGD progress
    is never discarded."""
    import jax.numpy as jnp

    av = AsyncBilateralAverager(_pairing(4))
    params = {"w": jnp.asarray([[0.0], [2.0], [4.0], [6.0]])}
    av.start()
    try:
        av.publish(0, params)
        # wait for the thread's deposit
        for _ in range(500):
            new, adopted = av.maybe_adopt(3, params)
            if adopted:
                break
            import time
            time.sleep(0.01)
        assert adopted, "averaging thread never deposited"
    finally:
        av.stop()
    w = np.asarray(new["w"]).ravel()
    partner = av.pairing[0]
    expect = np.array([0.0, 2, 4, 6])
    expect = expect + (expect[partner] - expect) * 0.5
    np.testing.assert_allclose(w, expect)
    # the adoption was recorded with its true step gap
    s = av.staleness_summary()
    assert s["adoptions"] == 1 and s["staleness_max"] == 3


def test_mailbox_overwrites_not_queues():
    """Only the newest averaging result survives — like the reference's
    shared buffer, a slow consumer sees ONE (stale) displacement, not a
    backlog of superseded ones."""
    import time

    import jax.numpy as jnp

    av = AsyncBilateralAverager(_pairing(4))
    p1 = {"w": jnp.asarray([[0.0], [2.0], [4.0], [6.0]])}
    p2 = {"w": jnp.asarray([[10.0], [10.0], [10.0], [10.0]])}
    av.start()
    try:
        av.publish(0, p1)
        time.sleep(0.3)
        av.publish(1, p2)
        time.sleep(0.3)
        new, adopted = av.maybe_adopt(2, p2)
    finally:
        av.stop()
    assert adopted
    # consensus params -> zero displacement: proves the p2-round result
    # replaced the p1 one rather than queueing behind it
    np.testing.assert_allclose(np.asarray(new["w"]),
                               np.asarray(p2["w"]))


@pytest.mark.slow
def test_trainer_bilat_async_converges_replicas(tmp_path):
    """End-to-end through the Trainer: local-SGD compiled step + host
    averaging keeps replicas in consensus (spread far below a no-comm
    control) and records a staleness distribution."""
    import jax

    from stochastic_gradient_push_tpu.algorithms.api import GossipAlgorithm
    from stochastic_gradient_push_tpu.data import (
        DistributedSampler, ShardedLoader, synthetic_classification)
    from stochastic_gradient_push_tpu.models import TinyCNN
    from stochastic_gradient_push_tpu.parallel import make_gossip_mesh
    from stochastic_gradient_push_tpu.train.loop import (
        Trainer, TrainerConfig)
    from stochastic_gradient_push_tpu.train.step import replica_spread

    world, batch, classes, img = 8, 4, 8, 12
    images, labels = synthetic_classification(
        world * batch * 6, num_classes=classes, image_size=img, seed=3)

    def run(bilat_async):
        cfg = TrainerConfig(
            push_sum=False, bilat=True, bilat_async=bilat_async,
            graph_class=DynamicBipartiteExponentialGraph,
            lr=0.1, warmup=False, lr_schedule={},
            batch_size=batch, num_epochs=3, num_itr_ignore=0,
            checkpoint_dir=str(tmp_path / f"async_{bilat_async}"),
            num_classes=classes, verbose=False, heartbeat_timeout=0,
            train_fast=True)
        if not bilat_async:
            # no-comm control: same config but bilateral averaging OFF
            cfg.bilat = False
            cfg.all_reduce = False
            cfg.push_sum = False
            cfg.graph_class = None

            class _Local(Trainer):
                def make_algorithm(self, ppi):
                    return GossipAlgorithm()
            trainer_cls = _Local
        else:
            trainer_cls = Trainer
        mesh = make_gossip_mesh(world)
        trainer = trainer_cls(cfg, TinyCNN(num_classes=classes), mesh,
                              sample_input_shape=(batch, img, img, 3))
        state = trainer.init_state()
        sampler = DistributedSampler(len(images), world)
        loader = ShardedLoader(images, labels, batch, sampler)
        state, result = trainer.fit(state, loader, sampler, None)
        spread = replica_spread(state, GossipAlgorithm())
        return spread["mean_spread"], result

    spread_async, result = run(True)
    spread_local, _ = run(False)

    stats = result["async_bilat"]
    assert stats["adoptions"] > 0, stats
    assert stats["staleness_mean"] >= 0.0
    # host averaging must hold replicas together vs the no-comm control
    assert spread_async < spread_local * 0.5, (spread_async, spread_local)
