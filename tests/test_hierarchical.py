"""Hierarchical (node, local) mesh: exact local averaging + node gossip
(≙ nprocs_per_node, distributed.py:62-78, 278-296, 551-562)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.models import TinyMLP
from stochastic_gradient_push_tpu.parallel import (
    LOCAL_AXIS,
    NODE_AXIS,
    make_hierarchical_mesh,
)
from stochastic_gradient_push_tpu.topology import (
    DynamicDirectedExponentialGraph,
    build_schedule,
)
from stochastic_gradient_push_tpu.train import (
    LRSchedule,
    build_train_step,
    init_train_state,
    replicate_state,
    sgd,
    shard_train_step,
)

NODES, LOCAL = 4, 2
BATCH, IMG, CLASSES = 4, 8, 4


def test_hierarchical_mesh_training_step():
    mesh = make_hierarchical_mesh(LOCAL, NODES * LOCAL)
    assert mesh.shape == {NODE_AXIS: NODES, LOCAL_AXIS: LOCAL}

    model = TinyMLP(num_classes=CLASSES)
    sched = build_schedule(
        DynamicDirectedExponentialGraph(NODES, peers_per_itr=1))
    alg = sgp(sched, NODE_AXIS)
    tx = sgd(momentum=0.9, weight_decay=1e-4)
    lrs = LRSchedule(ref_lr=0.1, batch_size=BATCH, world_size=NODES * LOCAL)
    step = build_train_step(model, alg, tx, lrs, itr_per_epoch=10,
                            num_classes=CLASSES, local_axis=LOCAL_AXIS)
    train_fn = shard_train_step(step, mesh, NODE_AXIS, LOCAL_AXIS)

    state = replicate_state(
        init_train_state(model, jax.random.PRNGKey(0),
                         jnp.zeros((BATCH, IMG, IMG, 3)), tx, alg), NODES)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(NODES * LOCAL, BATCH, IMG, IMG, 3)).astype(np.float32)
    y = rng.integers(0, CLASSES, size=(NODES * LOCAL, BATCH)).astype(np.int32)

    losses = []
    for i in range(30):
        state, metrics = train_fn(state, x, y)
        jax.block_until_ready(state)
        losses.append(float(np.mean(np.asarray(metrics["loss"]))))

    # training works and state stays node-stacked
    assert losses[-1] < losses[0]
    assert np.asarray(state.step).shape == (NODES,)
    w = np.asarray(state.gossip.ps_weight)
    np.testing.assert_allclose(w, np.ones_like(w), atol=1e-4)


def test_hierarchical_local_grads_match_wider_batch():
    """One hierarchical step (2 local devices x batch B) must equal a flat
    gossip step with per-rank batch 2B: exact local averaging is just a
    bigger effective batch."""
    from stochastic_gradient_push_tpu.parallel import (
        GOSSIP_AXIS, make_gossip_mesh)

    model = TinyMLP(num_classes=CLASSES)
    tx = sgd(momentum=0.9, weight_decay=1e-4)
    lrs = LRSchedule(ref_lr=0.1, batch_size=BATCH, world_size=NODES * LOCAL)
    sched = build_schedule(
        DynamicDirectedExponentialGraph(NODES, peers_per_itr=1))

    rng = np.random.default_rng(1)
    x = rng.normal(size=(NODES * LOCAL, BATCH, IMG, IMG, 3)).astype(np.float32)
    y = rng.integers(0, CLASSES, size=(NODES * LOCAL, BATCH)).astype(np.int32)

    # hierarchical: (4 nodes, 2 local)
    mesh_h = make_hierarchical_mesh(LOCAL, NODES * LOCAL)
    alg_h = sgp(sched, NODE_AXIS)
    step_h = build_train_step(model, alg_h, tx, lrs, itr_per_epoch=10,
                              num_classes=CLASSES, local_axis=LOCAL_AXIS)
    fn_h = shard_train_step(step_h, mesh_h, NODE_AXIS, LOCAL_AXIS)
    st_h = replicate_state(
        init_train_state(model, jax.random.PRNGKey(0),
                         jnp.zeros((BATCH, IMG, IMG, 3)), tx, alg_h), NODES)
    st_h, _ = fn_h(st_h, x, y)

    # flat: 4 ranks with the concatenated local batches
    mesh_f = make_gossip_mesh(NODES)
    alg_f = sgp(sched, GOSSIP_AXIS)
    step_f = build_train_step(model, alg_f, tx, lrs, itr_per_epoch=10,
                              num_classes=CLASSES)
    fn_f = shard_train_step(step_f, mesh_f, GOSSIP_AXIS)
    st_f = replicate_state(
        init_train_state(model, jax.random.PRNGKey(0),
                         jnp.zeros((BATCH * LOCAL, IMG, IMG, 3)), tx, alg_f),
        NODES)
    xf = x.reshape(NODES, LOCAL * BATCH, IMG, IMG, 3)
    yf = y.reshape(NODES, LOCAL * BATCH)
    st_f, _ = fn_f(st_f, xf, yf)

    for a, b in zip(jax.tree.leaves(st_h.params),
                    jax.tree.leaves(st_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_hierarchical_requires_matching_local_axis():
    from stochastic_gradient_push_tpu.train.loop import Trainer, TrainerConfig

    mesh = make_hierarchical_mesh(LOCAL, NODES * LOCAL)
    cfg = TrainerConfig(nprocs_per_node=4)  # wrong: mesh local axis is 2
    with pytest.raises(ValueError, match="hierarchical mesh"):
        Trainer(cfg, TinyMLP(num_classes=4), mesh, (4, 8, 8, 3))
