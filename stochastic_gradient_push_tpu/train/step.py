"""The jitted train/eval steps: model + algorithm + optimizer + schedule.

This is the compiled replacement for the reference's hot loop
(gossip_sgd.py:369-426) *and* the wrapper machinery it drives: forward-pre
hook (query + de-bias), backward hook (bias), optimizer step, transfer, and
the gossip thread's mix all become one XLA program per rank
(SURVEY.md §3.1).  The loop body does:

    pre_step  → overlap: LAUNCH round t's ppermute at the top of the
                step, so XLA schedules the collective behind the
                forward/backward (sync: no-op)
    eval      → de-biased params  →  forward/backward (bf16-friendly)
    reduce    → exact local/AR gradient averaging
    SGD       → torch-compatible update on the numerator params, LR from the
                compiled schedule
    post_step → sync: the gossip round (ppermute over ICI);
                overlap: consume the round launched staleness−1 steps
                ago at the bottom of the step

Everything is sharded over the gossip mesh axis with ``shard_map``: each
rank holds its own model replica (leading world dimension), its own batch
shard, and its own gossip state.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..algorithms.api import GossipAlgorithm
from ..parallel.collectives import as_scalar
from ..parallel.mesh import GOSSIP_AXIS
from ..telemetry import names
from ..utils import step_store
from .metrics import accuracy_topk, kl_div_loss, one_hot
from .state import TrainState

__all__ = ["build_train_step", "build_eval_step", "shard_train_step",
           "shard_scanned_train_step", "shard_eval_step", "restack",
           "replicate_state", "unreplicate", "replica_spread"]


def restack(new_state: TrainState, metrics):
    """Put the shard's leading dimension back on a step's outputs, each
    part under the scope that produced it.  The expand is free, but it is
    the last operation on its array: where the compiler fuses it with the
    update that feeds it, the whole fusion carries the expand's name, and
    an unnamed expand would file the optimizer's fusions under no scope."""
    def under(scope, tree):
        with jax.named_scope(scope):
            return jax.tree.map(lambda a: a[None], tree)

    return (new_state.replace(
        step=under(names.SCOPE_OPTIMIZER, new_state.step),
        params=under(names.SCOPE_GOSSIP, new_state.params),
        batch_stats=under(names.SCOPE_FORWARD, new_state.batch_stats),
        opt_state=under(names.SCOPE_OPTIMIZER, new_state.opt_state),
        gossip=under(names.SCOPE_GOSSIP, new_state.gossip)),
        under(names.SCOPE_HEALTH, metrics))


def _device_normalize(images):
    """uint8 batches normalize ON DEVICE (fused by XLA into the stem
    conv): the loader ships raw pixels — a 4x smaller host->device
    transfer than float32 (data/streaming.py ``output="uint8"``).
    float batches pass through, already normalized on host."""
    if images.dtype != jnp.uint8:
        return images
    from ..data.imagefolder import IMAGENET_MEAN, IMAGENET_STD

    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32)
    std = jnp.asarray(IMAGENET_STD, jnp.float32)
    return (images.astype(jnp.float32) / 255.0 - mean) / std


def build_train_step(model, algorithm: GossipAlgorithm, tx, lr_schedule,
                     itr_per_epoch: int, num_classes: int,
                     local_axis: str | None = None,
                     label_smoothing: float = 0.0,
                     grad_accum: int = 1,
                     health_axis: str | None = None) -> tp.Callable:
    """Returns the per-rank step ``(state, images, labels) -> (state, metrics)``.

    Call inside ``shard_map`` (see :func:`shard_train_step`), or directly for
    single-device debugging.

    Args:
      model: flax module with ``__call__(x, train)``.
      algorithm: a :class:`GossipAlgorithm`.
      tx: gradient transformation from :func:`~.state.sgd` (LR applied here).
      lr_schedule: ``(epoch, itr, itr_per_epoch) -> lr`` (see lr.py).
      itr_per_epoch: static iterations per epoch for the schedule.
      num_classes: classifier width for one-hot targets.
      local_axis: optional intra-node mesh axis; gradients and BN stats are
        exactly averaged over it (≙ nprocs_per_node local all-reduce,
        distributed.py:551-562 and BN buffer sync :269-276).
      label_smoothing: soft-target smoothing through the KLDiv loss.
      grad_accum: split each batch into this many microbatches and
        accumulate gradients before the optimizer step — 1/grad_accum peak
        activation memory.  Exactly equivalent for BN-free models; with
        BatchNorm, normalization statistics are per-microbatch and the
        running-stats EMA advances once per microbatch, so dynamics differ
        slightly from the full batch (as with any microbatched BN).
      health_axis: when set (the gossip axis), consensus health signals
        (resilience/monitor.py) are computed after the gossip round and
        ride the metrics pytree — ps-weight drift, push-sum mass error,
        NaN/Inf counts, consensus-residual probe.  Each is a collective
        over this axis, so every rank reports the same value.
    """
    if grad_accum < 1:
        raise ValueError("grad_accum must be >= 1")

    # The step names its own phases (telemetry/names.py): every
    # operation below is traced under one ``jax.named_scope`` of the
    # vocabulary, which the compiled program carries as metadata (no
    # run-time cost) and a device trace shows as the operation's op_name.
    # The forward scope sits INSIDE loss_fn so autodiff marks its
    # transpose: backward operations read ``transpose(jvp(sgp.forward))``.
    # Accuracy metrics count as forward; under grad_accum the microbatch
    # sums count as gradient reduction.

    def train_step(state: TrainState, images, labels):
        with jax.named_scope(names.SCOPE_FORWARD):
            images = _device_normalize(images)
        with jax.named_scope(names.SCOPE_PRE_STEP):
            params, gstate = algorithm.pre_step(state.params, state.gossip)
            z = algorithm.eval_params(params, gstate)

        def loss_fn(p, x, y, batch_stats):
            with jax.named_scope(names.SCOPE_FORWARD):
                out, mutated = model.apply(
                    {"params": p, "batch_stats": batch_stats},
                    x, train=True, mutable=["batch_stats"])
                loss = kl_div_loss(
                    out, one_hot(y, num_classes, label_smoothing))
            return loss, (out, mutated["batch_stats"])

        def accuracy(out, y):
            with jax.named_scope(names.SCOPE_FORWARD):
                return accuracy_topk(out, y, topk=(1, 5))

        if grad_accum == 1:
            (loss, (logits, batch_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(z, images, labels,
                                       state.batch_stats)
            top1, top5 = accuracy(logits, labels)
        else:
            b = images.shape[0]
            if b % grad_accum:
                raise ValueError(
                    f"batch {b} not divisible by grad_accum {grad_accum}")
            micro = b // grad_accum
            xs = images.reshape((grad_accum, micro) + images.shape[1:])
            ys = labels.reshape((grad_accum, micro) + labels.shape[1:])

            def accum(carry, xy):
                g_sum, loss_sum, t1_sum, t5_sum, bstats = carry
                x, y = xy
                (l, (out, bstats)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(z, x, y, bstats)
                a1, a5 = accuracy(out, y)
                with jax.named_scope(names.SCOPE_REDUCE_GRADS):
                    return (jax.tree.map(jnp.add, g_sum, g), loss_sum + l,
                            t1_sum + a1, t5_sum + a5, bstats), None

            zero_g = jax.tree.map(jnp.zeros_like, z)
            # scalar accumulators derive from the (device-varying) images so
            # the scan carry type matches the body outputs (vma rules)
            zero_s = jnp.sum(images * 0.0).astype(jnp.float32)
            (g_sum, loss_sum, t1_sum, t5_sum, batch_stats), _ = lax.scan(
                accum, (zero_g, zero_s, zero_s, zero_s,
                        state.batch_stats), (xs, ys))
            with jax.named_scope(names.SCOPE_REDUCE_GRADS):
                grads = jax.tree.map(lambda g: g / grad_accum, g_sum)
                loss = loss_sum / grad_accum
                top1 = t1_sum / grad_accum
                top5 = t5_sum / grad_accum

        with jax.named_scope(names.SCOPE_REDUCE_GRADS):
            if local_axis is not None:
                # exact intra-node averaging of gradients and BN statistics
                # (≙ the local all-reduce group, distributed.py:551-562, and
                # BN buffer sync :269-276).  Params are *invariant* over the
                # local axis (sharded over the node axis only), so autodiff
                # already psums grads over local devices — divide by the
                # axis size to turn that sum into the mean.
                n_local = lax.axis_size(local_axis)
                grads = jax.tree.map(lambda g: g / n_local, grads)
                batch_stats = jax.tree.map(
                    lambda b: lax.pmean(b, local_axis), batch_stats)
            grads = algorithm.reduce_grads(grads)

        with jax.named_scope(names.SCOPE_OPTIMIZER):
            step = as_scalar(state.step)
            epoch = step // itr_per_epoch
            itr = step % itr_per_epoch
            lr = lr_schedule(epoch, itr, itr_per_epoch)

            updates, opt_state = tx.update(grads, state.opt_state, params)
            params = jax.tree.map(
                lambda p, u: p - lr.astype(p.dtype) * u, params, updates)
            next_step = state.step + 1

        with jax.named_scope(names.SCOPE_GOSSIP):
            params, gstate = algorithm.post_step(params, gstate)

        with jax.named_scope(names.SCOPE_HEALTH):
            # grad-norm observability (the reference logs none; handy for
            # divergence triage) — one reduce over the raveled grads
            from ..utils.flatten import global_norm
            metrics = {"loss": loss, "top1": top1, "top5": top5, "lr": lr,
                       "grad_norm": global_norm(grads)}
            if local_axis is not None:
                metrics = jax.tree.map(
                    lambda m: lax.pmean(m, local_axis), metrics)
            if health_axis is not None:
                # consensus health AFTER the gossip round: the signals see
                # the state the next step will train on.  Already identical
                # across ranks (each is a collective), so the local-axis
                # pmean above must not re-average them — append afterwards.
                # The overlap FIFO rides along so the monitor observes the
                # DRAINED view (in-flight mass is not a leak).
                from ..resilience.monitor import health_signals
                metrics.update(health_signals(
                    params, grads, gstate.ps_weight, health_axis,
                    ef_residual=gstate.ef_residual,
                    in_flight=gstate.in_flight))
        new_state = state.replace(
            step=next_step, params=params, batch_stats=batch_stats,
            opt_state=opt_state, gossip=gstate)
        return new_state, metrics

    return train_step


def build_eval_step(model, algorithm: GossipAlgorithm,
                    num_classes: int) -> tp.Callable:
    """Per-rank eval step: de-biased params, running BN stats, no gossip
    (≙ ``validate``, gossip_sgd.py:440-471 — every rank evaluates
    independently, no collectives)."""

    def eval_step(state: TrainState, images, labels):
        images = _device_normalize(images)
        z = algorithm.val_params(state.params, state.gossip)
        logits = model.apply(
            {"params": z, "batch_stats": state.batch_stats},
            images, train=False)
        loss = kl_div_loss(logits, one_hot(labels, num_classes))
        top1, top5 = accuracy_topk(logits, labels, topk=(1, 5))
        return {"loss": loss, "top1": top1, "top5": top5}

    return eval_step


def shard_train_step(step_fn, mesh, axis_name: str = GOSSIP_AXIS,
                     local_axis: str | None = None,
                     check_vma: bool = True):
    """Wrap a per-rank step for a gossip mesh.

    Globally, every state leaf carries a leading gossip-rank dimension
    sharded over ``axis_name`` (each rank = one model replica); batches
    carry a leading dimension covering *all* devices.  The per-shard leading
    axis of size 1 is squeezed away before the per-rank step runs and
    restored after, so ``step_fn`` is written in plain single-rank terms.

    With ``local_axis`` (hierarchical ``(node, local)`` mesh,
    ≙ nprocs_per_node, distributed.py:62-78): batches shard over both axes
    (one shard per device), while state shards over the node axis only —
    the step's intra-node ``pmean`` keeps local replicas identical, which is
    what makes the node-only state sharding valid.

    ``check_vma=False`` is for a step whose gossip rides the Pallas
    kernel lane in *interpret* mode (``KernelLane.interpret``, tests
    only): the interpreter evaluates the kernel body on the step's own
    tracers and cannot type its mix of varying and unvarying operands.
    A compiled kernel is an opaque custom call and keeps the check.
    """
    batch_spec = (P(axis_name) if local_axis is None
                  else P((axis_name, local_axis)))

    def wrapped(state, images, labels):
        squeeze = lambda t: jax.tree.map(lambda a: a[0], t)
        return restack(*step_fn(
            squeeze(state), squeeze(images), squeeze(labels)))

    specs = dict(in_specs=(P(axis_name), batch_spec, batch_spec),
                 out_specs=(P(axis_name), P(axis_name)), check_vma=check_vma)
    sharded = jax.shard_map(wrapped, mesh=mesh, **specs)
    sharded.__name__ = names.MODULE_TRAIN_STEP
    return step_store.jit(sharded, material=(wrapped, mesh, specs),
                          donate_argnums=(0,))


def shard_scanned_train_step(step_fn, mesh, n_steps: int,
                             axis_name: str = GOSSIP_AXIS,
                             local_axis: str | None = None,
                             check_vma: bool = True):
    """Fuse ``n_steps`` train steps into ONE compiled program via
    ``lax.scan``.

    The reference pays a host round-trip per iteration (Python loop →
    dispatch → gossip thread handshake).  Here the whole micro-epoch is a
    single XLA program: dispatch overhead is amortized ``n_steps``×, and
    the latency-hiding scheduler can pipeline each step's gossip ppermute
    against the next step's compute without the host in the way.

    Batches gain a leading scan dimension: ``images[n_steps, world, ...]``.
    Returns ``(state, metrics)`` with metrics stacked ``[world, n_steps]``.
    ``check_vma`` as in :func:`shard_train_step`.
    """
    batch_spec = (P(None, axis_name) if local_axis is None
                  else P(None, (axis_name, local_axis)))

    def wrapped(state, images, labels):
        squeeze = lambda t: jax.tree.map(lambda a: a[0], t)
        # per-shard batches are [n_steps, 1, ...] → drop the shard axis
        images = jax.tree.map(lambda a: a[:, 0], images)
        labels = jax.tree.map(lambda a: a[:, 0], labels)

        def body(st, batch):
            im, lb = batch
            st, metrics = step_fn(st, im, lb)
            return st, metrics

        return restack(*lax.scan(body, squeeze(state), (images, labels)))

    specs = dict(in_specs=(P(axis_name), batch_spec, batch_spec),
                 out_specs=(P(axis_name), P(axis_name)), check_vma=check_vma)
    sharded = jax.shard_map(wrapped, mesh=mesh, **specs)
    sharded.__name__ = names.MODULE_TRAIN_STEP_SCAN
    return step_store.jit(sharded, material=(wrapped, mesh, specs),
                          donate_argnums=(0,))


def shard_eval_step(eval_fn, mesh, axis_name: str = GOSSIP_AXIS,
                    local_axis: str | None = None):
    """Wrap a per-rank eval step for a gossip mesh (see
    :func:`shard_train_step`); returns per-rank metrics stacked over the
    gossip dimension."""
    batch_spec = (P(axis_name) if local_axis is None
                  else P((axis_name, local_axis)))

    def wrapped(state, images, labels):
        squeeze = lambda t: jax.tree.map(lambda a: a[0], t)
        metrics = eval_fn(squeeze(state), squeeze(images), squeeze(labels))
        if local_axis is not None:
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, local_axis), metrics)
        return jax.tree.map(lambda a: a[None], metrics)

    sharded = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(axis_name), batch_spec, batch_spec),
        out_specs=P(axis_name))
    return jax.jit(sharded)


def replicate_state(state: TrainState, world_size: int) -> TrainState:
    """Stack a single-rank state into the leading world dimension.

    Every rank starts from identical values (same seed as the reference,
    gossip_sgd.py:172-175); they diverge through data and gossip.
    """
    return jax.tree.map(
        lambda a: jnp.broadcast_to(
            jnp.asarray(a)[None], (world_size,) + jnp.shape(a)),
        state)


def unreplicate(tree, rank: int = 0):
    """Extract one rank's slice of a world-stacked pytree."""
    return jax.tree.map(lambda a: np.asarray(a)[rank], tree)


def replica_spread(state: TrainState, algorithm: GossipAlgorithm) -> dict:
    """Cross-replica disagreement of the de-biased parameters.

    Observability for decentralized training the reference lacks: how far
    apart the rank replicas actually are.  Returns max/mean absolute
    deviation from the rank-mean over all parameters and the per-rank-
    averaged L2 norm of the disagreement (host-side numpy on a
    world-stacked state).
    """
    z = jax.vmap(algorithm.eval_params)(state.params, state.gossip)
    leaves = [np.asarray(l) for l in jax.tree.leaves(z)]
    world = leaves[0].shape[0]
    flat = np.concatenate([l.reshape(world, -1) for l in leaves], axis=1)
    dev = np.abs(flat - flat.mean(axis=0, keepdims=True))
    return {"max_spread": float(dev.max()),
            "mean_spread": float(dev.mean()),
            "spread_l2": float(np.linalg.norm(dev) / np.sqrt(world)),
            "param_scale": float(np.abs(flat).max())}
