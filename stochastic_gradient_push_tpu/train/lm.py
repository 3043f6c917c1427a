"""Language-model training: gossip data parallelism × ring-attention
sequence parallelism on one 2-D mesh.

Composes the decentralized algorithms with long-context support: the mesh
is ``(gossip, seq)`` — model replicas gossip over the first axis exactly as
in image training, while each replica's sequence is sharded over the second
axis and attention runs as a ring (parallel/ring_attention.py).  The
reference has no counterpart (its transformer runs lived in an external
fairseq fork, SURVEY.md §5); this is the TPU-native extension the task
treats as first-class.

Sharding contract:
  * state: leading gossip dimension, replicated over ``seq``
    (pointwise sublayers need the full parameters; autodiff therefore
    psums gradients over ``seq`` and the step divides by the axis size)
  * tokens/targets: leading ``(gossip, seq)`` dimensions, each seq shard
    holding a contiguous block of every sequence; targets are pre-shifted
    globally by the data pipeline so no cross-shard shift is needed
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..algorithms.api import GossipAlgorithm
from ..parallel.collectives import as_scalar
from ..parallel.mesh import GOSSIP_AXIS
from ..telemetry import names
from ..utils import step_store
from .state import TrainState
from .step import restack

SEQ_AXIS = "seq"
TP_AXIS = "tp"
EP_AXIS = "ep"

__all__ = ["SEQ_AXIS", "TP_AXIS", "EP_AXIS", "make_dp_sp_mesh",
           "make_dp_tp_mesh", "make_dp_sp_tp_mesh", "make_dp_ep_mesh",
           "make_dp_ep_sp_mesh", "make_dp_ep_tp_mesh",
           "make_dp_ep_sp_tp_mesh",
           "build_lm_train_step", "shard_lm_train_step",
           "build_lm_eval_step", "shard_lm_eval_step",
           "shard_scanned_lm_step", "lm_loss", "mtp_loss",
           "init_lm_state", "apply_tp_sharding", "tp_sharding_tree",
           "ep_tp_sharding_tree",
           "init_lm_state_tp", "ep_state_specs", "init_lm_state_ep"]


def _make_mesh(dims: tuple, axes: tuple, devices) -> Mesh:
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(dims))
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(dims), axes)


def make_dp_sp_mesh(dp: int, sp: int, devices=None) -> Mesh:
    """2-D ``(gossip, seq)`` mesh: dp model replicas × sp sequence shards."""
    return _make_mesh((dp, sp), (GOSSIP_AXIS, SEQ_AXIS), devices)


def make_dp_tp_mesh(dp: int, tp: int, devices=None) -> Mesh:
    """2-D ``(gossip, tp)`` mesh: dp gossip replicas × tp-way tensor
    parallelism inside each replica."""
    return _make_mesh((dp, tp), (GOSSIP_AXIS, TP_AXIS), devices)


def make_dp_sp_tp_mesh(dp: int, sp: int, tp: int, devices=None) -> Mesh:
    """3-D ``(gossip, seq, tp)`` mesh: gossip data parallelism × ring
    sequence parallelism × GSPMD tensor parallelism, all composed."""
    return _make_mesh((dp, sp, tp), (GOSSIP_AXIS, SEQ_AXIS, TP_AXIS),
                      devices)


def make_dp_ep_mesh(dp: int, ep: int, devices=None) -> Mesh:
    """2-D ``(gossip, ep)`` mesh: gossip replicas × expert parallelism.

    The ep axis doubles as extra data parallelism for the non-MoE
    sublayers: each ep shard carries its own tokens, and ALL gradients
    — replicated leaves and expert slices alike — are exactly averaged
    over ep (like the hierarchical local axis); expert PARAMS are
    sharded over ep, but every shard's tokens contribute to every
    expert's gradient through the all_to_all.
    """
    return _make_mesh((dp, ep), (GOSSIP_AXIS, EP_AXIS), devices)


def make_dp_ep_tp_mesh(dp: int, ep: int, tp: int, devices=None) -> Mesh:
    """3-D ``(gossip, ep, tp)`` mesh: gossip × expert × tensor
    parallelism.

    Experts shard over the *manual* ep axis (all_to_all token dispatch)
    while the tp axis stays *auto*: GSPMD partitions each expert slice's
    FFN dims — and every dense sublayer's Megatron dims — over tp
    according to the arrays' own shardings (:func:`ep_tp_sharding_tree`).
    The manual collectives (gossip ppermute, ep all_to_all) never mention
    tp, so the two regimes compose without a hand-written hybrid kernel.
    """
    return _make_mesh((dp, ep, tp), (GOSSIP_AXIS, EP_AXIS, TP_AXIS),
                      devices)


def make_dp_ep_sp_tp_mesh(dp: int, ep: int, sp: int, tp: int,
                          devices=None) -> Mesh:
    """4-D ``(gossip, ep, seq, tp)`` mesh: every parallelism axis at
    once — gossip DP × expert dispatch × ring-attention sequence shards,
    with GSPMD tensor parallelism on the auto ``tp`` axis inside each
    (gossip, ep, seq) cell.  Same partial-manual recipe as ep × tp: the
    manual collectives never mention tp."""
    return _make_mesh((dp, ep, sp, tp),
                      (GOSSIP_AXIS, EP_AXIS, SEQ_AXIS, TP_AXIS), devices)


def make_dp_ep_sp_mesh(dp: int, ep: int, sp: int, devices=None) -> Mesh:
    """3-D ``(gossip, ep, seq)`` mesh: gossip × expert × ring-sequence
    parallelism.

    Each (gossip, ep) pair holds its own batch of sequences, sharded into
    ``sp`` contiguous blocks over ``seq``; every seq shard routes its
    block's tokens to experts with an all_to_all over ``ep`` (per-block
    routing, as in MoE × sp), and ring attention runs over ``seq`` within
    each (gossip, ep) slice.
    """
    return _make_mesh((dp, ep, sp), (GOSSIP_AXIS, EP_AXIS, SEQ_AXIS),
                      devices)


def batch_layout(gossip_axis: str, seq_axis: str | None = None,
                 ep_axis: str | None = None):
    """``(PartitionSpec, n_leading_sharded_dims)`` for a token batch on
    the given manual axes — the single source of truth for the batch
    layout, shared by every shard_* wrapper (lm and pp, train and eval)
    so the spec ladder cannot drift between them.  Dim order:
    ``[gossip, ep?, seq?]``."""
    axes = [gossip_axis]
    if ep_axis is not None:
        axes.append(ep_axis)
    if seq_axis is not None:
        axes.append(seq_axis)
    return P(*axes), len(axes)


def _is_expert_path(path) -> bool:
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    return any(n in ("experts_up", "experts_down") for n in names)


def ep_state_specs(state, gossip_axis: str = GOSSIP_AXIS,
                   ep_axis: str = EP_AXIS):
    """Per-leaf PartitionSpecs for an expert-parallel LM state: expert
    weight leaves shard ``(gossip, ep)`` on their leading dims, everything
    else replicates over ep with ``P(gossip)``.  Works on arrays/avals."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (P(gossip_axis, ep_axis)
                            if _is_expert_path(path)
                            else P(gossip_axis)),
        state)


# transformer modules whose kernels shard over the tp axis: column-parallel
# (output features split) then row-parallel (input features split), the
# Megatron pattern — GSPMD inserts the reduction after o/down projections.
# MoE expert stacks follow the same pattern on their trailing dims.
_TP_COLUMN = {"q", "k", "v", "up", "lm_head"}
_TP_ROW = {"o", "down"}
_TP_EXPERT_COLUMN = {"experts_up"}      # [E, D, F]: shard F
_TP_EXPERT_ROW = {"experts_down"}       # [E, F, D]: shard F


def _tp_tail(path, leaf, tp_axis: str) -> list:
    """Per-leaf PartitionSpec tail (dims after the leading gossip dim)
    with the Megatron tp placement: projection kernels column-/row-
    parallel by module name, expert stacks on their FFN dim, everything
    else replicated.  Shared by every tp-aware sharding tree so the
    classification rules exist exactly once."""
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    ndim = jnp.ndim(leaf)
    tail = [None] * (ndim - 1)
    if ndim >= 3 and names and names[-1] == "kernel":
        parent = names[-2]
        if parent in _TP_COLUMN:
            tail[-1] = tp_axis
        elif parent in _TP_ROW:
            tail[-2] = tp_axis
    elif ndim >= 4 and names:
        if names[-1] in _TP_EXPERT_COLUMN:
            tail[-1] = tp_axis
        elif names[-1] in _TP_EXPERT_ROW:
            tail[-2] = tp_axis
    return tail


def tp_sharding_tree(tree, mesh, gossip_axis: str = GOSSIP_AXIS,
                     tp_axis: str = TP_AXIS):
    """NamedShardings for a gossip-stacked LM tree with Megatron-style
    tensor-parallel kernel shardings (works on arrays or avals).

    Leaves keep their leading gossip dimension; transformer projection
    kernels additionally shard over ``tp_axis`` (column- or row-parallel by
    module name); everything else (embeddings, LayerNorms, scalars,
    momentum of the same leaves — matched by path) replicates over tp.
    The manual gossip collective never sees the tp axis: it stays an Auto
    axis that GSPMD parallelizes inside each rank.
    """
    from jax.sharding import NamedSharding

    def spec_for(path, leaf):
        tail = _tp_tail(path, leaf, tp_axis)
        return NamedSharding(mesh, P(gossip_axis, *tail))

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def ep_tp_sharding_tree(tree, mesh, gossip_axis: str = GOSSIP_AXIS,
                        ep_axis: str = EP_AXIS, tp_axis: str = TP_AXIS):
    """NamedShardings for the ep × tp composition: expert leaves shard
    ``ep`` on their leading expert dim AND ``tp`` on their FFN dim
    (column/row by name, as in :func:`tp_sharding_tree`); dense projection
    kernels shard ``tp`` Megatron-style and replicate over ep; everything
    else replicates over both.  Works on arrays or avals."""
    from jax.sharding import NamedSharding

    def spec_for(path, leaf):
        tail = _tp_tail(path, leaf, tp_axis)
        if _is_expert_path(path) and tail:
            tail[0] = ep_axis
        return NamedSharding(mesh, P(gossip_axis, *tail))

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def apply_tp_sharding(tree, mesh, gossip_axis: str = GOSSIP_AXIS,
                      tp_axis: str = TP_AXIS):
    """Place an existing tree on a (gossip, tp) mesh
    (see :func:`tp_sharding_tree`); prefer :func:`init_lm_state_tp` for
    fresh state, which never materializes unsharded buffers."""
    shardings = tp_sharding_tree(tree, mesh, gossip_axis, tp_axis)
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


def init_lm_state_tp(model, mesh, algorithm, tx, dp: int, batch_size: int,
                     seq_len: int, seed: int = 0) -> TrainState:
    """Initialize TP-sharded LM state directly into its target shardings.

    The whole state (params, momentum, gossip buffers) is built inside one
    jitted program whose out_shardings carry the Megatron layout, so no
    full unsharded replica ever materializes on a single device — the init
    path scales to models that only fit *because* of tensor parallelism.
    """
    from .step import replicate_state

    def build():
        variables = model.init(
            jax.random.PRNGKey(seed),
            jnp.zeros((batch_size, seq_len), jnp.int32))
        params = replicate_state(variables["params"], dp)
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        return TrainState(
            step=jnp.zeros((dp,), jnp.int32), params=params,
            batch_stats={},
            opt_state=replicate_state(tx.init(one(params)), dp),
            gossip=replicate_state(algorithm.init(one(params)), dp))

    shapes = jax.eval_shape(build)
    shardings = tp_sharding_tree(shapes, mesh)
    return jax.jit(build, out_shardings=shardings)()


def lm_loss(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy over the local block.

    Written as ``logsumexp - target_logit`` (identical to
    ``-take(log_softmax)``) so the only loss residual the backward saves
    is the ``[B, T]`` logsumexp — the ``log_softmax`` formulation pins a
    full ``[B, T, vocab]`` float32 residual (~1 GB at the bench shape
    b8 t1024 v32k), pure HBM traffic XLA instead re-derives from the
    saved logits inside the fused backward.

    The target's logit is a masked sum over the vocabulary axis, not a
    ``take_along_axis``: the sum of one logit and zeros is that logit,
    bit for bit, and its transpose is a ``select`` where a gather's is a
    scatter-add of ``B * T`` numbers into a float32 array of the logits'
    size.  XLA then forms ``softmax - onehot`` inside the operand fusions
    of the head's two backward products and the step holds the logits
    once, in bf16.  At ``[1, 8192, 50257]`` (50257 is no multiple of 128,
    so the scatter's flat operand was no bitcast) the gather cost two
    ``dynamic-update-slice`` loops re-laying 1.65 GB there and back and
    five more passes over float32 arrays of that size: 12.9 GB of the
    step's 169.5 GB of traffic by XLA's count for a v5e, and on the chip
    55 ms of GPT-2 medium's 397 ms step (PERF.md §6, PR 34).
    """
    with jax.named_scope(names.SCOPE_LM_HEAD):
        return jnp.mean(_token_losses(logits, targets))


def _token_losses(logits, targets):
    """``logsumexp - target_logit`` a position, ``[B, T]`` float32."""
    logits = jnp.asarray(logits, jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ids = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    tgt = jnp.sum(jnp.where(ids == targets[..., None], logits, 0.0),
                  axis=-1)
    return lse - tgt


# the weight of the multi-token-prediction loss, which no config.json
# gives: DeepSeek-V3's for its first 10 T tokens (arXiv:2412.19437 §4.2)
MTP_LOSS_WEIGHT = 0.3


def mtp_loss(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """The multi-token-prediction module's cross-entropy: position ``i``'s
    logits predict the token after next, ``targets[i + 1]``; the last
    position's lies past the block and is left out of the mean.  The step
    adds it to the trunk's at ``MTP_LOSS_WEIGHT``."""
    with jax.named_scope(names.SCOPE_LM_HEAD):
        t = targets.shape[-1]
        losses = _token_losses(logits, jnp.roll(targets, -1, axis=-1))
        kept = jnp.arange(t) < t - 1
        return jnp.sum(jnp.where(kept, losses, 0.0)) / (
            losses.size // t * (t - 1))


def _sown(collection, name: str) -> list:
    """The values a model's layers sowed under ``name``, in layer order."""
    return [leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(collection)
            if any(getattr(k, "key", None) == name for k in path)]


def build_lm_train_step(model, algorithm: GossipAlgorithm, tx, lr_schedule,
                        itr_per_epoch: int,
                        seq_axis: str | None = SEQ_AXIS,
                        ep_axis: str | None = None,
                        moe_loss_coef: float = 0.01,
                        grad_accum: int = 1,
                        health_axis: str | None = None) -> tp.Callable:
    """Per-rank LM step ``(state, tokens, targets) -> (state, metrics)``.

    Same four-slot structure as the image step (train/step.py); loss is
    token-mean cross-entropy, and with sequence sharding the seq-psummed
    gradients are renormalized to the global token mean.  With
    ``ep_axis``, MoE load-balance losses (sown by the model) join the
    objective and ALL gradients are renormalized by the ep shard count —
    expert slices included, since the all_to_all transpose accumulates
    every shard's contribution into them exactly as the implicit psum
    does for replicated leaves.

    ``grad_accum`` splits the batch into that many microbatches scanned
    sequentially before the optimizer step — 1/grad_accum peak
    activation memory, the long-context lever alongside remat (the LM
    has no BatchNorm, so accumulation is EXACTLY equivalent to the full
    batch; cf. the image step's per-microbatch BN caveat).  MoE caveat:
    capacity slots are per microbatch (t·cf/E per chunk), so routing
    with tight capacity can drop differently than full-batch.
    """
    if grad_accum < 1:
        raise ValueError("grad_accum must be >= 1")

    # phases named as in the image step (train/step.py says how and why)

    def train_step(state: TrainState, tokens, targets):
        with jax.named_scope(names.SCOPE_PRE_STEP):
            params, gstate = algorithm.pre_step(state.params, state.gossip)
            z = algorithm.eval_params(params, gstate)

        def loss_fn(p, toks, tgts):
            with jax.named_scope(names.SCOPE_FORWARD):
                logits, mutated = model.apply(
                    {"params": p}, toks, train=True,
                    mutable=["losses", "moe_metrics", "mtp"])
                ce = lm_loss(logits, tgts)
                loss = ce
                ahead = _sown(mutated.get("mtp", {}), "logits")
                if ahead:
                    loss = loss + MTP_LOSS_WEIGHT * mtp_loss(ahead[0], tgts)
                sown = jax.tree.leaves(mutated.get("losses", {}))
                if sown:
                    loss = loss + moe_loss_coef * sum(
                        jnp.mean(l) for l in sown) / len(sown)
                counters = mutated.get("moe_metrics", {})
                dropped = _sown(counters, "dropped_fraction")
                moe = {"moe_dropped": (
                    sum(jnp.mean(d) for d in dropped) / len(dropped)
                    if dropped else jnp.float32(0.0))}
                rows = _sown(counters, "expert_rows")
                if rows:
                    # the top-k layer's counters, summed over its layers
                    moe["moe_expert_rows"] = sum(rows)
                    moe["moe_pairs_not_held"] = sum(
                        _sown(counters, "pairs_not_held"))
            return loss, (ce, moe)

        if grad_accum == 1:
            (loss, (ce, moe)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(z, tokens, targets)
        else:
            b = tokens.shape[0]
            if b % grad_accum:
                raise ValueError(
                    f"batch {b} not divisible by grad_accum {grad_accum}")
            micro = b // grad_accum
            xs = tokens.reshape((grad_accum, micro) + tokens.shape[1:])
            ys = targets.reshape((grad_accum, micro) + targets.shape[1:])

            def accum(carry, xy):
                g_sum, loss_sum, ce_sum, moe_sum = carry
                toks, tgts = xy
                (l, (c, m)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(z, toks, tgts)
                with jax.named_scope(names.SCOPE_REDUCE_GRADS):
                    return (jax.tree.map(jnp.add, g_sum, g), loss_sum + l,
                            ce_sum + c,
                            jax.tree.map(jnp.add, moe_sum, m)), None

            zero_g = jax.tree.map(jnp.zeros_like, z)
            # scalar accumulators derive from the (device-varying) tokens
            # so the scan carry type matches the body outputs (vma rules)
            zero_s = jnp.sum(tokens * 0.0).astype(jnp.float32)
            zero_moe = jax.tree.map(
                lambda a: zero_s + jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(loss_fn, z, xs[0], ys[0])[1][1])
            (g_sum, loss, ce, moe), _ = lax.scan(
                accum, (zero_g, zero_s, zero_s, zero_moe), (xs, ys))
            with jax.named_scope(names.SCOPE_REDUCE_GRADS):
                grads = jax.tree.map(lambda g: g / grad_accum, g_sum)
                loss = loss / grad_accum
                ce = ce / grad_accum
                # the dropped share is a mean; the counters stay the
                # step's sums over its microbatches
                moe["moe_dropped"] = moe["moe_dropped"] / grad_accum

        with jax.named_scope(names.SCOPE_REDUCE_GRADS):
            if seq_axis is not None:
                # params are invariant over seq → autodiff psums grads over
                # the seq shards; divide to get the global token mean
                n_seq = lax.axis_size(seq_axis)
                grads = jax.tree.map(lambda g: g / n_seq, grads)
                loss = lax.pmean(loss, seq_axis)
                ce = lax.pmean(ce, seq_axis)
                moe["moe_dropped"] = lax.pmean(moe["moe_dropped"], seq_axis)
            if ep_axis is not None:
                # the objective is the MEAN over ep shards of per-shard
                # loss.  Replicated params are ep-invariant → autodiff psums
                # their grads across shards; expert slices live on one shard
                # each, but the all_to_all transpose accumulates every
                # shard's cotangents into them just the same (each expert
                # processes slots from ALL shards).  Both arrive as the SUM
                # over shards → divide everything by n_ep for the mean.
                # (Exempting expert slices would train them with an
                # effective n_ep× lr; pinned by test_expert_parallel_lm.py::
                # test_ep_train_step_matches_full_expert_model.)
                n_ep = lax.axis_size(ep_axis)
                grads = jax.tree.map(lambda g: g / n_ep, grads)
                loss = lax.pmean(loss, ep_axis)
                ce = lax.pmean(ce, ep_axis)
                moe["moe_dropped"] = lax.pmean(moe["moe_dropped"], ep_axis)
            grads = algorithm.reduce_grads(grads)

        with jax.named_scope(names.SCOPE_OPTIMIZER):
            step = as_scalar(state.step)
            lr = lr_schedule(step // itr_per_epoch, step % itr_per_epoch,
                             itr_per_epoch)
            updates, opt_state = tx.update(grads, state.opt_state, params)
            params = jax.tree.map(
                lambda p, u: p - lr.astype(p.dtype) * u, params, updates)
            next_step = state.step + 1
        with jax.named_scope(names.SCOPE_GOSSIP):
            params, gstate = algorithm.post_step(params, gstate)

        with jax.named_scope(names.SCOPE_HEALTH):
            # perplexity from the bare cross-entropy, not the MoE-augmented
            # objective; moe_dropped makes capacity overflow observable;
            # grad_norm (utils/flatten.py) for divergence triage — averaged
            # over seq/ep shards (each shard's expert-slice VALUES differ —
            # different experts live there — so the raw norm varies over ep
            # and would break the metrics' replication)
            from ..utils.flatten import global_norm
            gn = global_norm(grads)
            for ax in (seq_axis, ep_axis):
                if ax is not None:
                    gn = lax.pmean(gn, ax)
            metrics = {"loss": loss, "ppl": jnp.exp(ce), "lr": lr,
                       "grad_norm": gn, **moe}
            if health_axis is not None:
                # consensus health AFTER the gossip round (resilience/):
                # each signal is a collective over the gossip axis and — on
                # a dp×sp mesh — seq-invariant, since params and the
                # seq-psummed grads are replicated over seq.  (ep shards
                # hold different expert slices, so health composes with the
                # flat dp/sp meshes only; the CLI enforces that.)
                from ..resilience.monitor import health_signals
                # the overlap FIFO rides along so the monitor observes the
                # DRAINED view (in-flight mass is not a leak)
                metrics.update(health_signals(
                    params, grads, gstate.ps_weight, health_axis,
                    ef_residual=gstate.ef_residual,
                    in_flight=gstate.in_flight))
        return state.replace(step=next_step, params=params,
                             opt_state=opt_state, gossip=gstate), metrics

    return train_step


def shard_lm_train_step(step_fn, mesh, gossip_axis: str = GOSSIP_AXIS,
                        seq_axis: str | None = SEQ_AXIS,
                        tp: bool = False,
                        state_specs=None,
                        ep_axis: str | None = None):
    """Wrap for the mesh: state stacks over gossip ranks; token batches
    stack over ``(gossip[, seq])``.

    With ``tp=True`` the mesh's ``tp`` axis stays *auto*: the gossip
    collective is manual SPMD while GSPMD partitions each rank's compute
    over tp according to the arrays' own shardings
    (see :func:`apply_tp_sharding`).
    """
    batch_spec, squeeze_n = batch_layout(gossip_axis, seq_axis, ep_axis)

    def wrapped(state, tokens, targets):
        sq_state = jax.tree.map(lambda a: a[0], state)
        sq = lambda t: jax.tree.map(
            lambda a: a.reshape(a.shape[squeeze_n:]), t)
        return restack(*step_fn(sq_state, sq(tokens), sq(targets)))

    kwargs = {}
    if tp:
        # the tp mesh axis stays auto: GSPMD partitions per-rank compute
        manual = {gossip_axis} | ({seq_axis} if seq_axis else set()) \
            | ({ep_axis} if ep_axis else set())
        kwargs["axis_names"] = manual
    state_spec = P(gossip_axis) if state_specs is None else state_specs
    specs = dict(in_specs=(state_spec, batch_spec, batch_spec),
                 out_specs=(state_spec, P(gossip_axis)), **kwargs)
    sharded = jax.shard_map(wrapped, mesh=mesh, **specs)
    sharded.__name__ = names.MODULE_LM_TRAIN_STEP
    return step_store.jit(sharded, material=(wrapped, mesh, specs),
                          donate_argnums=(0,))


def build_lm_eval_step(model, algorithm: GossipAlgorithm,
                       seq_axis: str | None = None,
                       ep_axis: str | None = None) -> tp.Callable:
    """Per-rank LM eval: de-biased params, no gossip, no state update
    (≙ ``validate``, gossip_sgd.py:440-471 — every rank evaluates
    independently; only the seq/ep means are collective)."""

    def eval_step(state: TrainState, tokens, targets):
        z = algorithm.val_params(state.params, state.gossip)
        logits = model.apply({"params": z}, tokens, train=False)
        ce = lm_loss(logits, targets)
        if seq_axis is not None:
            ce = lax.pmean(ce, seq_axis)
        if ep_axis is not None:
            # ep shards evaluate their own held-out tokens (the ep axis
            # doubles as data parallelism for eval, like training)
            ce = lax.pmean(ce, ep_axis)
        return {"loss": ce, "ppl": jnp.exp(ce)}

    return eval_step


def shard_lm_eval_step(eval_fn, mesh, gossip_axis: str = GOSSIP_AXIS,
                       seq_axis: str | None = SEQ_AXIS, tp: bool = False,
                       state_specs=None, ep_axis: str | None = None):
    """Wrap an LM eval step for the mesh (mirrors
    :func:`shard_lm_train_step`, metrics only, no donation)."""
    batch_spec, squeeze_n = batch_layout(gossip_axis, seq_axis, ep_axis)

    def wrapped(state, tokens, targets):
        sq_state = jax.tree.map(lambda a: a[0], state)
        sq = lambda t: jax.tree.map(
            lambda a: a.reshape(a.shape[squeeze_n:]), t)
        metrics = eval_fn(sq_state, sq(tokens), sq(targets))
        return jax.tree.map(lambda a: a[None], metrics)

    kwargs = {}
    if tp:
        kwargs["axis_names"] = {gossip_axis} \
            | ({seq_axis} if seq_axis else set()) \
            | ({ep_axis} if ep_axis else set())
    state_spec = P(gossip_axis) if state_specs is None else state_specs
    sharded = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(state_spec, batch_spec, batch_spec),
        out_specs=P(gossip_axis), **kwargs)
    return jax.jit(sharded)


def shard_scanned_lm_step(step_fn, mesh, n_steps: int,
                          gossip_axis: str = GOSSIP_AXIS,
                          seq_axis: str | None = None):
    """Fuse ``n_steps`` LM train steps into one compiled program via
    ``lax.scan`` (the LM counterpart of train/step.py::
    shard_scanned_train_step — same dispatch-amortization rationale).

    Token batches gain a leading scan dimension:
    ``tokens[n_steps, dp(, sp), batch, block]``; metrics come back stacked
    ``[dp, n_steps]``.  Supports the plain dp and dp×sp (ring) layouts.
    """
    if seq_axis is None:
        batch_spec = P(None, gossip_axis)
        lead = 2
    else:
        batch_spec = P(None, gossip_axis, seq_axis)
        lead = 3

    def wrapped(state, tokens, targets):
        sq = lambda t: jax.tree.map(
            lambda a: a.reshape(a.shape[:1] + a.shape[lead:]), t)

        def body(st, batch):
            toks, tgts = batch
            return step_fn(st, toks, tgts)

        return restack(*lax.scan(
            body, jax.tree.map(lambda a: a[0], state),
            (sq(tokens), sq(targets))))

    specs = dict(in_specs=(P(gossip_axis), batch_spec, batch_spec),
                 out_specs=(P(gossip_axis), P(gossip_axis)))
    sharded = jax.shard_map(wrapped, mesh=mesh, **specs)
    sharded.__name__ = names.MODULE_LM_TRAIN_STEP_SCAN
    return step_store.jit(sharded, material=(wrapped, mesh, specs),
                          donate_argnums=(0,))


def init_lm_state(model, mesh, algorithm, tx, dp: int, sp: int,
                  batch_size: int, block_len: int, seed: int = 0,
                  gossip_axis: str = GOSSIP_AXIS,
                  seq_axis: str | None = SEQ_AXIS) -> TrainState:
    """Build the gossip-stacked LM train state.

    Ring-attention models reference the mesh axis, so parameter init runs
    under ``shard_map``; optimizer and gossip state replicate over the
    gossip dimension.  Shared by the LM CLI and the multi-chip dry run.
    """
    from .step import replicate_state

    ring = seq_axis is not None
    batch_spec = P(gossip_axis, seq_axis) if ring else P(gossip_axis)

    def init_fn(toks, key):
        t = toks[0, 0] if ring else toks[0]
        variables = model.init(key, t)
        return jax.tree.map(lambda a: a[None], variables["params"])

    has_tp = TP_AXIS in mesh.axis_names
    kwargs = {}
    if has_tp:
        kwargs["axis_names"] = {gossip_axis} | (
            {seq_axis} if ring else set())
    sm_init = jax.shard_map(init_fn, mesh=mesh, in_specs=(batch_spec, P()),
                            out_specs=P(gossip_axis), **kwargs)
    dummy_shape = ((dp, sp, batch_size, block_len) if ring
                   else (dp, batch_size, block_len))

    def build(dummy, key):
        params = sm_init(dummy, key)
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        return TrainState(
            step=jnp.zeros((dp,), jnp.int32), params=params,
            batch_stats={},
            opt_state=replicate_state(tx.init(one(params)), dp),
            gossip=replicate_state(algorithm.init(one(params)), dp))

    dummy = np.zeros(dummy_shape, np.int32)
    # the key is an argument of the program, not a constant in it: every
    # seed runs the one compiled initialisation
    key = jax.random.PRNGKey(seed)
    if has_tp:
        # materialize straight into the tensor-parallel layout: momentum
        # and gossip buffers are created sharded, never full-size
        shapes = jax.eval_shape(build, dummy, key)
        return jax.jit(build, out_shardings=tp_sharding_tree(
            shapes, mesh))(dummy, key)
    return jax.jit(build)(dummy, key)


def init_lm_state_ep(model, mesh, algorithm, tx, dp: int, ep: int,
                     batch_size: int, seq_len: int,
                     seed: int = 0, sp: int = 1) -> TrainState:
    """Initialize expert-parallel LM state on a ``(gossip, ep)`` mesh —
    or ``(gossip, ep, seq)`` with ``sp > 1`` (ep × sp composition);
    pair with ``ep_state_specs(state)`` for the train step's specs.

    Parameter init runs under shard_map (the MoE module sizes its local
    expert slice from the live ep axis); replicated leaves are made
    ep-invariant with a no-op ``pmean`` (identical values on every shard),
    expert leaves exit sharded over ep, and the whole state materializes
    straight into its per-leaf shardings.
    """
    from jax.sharding import NamedSharding

    from .step import replicate_state

    ring = sp > 1
    lead = 3 if ring else 2  # leading sharded batch dims to strip

    def init_fn(toks):
        t = toks.reshape(toks.shape[lead:])
        # two init draws: a common key for replicated leaves (identical on
        # every shard → pmean is a no-op that proves ep-invariance) and a
        # shard-folded key so every GLOBAL expert gets an independent draw
        common = model.init(jax.random.PRNGKey(seed), t)["params"]
        local = model.init(
            jax.random.fold_in(jax.random.PRNGKey(seed),
                               lax.axis_index(EP_AXIS)),
            t)["params"]
        params = jax.tree_util.tree_map_with_path(
            lambda path, c, l: l if _is_expert_path(path)
            else lax.pmean(c, EP_AXIS),
            common, local)
        return jax.tree.map(lambda a: a[None], params)

    # param STRUCTURE (paths only) via an axis-free probe of the same cfg
    probe = type(model)(model.cfg._replace(ep_axis=None, seq_axis=None,
                                           attn_impl="full"))
    probe_shapes = jax.eval_shape(
        lambda: probe.init(jax.random.PRNGKey(seed),
                           jnp.zeros((batch_size, seq_len // sp),
                                     jnp.int32)))
    param_specs = ep_state_specs(probe_shapes["params"])

    in_spec = (P(GOSSIP_AXIS, EP_AXIS, SEQ_AXIS) if ring
               else P(GOSSIP_AXIS, EP_AXIS))
    has_tp = TP_AXIS in mesh.axis_names
    sm_kwargs = {}
    if has_tp:
        # ep × tp: only gossip/ep (and seq) are manual; tp stays auto so
        # GSPMD lays the init out per ep_tp_sharding_tree
        sm_kwargs["axis_names"] = {GOSSIP_AXIS, EP_AXIS} | (
            {SEQ_AXIS} if ring else set())
    sm_init = jax.shard_map(
        init_fn, mesh=mesh, in_specs=(in_spec,), out_specs=param_specs,
        **sm_kwargs)
    dummy_shape = ((dp, ep, sp, batch_size, seq_len // sp) if ring
                   else (dp, ep, batch_size, seq_len))
    dummy = np.zeros(dummy_shape, np.int32)

    def build(d):
        params = sm_init(d)
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        return TrainState(
            step=jnp.zeros((dp,), jnp.int32), params=params,
            batch_stats={},
            opt_state=replicate_state(tx.init(one(params)), dp),
            gossip=replicate_state(algorithm.init(one(params)), dp))

    shapes = jax.eval_shape(build, dummy)
    if has_tp:
        shardings = ep_tp_sharding_tree(shapes, mesh)
    else:
        specs = ep_state_specs(shapes)
        shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                                 is_leaf=lambda x: isinstance(x, P))
    return jax.jit(build, out_shardings=shardings)(dummy)
