"""Gossip collectives: compiled replacements for the reference gossipers.

The reference implements gossip as host-driven point-to-point transfers —
``dist.broadcast`` on 2-member process groups fired from a background thread
(gossiper.py:176-323, distributed.py:459-510).  Here each gossip round is a
handful of ``lax.ppermute`` calls *inside the jitted train step*: the
permutation tables come from a frozen :class:`GossipSchedule`, the traced
phase index selects among them with ``lax.switch``, and XLA schedules the
ICI transfers to overlap with compute.  There are no threads, locks, streams,
heartbeats, or poison values — the entire class of hazards the reference
hand-manages (SURVEY.md §5 "Race detection") does not exist in this design.

All functions must be called inside ``shard_map``/``pjit`` with ``axis_name``
bound to a mesh axis whose size equals ``schedule.world_size``.

Correspondence to the reference:

* :func:`mix_push_sum`  ≙ ``PushSum.mix``   (gossiper.py:176-219)
* :func:`mix_push_pull` ≙ ``PushPull.mix``  (gossiper.py:222-275)
* :func:`mix_bilat`     ≙ ``BilatPushPull.mix`` (gossiper.py:278-323),
  in the synchronous perfect-matching formulation
* :func:`allreduce_mean` ≙ the DDP AllReduce baseline (gossip_sgd.py:179-180)

Wire format: every *real* payload leaf (``size > 1``) crosses the
``ppermute`` boundary through a :class:`~.wire.WireCodec` — identity,
bf16 truncation, or per-block int8 (``parallel/wire.py``, the single
encode path; sgplint SGPL010 bans raw ``astype`` wire casts anywhere
else).  Scalar leaves — the push-sum weight lane — always ship exact
f32: quantizing the de-bias divisor buys no bandwidth and breaks the
mass conservation every consensus guarantee rests on.

Error feedback: with a lossy codec, :func:`gossip_round` optionally
carries a per-rank residual accumulator mirroring the mixed tree.  Round
``t`` sends ``Q(wᵢ·x + r)`` (the residual rides the first outgoing
message), and the new residual is the total quantization error across
the round's messages — so what every rank has *cumulatively delivered*
equals what exact mixing would have delivered, up to the current
(bounded) residual.  Compression noise is therefore a bounded
perturbation of the network mean, never a bias.  Composition rules:

* zero-weight edges (irregular graphs' passive ranks, hierarchical
  non-delegates) neither receive the injected residual nor leak it —
  injection is gated on ``wᵢ > 0``;
* a fault-dropped edge ships exactly zero (symmetric codecs keep
  ``Q(0) == 0``), the mixing weight is reabsorbed by the sender as
  usual, and the pending residual is *carried* to the next round;
* NaN corruption drills poison the residual along with the payload —
  the ``ef_residual_rms`` health signal (resilience/monitor.py) makes
  that visible the same step.

Transport lanes: every real payload leaf crosses the wire either as an
XLA ``lax.ppermute`` + receiver decode, or through the split Pallas
transport (ops/gossip_kernel.py).  On the kernel lane the round's
payload leaves are packed into ``buckets`` contiguous byte-bounded
transport buckets; each bucket is ONE :func:`~..ops.gossip_kernel.\
gossip_edge_start` program serving all ``peers_per_itr`` edges (its own
``collective_id`` slot), and its matching wait —
:func:`~..ops.gossip_kernel.gossip_edge_wait` — decodes in VMEM and
folds the edges into the accumulator.  A synchronous round waits every
bucket immediately; a split round (:func:`overlap_launch`) returns the
live handles inside a :class:`PendingShares` so the caller can run the
whole step's compute between the start and the wait — the pipelined
per-bucket form of "The Algorithm of Pipelined Gossiping".  Everything
upstream of the pack — sender multiply, fault masks, EF injection,
``codec.encode`` — is shared per (edge, leaf), so the EF residual
telescopes against the union of the bucketed sends and fault masks key
on the launch tick whatever step lands the bucket.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..telemetry import names
from ..topology.hierarchical import HierarchicalSchedule
from ..topology.schedule import GossipSchedule
from ..topology.synthesized import SynthesizedSchedule
from . import wire as wire_mod

__all__ = [
    "as_scalar",
    "gossip_round",
    "overlap_launch",
    "intra_average",
    "mix_push_sum",
    "mix_push_pull",
    "mix_bilat",
    "allreduce_mean",
    "allreduce_sum",
    "PendingShares",
    "land_shares",
    "settle_share",
    "empty_incoming",
]


def _perm_pairs(dests: np.ndarray) -> list[tuple[int, int]]:
    """ppermute (source, destination) pairs from a destination table."""
    return [(int(src), int(dst)) for src, dst in enumerate(dests)]


def as_scalar(x):
    """Normalize a traced state scalar to shape ().

    Per-rank state scalars arrive shaped ``(1,)`` when sharded over the
    gossip axis of a mesh (one element per rank); every consumer that
    indexes, switches, or broadcasts on them goes through this.
    """
    return jnp.reshape(x, ())


def _rank_weight(table: np.ndarray, axis_name: str):
    """This rank's weight from a per-rank table; constant-folded when all
    ranks share one value.  jnp.asarray keeps float64 only under
    jax_enable_x64; with the default config weights are float32 before the
    per-leaf cast."""
    if np.all(table == table[0]):
        return jnp.asarray(table[0])
    return jnp.asarray(table)[lax.axis_index(axis_name)]


def _resolve_codec(codec, comm_dtype):
    """One wire codec from the new (``codec``) and deprecated
    (``comm_dtype``) knobs; lossless resolves to None (the identity
    path compiles to exactly the pre-codec HLO)."""
    if codec is None and comm_dtype is not None:
        codec = wire_mod.from_comm_dtype(comm_dtype)
    if codec is not None and not codec.lossy:
        return None
    return codec


def _kernel_spec(send_codec):
    """The in-kernel decode spec the kernel lane would run for this
    resolved codec: the exact wire is the f32 passthrough; a lossy codec
    with no spec pins the XLA path (``transport_kernel_name`` stamps
    it)."""
    if send_codec is None:
        return wire_mod.F32.kernel_spec()
    return send_codec.kernel_spec()


def _transport_plan(leaves, spec, num_buckets):
    """Static transport plan for the kernel lane: partition the payload
    (``size > 1``) leaf slots into ``num_buckets`` contiguous,
    byte-bounded buckets — the OSGP reference's message bucketing, made
    static.  Scalar leaves (the push-sum weight) never enter a bucket:
    they take the exact-f32 ppermute lane.

    Returns a tuple of buckets, each a tuple of ``(slot, n, padded)``
    triples — ``slot`` the leaf's flatten position, ``n`` its element
    count, ``padded`` its packed length (int8 leaves pad to whole codec
    blocks so per-row scales stay block-local across the concat).
    Nested tuples of ints: hashable, so the plan can ride pytree aux
    data (:class:`PendingShares`) and must compare equal across the
    phase ``lax.switch`` branches (it is phase-independent by
    construction).  ``()`` when no leaf qualifies — the caller then
    skips the kernel entirely.  A dtype change between adjacent leaves
    forces a bucket boundary (one bucket ships ONE packed accumulator),
    so pathological mixed-dtype trees may exceed ``num_buckets``.
    """
    block = spec.block if spec.kind == "int8" else None
    items = []
    for j, a in enumerate(leaves):
        n = int(np.prod(jnp.shape(a), dtype=np.int64))
        if n <= 1:
            continue
        padded = n if block is None else -(-n // int(block)) * int(block)
        items.append((j, n, padded, jnp.asarray(a).dtype))
    if not items:
        return ()
    k = max(1, min(int(num_buckets), len(items)))
    total = float(sum(p for _, _, p, _ in items))
    buckets, cur, cum = [], [], 0.0
    for idx, (j, n, padded, dt) in enumerate(items):
        if cur and dt != cur[-1][3]:
            buckets.append(cur)
            cur = []
        cur.append((j, n, padded, dt))
        cum += padded
        left = len(items) - idx - 1
        need = k - len(buckets) - 1
        if left > 0 and need > 0 and (
                left == need
                or cum >= total * (len(buckets) + 1) / k):
            buckets.append(cur)
            cur = []
    if cur:
        buckets.append(cur)
    return tuple(tuple((j, n, p) for j, n, p, _ in b) for b in buckets)


def _pack_bucket(bucket, sent, kind, ne):
    """Stack one bucket's buffered encoded parts into the kernel's
    ``[E, ...]`` convention: concatenate the bucket's leaves within each
    edge (int8 along the block-row axis — every leaf is a whole number
    of blocks, so scales stay block-local), then stack the
    ``peers_per_itr`` edges in front."""
    if kind == "int8":
        q = jnp.stack([
            jnp.concatenate([sent[j][i][0] for j, _, _ in bucket], axis=0)
            for i in range(ne)])
        s = jnp.stack([
            jnp.concatenate([sent[j][i][1] for j, _, _ in bucket], axis=0)
            for i in range(ne)])
        return (q, s)
    v = jnp.stack([
        jnp.concatenate([sent[j][i][0].reshape(-1) for j, _, _ in bucket])
        for i in range(ne)])
    return (v,)


def _pack_acc(bucket, acc):
    """One bucket's packed flat accumulator: each leaf raveled and
    zero-padded to its packed length (the pad lanes receive decode(0)
    == 0 from the wire, so they stay zero and are sliced away)."""
    segs = []
    for j, n, padded in bucket:
        seg = acc[j].reshape(-1)
        if padded != n:
            seg = jnp.pad(seg, (0, padded - n))
        segs.append(seg)
    return segs[0] if len(segs) == 1 else jnp.concatenate(segs)


def _unpack_acc(bucket, flat, acc):
    """Scatter a waited bucket back into the accumulator leaves (inverse
    of :func:`_pack_acc`); mutates ``acc`` in place."""
    off = 0
    for j, n, padded in bucket:
        acc[j] = flat[off:off + n].reshape(jnp.shape(acc[j]))
        off += padded


@jax.tree_util.register_pytree_node_class
class PendingShares:
    """One split round's deferred incoming share on the kernel lane.

    :func:`overlap_launch` with an active Pallas ``kernel`` returns this
    in place of the plain incoming tree: ``inc`` carries the
    jnp-transported leaves (the exact-f32 scalar lane — the push-sum
    weight — and anything the kernel does not carry; bucketed slots are
    zeros there), ``handles`` one live
    :class:`~..ops.gossip_kernel.TransportHandle` per transport bucket
    holding landed WIRE bytes, and the aux ``plan`` the static bucket
    layout (:func:`_transport_plan`).  A registered pytree, so it rides
    the overlap FIFO slot through the step, ``lax.cond`` arms and the
    phase ``lax.switch`` (the plan is phase-independent).  Consume it
    exactly once — :func:`land_shares` into the target tree, or
    :func:`settle_share` to a plain share — to preserve push-sum mass.
    """

    def __init__(self, inc, handles, plan):
        self.inc = inc
        self.handles = tuple(handles)
        self.plan = plan

    def tree_flatten(self):
        return (self.inc, self.handles), self.plan

    @classmethod
    def tree_unflatten(cls, plan, children):
        inc, handles = children
        return cls(inc, handles, plan)


def land_shares(tree, incoming):
    """Fold one incoming gossip share into ``tree`` — the single consume
    seam of the overlap FIFO.  A plain share (the XLA lane, settled or
    zero slots, world 1) is an elementwise tree add.  A
    :class:`PendingShares` lands each transport bucket through the wait
    kernel (:func:`~..ops.gossip_kernel.gossip_edge_wait`): pull the
    landed chunks, decode the wire in VMEM, fold all ``peers_per_itr``
    edges into the packed accumulator — the same per-edge fold order as
    the synchronous kernel round — then scatter the result back into the
    leaves; the non-bucketed ``inc`` slots (the scalar ps-weight lane)
    are plain adds."""
    if not isinstance(incoming, PendingShares):
        return jax.tree.map(
            lambda p, b: p + jnp.asarray(b, jnp.asarray(p).dtype),
            tree, incoming)
    from ..ops import gossip_kernel as gk

    leaves, treedef = jax.tree.flatten(tree)
    inc = jax.tree.leaves(incoming.inc)
    if len(inc) != len(leaves):
        raise ValueError(
            "pending share does not mirror the target tree "
            f"({len(inc)} vs {len(leaves)} leaves)")
    bucketed = {j for bucket in incoming.plan for j, _, _ in bucket}
    out = [a if j in bucketed
           else a + jnp.asarray(inc[j], jnp.asarray(a).dtype)
           for j, a in enumerate(leaves)]
    for handle, bucket in zip(incoming.handles, incoming.plan):
        flat = gk.gossip_edge_wait(handle, _pack_acc(bucket, out))
        _unpack_acc(bucket, flat, out)
    return jax.tree.unflatten(treedef, out)


def settle_share(incoming):
    """Materialize a :class:`PendingShares` into the plain share tree
    the FIFO stores between steps: land it into zeros.  ``post_step``
    settles every slot it does not consume at the bottom of the step
    that launched it, so checkpoints, resharding, drains and the
    monitor only ever see plain arrays — a live transport handle exists
    strictly inside one jitted step.  Plain shares pass through
    untouched."""
    if not isinstance(incoming, PendingShares):
        return incoming
    return land_shares(jax.tree.map(jnp.zeros_like, incoming.inc),
                       incoming)


def empty_incoming(tree, schedule, codec=None, comm_dtype=None,
                   kernel=None, buckets=1):
    """The zero incoming share structurally matching what
    :func:`overlap_launch` returns for this configuration — the
    thinning skip branch (``PushSumGossip.pre_step``) must hand
    ``lax.cond`` the same pytree as the launch arm.  Plain zeros on the
    XLA lane (also world 1, a specless lossy codec, or a tree with no
    payload leaves); on the kernel lane a zero :class:`PendingShares`
    (waiting a zero handle lands a zero contribution: decode(0) == 0
    for every codec)."""
    zeros = jax.tree.map(jnp.zeros_like, tree)
    if kernel is None or schedule.world_size == 1:
        return zeros
    if isinstance(schedule, HierarchicalSchedule):
        # only the delegate (inter) share rides in flight
        schedule = schedule.inter_schedule
    spec = _kernel_spec(_resolve_codec(codec, comm_dtype))
    if spec is None:
        return zeros
    plan = _transport_plan(jax.tree.leaves(tree), spec, buckets)
    if not plan:
        return zeros
    from ..ops import gossip_kernel as gk

    handles = tuple(
        gk.empty_transport_handle(
            spec, sum(p for _, _, p in bucket), schedule.peers_per_itr,
            interpret=kernel.interpret, chunk_elems=kernel.chunk_elems)
        for bucket in plan)
    return PendingShares(zeros, handles, plan)


def _round_fn(schedule: GossipSchedule, phase_idx: int, axis_name: str,
              comm_dtype=None, faults=None, codec=None, split=False,
              kernel=None, buckets=1):
    """Build the mixing function for one static phase of the schedule.

    Returns ``mix(tree, tick, residual) -> (out, new_residual)``;
    ``tick`` is None without faults and ``residual`` is None without
    error feedback (``new_residual`` is then None too).  With
    ``split=True`` the function instead returns ``((local, incoming),
    new_residual)`` — the same round separated into the kept local share
    ``lo·x`` (reabsorbed fault weight included) and the received peer
    contributions ``Σᵢ ppermute(wᵢ·x)``, whose sum IS the synchronous
    round.  The split form is the double-buffered overlap round's launch
    half: the caller applies ``local`` now and defers ``incoming`` — a
    plain tree on the XLA lane, a :class:`PendingShares` carrying live
    transport handles on the kernel lane (fold it with
    :func:`land_shares` / :func:`settle_share`).

    ``codec`` (a :class:`~.wire.WireCodec`; ``comm_dtype`` is the
    deprecated bf16-only alias) compresses the wire payload: real
    payload leaves are encoded before the ppermute and decoded back in
    the leaf dtype at the receiver.  The local share always stays full
    precision, so the push-sum mass error is bounded by the received
    fraction of each round; scalar leaves (the push-sum weight) never
    go through the codec at all.

    ``residual`` enables error feedback (see the module docstring): the
    pending residual is injected into the first outgoing message of
    ranks that actually send (``w₀ > 0``), and the returned residual
    accumulates this round's quantization error — with the carry rule
    that a dropped or non-sending slot keeps its residual pending.

    ``faults`` (a :class:`~..resilience.faults.FaultMasks`) injects
    deterministic edge failures: outgoing messages are masked with the
    plan's keep table at ``tick``, and — mass-conserving semantics —
    the sender reabsorbs the undelivered mixing weight into its local
    share so the effective matrix stays column-stochastic (push-sum
    remains exactly mean-preserving under any fault plan).  NaN
    corruption poisons real payload leaves only; the push-sum weight
    lane stays finite so ps-weight telemetry survives the fault.

    ``kernel`` (an :class:`~..ops.gossip_kernel.KernelLane`, or None for
    the XLA ppermute lane) routes real payload leaves through the split
    Pallas transport: the per-(edge, leaf) loop below only encodes and
    buffers; after the loop each of the ``buckets`` transport buckets
    (:func:`_transport_plan`) issues ONE
    :func:`~..ops.gossip_kernel.gossip_edge_start` serving all
    ``peers_per_itr`` edges, and is folded by the matching wait —
    immediately for a synchronous round, deferred inside a
    :class:`PendingShares` for ``split=True`` (the overlap launch the
    split exists for).  Scalar leaves — the push-sum weight — never
    enter the kernel.
    """
    lo_table = schedule.self_weight[phase_idx]
    edge_w = schedule.edge_weights[phase_idx]
    perms = schedule.perms[phase_idx]
    send_codec = _resolve_codec(codec, comm_dtype)

    def mix(tree, tick, residual):
        if residual is not None and send_codec is None:
            raise ValueError("error feedback needs a lossy wire codec "
                             "(bf16/int8); exact wires have no "
                             "quantization error to feed back")
        lo = _rank_weight(lo_table, axis_name)
        leaves, treedef = jax.tree.flatten(tree)
        res_in = (jax.tree.leaves(residual)
                  if residual is not None else None)
        if res_in is not None and len(res_in) != len(leaves):
            raise ValueError(
                "ef residual tree does not mirror the mixed tree "
                f"({len(res_in)} vs {len(leaves)} leaves)")
        # untouched (scalar / exact) leaves carry their residual through
        err = list(res_in) if res_in is not None else None
        out = [a * lo.astype(a.dtype) for a in leaves]
        # received contributions accumulate into the local share (sync)
        # or into a separate incoming tree (overlap launch); fault
        # reabsorption always lands in the LOCAL share — the sender
        # keeps the undelivered weight, it is never in flight
        inc = [jnp.zeros_like(a) for a in leaves] if split else None
        acc = inc if split else out
        # kernel lane: a static transport plan buckets the payload
        # leaves; the (edge, leaf) loop below then only ENCODES and
        # buffers into `sent` — the remote DMA is issued per bucket
        # after the loop.  An empty plan (specless codec, no payload
        # leaves, kernel off) leaves `sent` empty and every leaf on the
        # XLA path.
        spec = _kernel_spec(send_codec) if kernel is not None else None
        plan = (_transport_plan(leaves, spec, buckets)
                if spec is not None else ())
        sent = {j: [] for bucket in plan for j, _, _ in bucket}
        corrupt = (faults.corrupt_at(tick, axis_name)
                   if faults is not None and faults.any_corruption else None)
        for i in range(schedule.peers_per_itr):
            w_i = _rank_weight(edge_w[i], axis_name)
            keep = (faults.keep_at(tick, i, axis_name)
                    if faults is not None else None)
            pairs = _perm_pairs(perms[i])
            for j, a in enumerate(leaves):
                msg = a * w_i.astype(a.dtype)
                # error feedback: the pending residual rides the FIRST
                # outgoing message — of ranks that actually send (a
                # zero-weight edge must neither ship nor consume it)
                inject = (res_in is not None and i == 0 and a.size > 1)
                if inject:
                    gate = (w_i > 0).astype(msg.dtype)
                    r = res_in[j].astype(msg.dtype)
                    msg = msg + r * gate
                # corrupt real payloads only (size > 1, like compression):
                # a poisoned de-bias divisor would blind the very
                # ps-weight telemetry that detects the fault
                if corrupt is not None and msg.size > 1:
                    msg = jnp.where(corrupt > 0,
                                    jnp.asarray(jnp.nan, msg.dtype), msg)
                if keep is not None:
                    # a dropped edge delivers nothing — `where`, not `*`,
                    # so a dropped+corrupted message is 0, never 0·NaN
                    msg = jnp.where(keep > 0, msg, jnp.zeros_like(msg))
                if send_codec is not None and msg.size > 1:
                    # the codec's own work is named apart from the round
                    # it rides in (telemetry/names.py: sgp.gossip.wire)
                    with jax.named_scope(names.SCOPE_WIRE):
                        parts = send_codec.encode(msg)
                    if j in sent:
                        sent[j].append(parts)
                    else:
                        landed = tuple(lax.ppermute(p, axis_name, pairs)
                                       for p in parts)
                        with jax.named_scope(names.SCOPE_WIRE):
                            acc[j] = acc[j] + send_codec.decode(landed, msg)
                    if res_in is not None:
                        # quantization error of what was attempted on the
                        # wire (zero for a dropped edge: Q(0) == 0) —
                        # computed from the SAME encoded parts both
                        # transport lanes ship, so the residual
                        # telescopes against the union of bucketed sends
                        with jax.named_scope(names.SCOPE_WIRE):
                            q_err = msg - send_codec.decode(parts, msg)
                        if inject:
                            # carry rule: when this rank did not put its
                            # residual on the wire (w₀ == 0 or the edge
                            # was dropped) the residual stays pending
                            attempt = gate * (
                                keep.astype(msg.dtype) if keep is not None
                                else jnp.asarray(1.0, msg.dtype))
                            err[j] = q_err + r * (1.0 - attempt)
                        else:
                            err[j] = err[j] + q_err
                elif msg.size > 1:
                    if j in sent:
                        sent[j].append((msg,))
                    else:
                        acc[j] = acc[j] + lax.ppermute(msg, axis_name,
                                                       pairs)
                else:
                    # scalar (ps-weight) lane: exact f32 ppermute in BOTH
                    # transport lanes — bit-identical by construction
                    acc[j] = acc[j] + lax.ppermute(msg, axis_name, pairs)
            if keep is not None and faults.reabsorb:
                # sender reabsorbs the undelivered weight: the effective
                # column still sums to 1 (mass conservation).  In-place
                # (`out` may be aliased by `acc` on the sync path)
                drop_w = w_i * (1.0 - keep)
                for j, a in enumerate(leaves):
                    out[j] = out[j] + a * drop_w.astype(a.dtype)
        handles = []
        if plan:
            from ..ops import gossip_kernel as gk

            ne = schedule.peers_per_itr
            dests = np.stack([np.asarray(perms[i]) for i in range(ne)])
            for b, bucket in enumerate(plan):
                handle = gk.gossip_edge_start(
                    _pack_bucket(bucket, sent, spec.kind, ne), dests,
                    axis_name, spec,
                    n_decoded=sum(p for _, _, p in bucket),
                    interpret=kernel.interpret,
                    chunk_elems=kernel.chunk_elems,
                    collective_id=b % gk.COLLECTIVE_ID_SLOTS)
                if split:
                    # overlap launch: the handle rides the FIFO; the
                    # caller waits it at the bottom of the step
                    handles.append(handle)
                else:
                    # synchronous round: wait immediately — decode in
                    # VMEM, fold all edges into the packed accumulator
                    flat = gk.gossip_edge_wait(handle,
                                               _pack_acc(bucket, acc))
                    _unpack_acc(bucket, flat, acc)
        new_res = (jax.tree.unflatten(jax.tree.structure(residual), err)
                   if res_in is not None else None)
        if split:
            incoming = jax.tree.unflatten(treedef, inc)
            if plan:
                incoming = PendingShares(incoming, handles, plan)
            return (jax.tree.unflatten(treedef, out), incoming), new_res
        return jax.tree.unflatten(treedef, out), new_res

    return mix


def _hier_round_fn(hsched: HierarchicalSchedule, round_idx: int,
                   axis_name: str, comm_dtype=None, codec=None,
                   kernel=None, buckets=1):
    """One compiled hierarchical round: leader ppermute, then the exact
    intra-slice average as ONE grouped all-reduce over the slice sub-axis
    (ICI-local; the ``slice_size − 1`` rotate-permutations of the table
    representation collapse into a single collective).  Numerically this
    applies exactly ``W_intra @ W_inter(round)`` — the matrices the
    verifier checks.

    The wire codec applies to the *delegate* (inter) lane only — the
    expensive cross-slice DCN messages.  The intra-slice psum is exact
    by construction: a grouped collective has no per-message wire to
    encode, and it is ICI-local anyway — the bytes worth compressing
    are the DCN ones.  The error-feedback residual likewise lives on
    the inter lane and stays rank-local (never psum-averaged: it is
    sender memory, not network mass).

    The Pallas ``kernel`` lane likewise rides the delegate (inter) edge
    phase only — the grouped intra-slice psum is a fused XLA collective
    already and stays one.
    """
    inter = _round_fn(hsched.inter_schedule, round_idx, axis_name,
                      comm_dtype, codec=codec, kernel=kernel,
                      buckets=buckets)

    def mix(tree, tick, residual):
        t, new_res = inter(tree, tick, residual)
        return intra_average(t, hsched, axis_name), new_res

    return mix


def intra_average(tree, hsched: HierarchicalSchedule, axis_name: str):
    """The exact intra-slice average of a hierarchical round: ONE grouped
    all-reduce over the slice sub-axis (ICI-local), numerically
    ``W_intra @ tree``.  Public because the overlap consume path applies
    it separately: the delegate (DCN) share is deferred in flight while
    this cheap local collective stays at the bottom of the step."""
    return _grouped_average(tree, axis_name, hsched.slice_groups)


def _grouped_average(tree, axis_name: str, groups):
    """Exact average inside each of ``groups`` (equal-sized rank blocks
    of ``axis_name``), bit-identical across a group's members.

    The all-reduce is spelled as its two halves — grouped
    ``psum_scatter`` then grouped ``all_gather`` over the flattened leaf
    — because ``lax.psum(..., axis_index_groups=)`` has no rule under
    ``shard_map``'s varying-axes check, while both halves are
    varying→varying collectives the checker types."""
    groups = [list(g) for g in groups]
    g = len(groups[0])

    def average(a):
        flat = jnp.ravel(a * jnp.asarray(1.0 / g, a.dtype))
        pad = -flat.size % g
        if pad:
            flat = jnp.pad(flat, (0, pad))
        part = lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                axis_index_groups=groups, tiled=True)
        full = lax.all_gather(part, axis_name, axis=0,
                              axis_index_groups=groups, tiled=True)
        return jnp.reshape(full[:a.size], a.shape)

    return jax.tree.map(average, tree)


def _synth_round_fn(ssched: SynthesizedSchedule, phase_idx: int,
                    axis_name: str, comm_dtype=None, codec=None,
                    kernel=None, buckets=1):
    """One compiled synthesized phase: an edge phase is one ``ppermute``
    round through the compact per-phase tables (full wire-codec path),
    a psum phase is ONE grouped all-reduce over the spec's equal rank
    blocks — numerically exactly the ``g − 1`` rotate-permutation
    matrix the verifier checks.  The error-feedback residual rides edge
    phases only and passes through psum phases untouched (an exact
    collective has no quantization error to account).  The Pallas
    ``kernel`` lane follows the same split: edge phases take the fused
    transport, psum phases stay a grouped XLA all-reduce."""
    if ssched.phase_kinds[phase_idx] == "psum":
        groups = ssched.phase_groups[phase_idx]

        def mix(tree, tick, residual):
            return _grouped_average(tree, axis_name, groups), residual

        return mix
    return _round_fn(ssched.edge_phase_schedule(phase_idx), 0, axis_name,
                     comm_dtype, codec=codec, kernel=kernel,
                     buckets=buckets)


def gossip_round(tree, phase, schedule: GossipSchedule, axis_name: str,
                 comm_dtype=None, faults=None, tick=None, codec=None,
                 ef_residual=None, kernel=None, buckets=1):
    """One synchronous gossip round over an arbitrary pytree.

    Computes ``lo * x + Σ_i ppermute(w_i * x, perm_i(phase))`` — the
    column-stochastic mixing the reference assembles from weighted broadcasts
    (gossiper.py:125-147, 191-215).  ``phase`` is a traced int32 scalar;
    rotation (graph_manager.py:128-133) is a free modulo, not communicator
    churn.  ``codec`` (:mod:`.wire`) compresses the wire payload;
    ``comm_dtype`` is the deprecated bf16-only alias.

    A :class:`~..topology.hierarchical.HierarchicalSchedule` compiles to
    its two-level form: leader ``ppermute`` across slices plus one grouped
    ``psum`` inside each slice per round (see :func:`_hier_round_fn`);
    ``phase`` then counts *rounds*, each spanning two table phases, and
    the codec compresses the delegate (DCN) lane only.  A
    :class:`~..topology.synthesized.SynthesizedSchedule` compiles one
    round per table phase — an edge phase is one ``ppermute``, a psum
    phase one grouped collective (see :func:`_synth_round_fn`); the
    codec compresses edge phases only, and fault injection / overlap
    are rejected (no per-edge psum mask, no augmented table form).

    ``faults`` applies a compiled fault plan (resilience/faults.py) with
    mass-conserving drop semantics; ``tick`` is the fault-time index (a
    traced step counter, defaults to ``phase`` — they coincide except
    under communication thinning, where the rotation advances slower than
    the step clock).

    ``ef_residual`` (a pytree mirroring ``tree``) enables error feedback
    with a lossy codec; the call then returns ``(mixed, new_residual)``
    instead of ``mixed`` (see the module docstring for the semantics).

    ``kernel`` (an :class:`~..ops.gossip_kernel.KernelLane`; resolve the
    CLI flag with :func:`~..ops.gossip_kernel.resolve_gossip_kernel`)
    routes real payload leaves through the split Pallas remote-DMA
    transport instead of ``lax.ppermute`` + decode; None is the XLA
    lane.  ``buckets`` partitions the payload into that many contiguous
    byte-bounded transport buckets (:func:`_transport_plan`), each ONE
    start/wait pallas_call pair serving all ``peers_per_itr`` edges
    with its own ``collective_id`` slot — total wire bytes are
    unchanged, only the pipelining granularity.  Numerics are lane- and
    bucket-independent (pinned by the kernel parity tests); scalar
    leaves ship the same exact ppermute either way.
    """
    mixed, new_res = _apply_round(tree, phase, schedule, axis_name,
                                  comm_dtype, faults, tick, codec,
                                  ef_residual, split=False, kernel=kernel,
                                  buckets=buckets)
    return mixed if ef_residual is None else (mixed, new_res)


def overlap_launch(tree, phase, schedule: GossipSchedule, axis_name: str,
                   comm_dtype=None, faults=None, tick=None, codec=None,
                   ef_residual=None, kernel=None, buckets=1):
    """Launch half of the double-buffered overlap round.

    Issues round ``phase``'s ``ppermute`` NOW — called at the TOP of the
    train step, so XLA schedules the collective behind the forward/
    backward compute — and returns ``(local, incoming)``: the kept local
    share ``lo·x`` and the received peer contributions, whose sum is
    exactly the synchronous :func:`gossip_round`.  The caller applies
    ``local`` immediately and defers ``incoming`` (the in-flight FIFO in
    ``algorithms.GossipState``); consuming every launched share exactly
    once preserves push-sum mass for any staleness, which is the
    invariant ``analysis.verify_schedule`` checks on
    :meth:`~..topology.schedule.GossipSchedule.overlap_schedule`'s
    augmented tables (SGPV106).

    Feature composition matches the synchronous round — this is what
    makes overlap a first-class phase schedule rather than a mode flag:

    * ``faults``: keep/corrupt masks are resolved at the LAUNCH tick
      (``tick``), so a share launched under one fault state and consumed
      under another stays mass-conserving — the sender reabsorbed the
      undelivered weight when the wire actually fired;
    * ``codec`` / ``ef_residual``: the residual is injected into (and the
      new residual telescopes against) the round being SENT, not the
      round being consumed;
    * hierarchical schedules defer the delegate (inter/DCN) share only;
      the caller runs :func:`intra_average` after consuming (the cheap
      ICI-local psum stays synchronous — it cannot ride in flight).

    Returns ``(local, incoming)``, or ``(local, incoming, new_residual)``
    when ``ef_residual`` is given.  On the XLA lane ``incoming`` is a
    plain tree; with ``kernel`` (a
    :class:`~..ops.gossip_kernel.KernelLane`) it is a
    :class:`PendingShares` whose per-bucket transport handles carry the
    round's wire — the split start/wait kernel issues its remote DMA
    HERE, at the top of the step, and the caller folds the landed
    buckets with :func:`land_shares` (or :func:`settle_share`) at the
    bottom, so the in-VMEM decode + axpy win rides the overlap instead
    of being forced back to the ppermute lane.  ``buckets`` sets the
    pipelining granularity (multiple buckets in flight per round, each
    its own ``collective_id`` slot); every launched share must be
    landed exactly once, whatever the bucket count — push-sum mass is
    the invariant SGPV106 pins.
    """
    out, new_res = _apply_round(tree, phase, schedule, axis_name,
                                comm_dtype, faults, tick, codec,
                                ef_residual, split=True, kernel=kernel,
                                buckets=buckets)
    local, incoming = out
    if ef_residual is None:
        return local, incoming
    return local, incoming, new_res


def _apply_round(tree, phase, schedule, axis_name, comm_dtype, faults,
                 tick, codec, ef_residual, split, kernel=None, buckets=1):
    """Shared dispatch of one (possibly split) gossip round: validation,
    per-phase branch construction, traced-phase ``lax.switch``.  The
    kernel lane rides ``split`` rounds too — the start/wait split is
    exactly what lets the remote DMA launch at the top of the step and
    land at the bottom (the old forced-xla overlap downgrade is gone).
    """
    if buckets < 1:
        raise ValueError("buckets must be >= 1")
    if isinstance(schedule, HierarchicalSchedule) and faults is not None:
        # static configuration error: reject before any axis
        # introspection so the message survives outside a mesh context
        raise ValueError(
            "fault injection is not supported on hierarchical "
            "schedules: the intra-slice psum has no per-edge mask "
            "(use a flat topology for fault drills)")
    if isinstance(schedule, SynthesizedSchedule):
        if faults is not None:
            raise ValueError(
                "fault injection is not supported on synthesized "
                "schedules: grouped psum phases have no per-edge mask "
                "(use a flat registry topology for fault drills)")
        if split:
            raise ValueError(
                "overlap is not supported on synthesized schedules: a "
                "psum/ppermute phase composition has no single "
                "augmented in-flight form (use a registry topology for "
                "overlap runs)")
    if ef_residual is not None and _resolve_codec(codec, comm_dtype) is None:
        raise ValueError(
            "error feedback needs a lossy wire codec (bf16/int8); exact "
            "wires have no quantization error to feed back")
    axis_size = lax.axis_size(axis_name)
    if axis_size != schedule.world_size:
        raise ValueError(
            f"schedule was built for world_size={schedule.world_size} but "
            f"mesh axis '{axis_name}' has size {axis_size}")
    if schedule.world_size == 1:
        if split:
            return (tree, jax.tree.map(jnp.zeros_like, tree)), ef_residual
        return tree, ef_residual

    if isinstance(schedule, SynthesizedSchedule):
        # one compiled round per table phase (edge ppermute or grouped
        # psum); the traced phase index selects among them like any
        # flat rotation
        branches = [_synth_round_fn(schedule, p, axis_name, comm_dtype,
                                    codec, kernel=kernel, buckets=buckets)
                    for p in range(schedule.num_phases)]
        idx = as_scalar(phase) % schedule.num_phases
        fault_tick = None
    elif isinstance(schedule, HierarchicalSchedule):
        rounds = schedule.rounds_per_cycle
        if split:
            # overlap launch: the delegate ppermute only — the caller
            # runs intra_average when the share is consumed
            branches = [_round_fn(schedule.inter_schedule, q, axis_name,
                                  comm_dtype, codec=codec, split=True,
                                  kernel=kernel, buckets=buckets)
                        for q in range(rounds)]
        else:
            branches = [_hier_round_fn(schedule, q, axis_name, comm_dtype,
                                       codec, kernel=kernel,
                                       buckets=buckets)
                        for q in range(rounds)]
        idx = as_scalar(phase) % rounds
        fault_tick = None
    else:
        if faults is not None:
            fault_tick = as_scalar(phase if tick is None else tick)
        else:
            fault_tick = None
        branches = [_round_fn(schedule, p, axis_name, comm_dtype, faults,
                              codec, split=split, kernel=kernel,
                              buckets=buckets)
                    for p in range(schedule.num_phases)]
        idx = as_scalar(phase) % schedule.num_phases

    operand = (tree, fault_tick, ef_residual)
    if len(branches) == 1:
        return branches[0](*operand)
    return lax.switch(
        idx, [lambda op, fn=fn: fn(*op) for fn in branches], operand)


def mix_push_sum(params, ps_weight, phase, schedule: GossipSchedule,
                 axis_name: str, comm_dtype=None, faults=None, tick=None,
                 codec=None, ef_residual=None, kernel=None, buckets=1):
    """Push-sum round: jointly mixes parameters and the push-sum weight.

    The reference appends the scalar ps-weight to the flat payload only when
    mixing is irregular (gossiper.py:83-85, 131-132); here it always rides
    along as one extra pytree leaf — one scalar lane, zero bookkeeping.
    The weight lane is ALWAYS exact f32 (wire codecs skip scalar leaves),
    so mass conservation — and therefore the de-biased consensus value —
    survives compression and every mass-conserving fault plan.

    Returns ``(mixed_params, mixed_ps_weight)``, or
    ``(mixed_params, mixed_ps_weight, new_residual)`` when
    ``ef_residual`` (a params-shaped pytree) enables error feedback.
    For regular schedules a complete synchronous round maps
    ``ps_weight == 1 → 1``, which is the algebraic form of the
    reference's lazy-mixing shortcut (distributed.py:188-191).
    """
    tree = (params, ps_weight)
    if ef_residual is None:
        return gossip_round(tree, phase, schedule, axis_name,
                            comm_dtype=comm_dtype, faults=faults,
                            tick=tick, codec=codec, kernel=kernel,
                            buckets=buckets)
    full_res = (ef_residual, jax.tree.map(jnp.zeros_like, ps_weight))
    (p, w), (new_res, _) = gossip_round(
        tree, phase, schedule, axis_name, comm_dtype=comm_dtype,
        faults=faults, tick=tick, codec=codec, ef_residual=full_res,
        kernel=kernel, buckets=buckets)
    return p, w, new_res


def mix_push_pull(params, phase, schedule: GossipSchedule, axis_name: str,
                  comm_dtype=None, codec=None, kernel=None, buckets=1):
    """Doubly-stochastic (D-PSGD) round.

    With uniform mixing on a regular graph the mixing matrix is doubly
    stochastic, so no push-sum weight is needed — matches
    ``PushPull.mix`` semantics (gossiper.py:222-275) where the active/passive
    send ordering existed purely to avoid NCCL deadlock and has no analogue
    in a compiled collective.
    """
    if not schedule.regular:
        raise ValueError("push-pull requires a regular schedule "
                         "(doubly-stochastic mixing)")
    return gossip_round(params, phase, schedule, axis_name,
                        comm_dtype=comm_dtype, codec=codec, kernel=kernel,
                        buckets=buckets)


def mix_bilat(params, phase, pairing: np.ndarray, axis_name: str):
    """Bilateral pairwise averaging: ``x ← (x + x_partner) / 2``.

    The synchronous formulation of AD-PSGD's bilateral exchange
    (gossiper.py:278-323, ad_psgd.py:347-363): each phase is a perfect
    matching (involution), so one ppermute moves both directions of every
    pair simultaneously.
    """
    num_phases, world = pairing.shape
    axis_size = lax.axis_size(axis_name)
    if axis_size != world:
        raise ValueError(
            f"pairing was built for world_size={world} but mesh axis "
            f"'{axis_name}' has size {axis_size}")
    if world == 1:
        return params

    def branch(p):
        pairs = _perm_pairs(pairing[p])

        def fn(tree):
            return jax.tree.map(
                lambda a: (a + lax.ppermute(a, axis_name, pairs))
                * jnp.asarray(0.5, a.dtype),
                tree)
        return fn

    if num_phases == 1:
        return branch(0)(params)
    return lax.switch(as_scalar(phase) % num_phases,
                      [branch(p) for p in range(num_phases)], params)


def allreduce_sum(tree, axis_name: str):
    """Exact all-reduce sum (the AR baseline's collective)."""
    return jax.tree.map(lambda a: lax.psum(a, axis_name), tree)


def allreduce_mean(tree, axis_name: str):
    """Exact all-reduce mean — replaces ``DistributedDataParallel``'s NCCL
    gradient averaging (gossip_sgd.py:179-180)."""
    return jax.tree.map(lambda a: lax.pmean(a, axis_name), tree)
