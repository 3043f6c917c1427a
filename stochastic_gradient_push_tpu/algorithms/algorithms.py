"""The five training algorithms: AR, SGP, OSGP, D-PSGD, AD-PSGD.

Selection matrix (mirrors the reference CLI semantics, gossip_sgd.py:179-190):

| reference flags                    | here                          |
|------------------------------------|-------------------------------|
| ``--all_reduce True``              | :func:`all_reduce`            |
| ``--push_sum True``                | :func:`sgp` (overlap=False)   |
| ``--push_sum True --overlap True`` | :func:`sgp` (overlap=True)    |
| ``--push_sum False``               | :func:`dpsgd`                 |
| ``gossip_sgd_adpsgd.py``           | :func:`adpsgd`                |
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import collectives
from ..parallel.collectives import as_scalar
from ..parallel.pipeline import pvary_missing
from ..topology.schedule import GossipSchedule
from .api import GossipAlgorithm, GossipState, Params

__all__ = ["all_reduce", "sgp", "osgp", "dpsgd", "adpsgd",
           "GOSSIP_MODES", "gossip_mode", "gossip_algorithm",
           "drain_in_flight", "drain_state",
           "AllReduce", "PushSumGossip", "PushPullGossip", "BilateralGossip"]


def drain_in_flight(params, ps_weight, in_flight):
    """Fold every overlap in-flight share into ``(params, ps_weight)``
    and return the FIFO as zero slots.

    This is THE mass fold of the double-buffered schedule — purely
    per-rank adds (no collective): each pending share is network mass
    that left its sender and has not yet landed, so consuming it early
    is mean-preserving and counts it exactly once.  Single source of
    truth for every drain site: the in-step exact average
    (:meth:`PushSumGossip.global_average`), the validation view
    (:meth:`PushSumGossip.val_params`), and both run layers' checkpoint
    save barriers (train/loop.py, run/gossip_lm.py).  Works on
    per-rank state inside ``shard_map`` and on world-stacked host
    arrays alike (the adds are elementwise).

    Returns ``(params, ps_weight, drained_fifo)``.
    """
    for in_p, in_w in in_flight:
        params = jax.tree.map(
            lambda p, b: p + jnp.asarray(b, jnp.asarray(p).dtype),
            params, in_p)
        ps_weight = ps_weight + jnp.reshape(jnp.asarray(in_w),
                                            jnp.shape(ps_weight))
    drained = tuple(
        (jax.tree.map(jnp.zeros_like, in_p), jnp.zeros_like(in_w))
        for in_p, in_w in in_flight)
    return params, ps_weight, drained


def _vary_like(tree, like):
    """Mark every leaf of ``tree`` varying over the manual mesh axes the
    matching leaf of ``like`` varies over (no-op outside ``shard_map``)."""
    return jax.tree.map(
        lambda a, b: pvary_missing(a, tuple(jax.typeof(b).vma)), tree, like)


def drain_state(state):
    """Drain a train-state-like object's overlap FIFO into its params:
    the state-level wrapper around :func:`drain_in_flight` both run
    layers use at the checkpoint save barrier (train/loop.py and
    run/gossip_lm.py), so the checkpoint — and the continuing run,
    which adopts the returned state — carries nothing in flight and
    reshards/reloads like a sync checkpoint.  Duck-typed over anything
    with ``.params``, ``.gossip`` (a :class:`~.api.GossipState`) and
    flax-style ``.replace``; a no-op for sync runs and for staleness-1
    overlap (whose FIFO is empty between steps)."""
    fifo = getattr(getattr(state, "gossip", None), "in_flight", None)
    if not fifo:
        return state
    params, ps_weight, drained = drain_in_flight(
        state.params, state.gossip.ps_weight, fifo)
    return state.replace(
        params=params,
        gossip=state.gossip.replace(ps_weight=ps_weight,
                                    in_flight=drained))


class AllReduce(GossipAlgorithm):
    """Exact AllReduce-SGD baseline (≙ DistributedDataParallel,
    gossip_sgd.py:179-180): average gradients with ``psum`` every step."""

    name = "ar"

    def __init__(self, axis_name: str):
        self.axis_name = axis_name

    def reduce_grads(self, grads: Params) -> Params:
        return collectives.allreduce_mean(grads, self.axis_name)


class PushSumGossip(GossipAlgorithm):
    """Stochastic Gradient Push — synchronous or overlap (SGP / OSGP).

    Synchronous (overlap=False, ≙ ``GossipDataParallel(push_sum=True,
    overlap=False)``): after the optimizer step, run one complete push-sum
    round — parameters and push-sum weight mixed jointly
    (distributed.py:389-434 + gossiper.py:176-219 collapsed into one
    collective).

    Overlap (overlap=True, ≙ OSGP, distributed.py:571-588) is a
    first-class *phase schedule*, double-buffered around the compute:
    ``pre_step`` LAUNCHES round t at the top of the step —
    :func:`~..parallel.collectives.overlap_launch` issues the
    ``ppermute`` before the forward/backward, so XLA schedules the
    collective behind backprop compute — keeping only the local share
    ``lo·x`` and appending the incoming share to ``state.in_flight``;
    ``post_step`` CONSUMES the oldest in-flight share at the bottom.
    The de-bias ``x/w`` is invariant to the local rescale (both lanes
    scale by ``lo``), so the gradient is still evaluated at the exact
    de-biased iterate; the consumed share is one round stale, giving
    the effective recursion ``x_{t+1} = W·x_t − lr·u_t`` at staleness 1
    — the staleness-shifted mixing of "The Algorithm of Pipelined
    Gossiping", whose augmented matrix
    (:meth:`~..topology.schedule.GossipSchedule.overlap_schedule`) the
    schedule verifier checks column-stochastic and contracting exactly
    like sync schedules (SGPV106).

    ``staleness`` bounds how many steps an incoming share may ride in
    flight (≙ ``synch_freq``: the reference polls non-blocking for up to N
    steps before forcing a wait, distributed.py:127-129, :578, so its max
    staleness is ``synch_freq+1``; here the bound is exact rather than
    comm-speed-dependent).  ``in_flight`` is a FIFO of ``staleness``
    slots: ``pre_step`` fills the freed tail slot with the round just
    launched, ``post_step`` pops the head (launched ``staleness − 1``
    steps earlier).  Memory cost: ``staleness`` extra parameter copies.
    Every launched share is consumed exactly once, so push-sum mass
    conservation is preserved for any staleness.

    Because overlap is a schedule rather than a mode flag, the feature
    matrix composes like sync:

    * ``wire`` / ``error_feedback`` — the residual is injected into, and
      telescopes against, the round being SENT at launch time; a share
      consumed steps later carries its quantization error already
      accounted (staleness-aware EF carry).
    * ``faults`` — keep/corrupt masks are resolved at the LAUNCH tick,
      so a share launched under one fault state and consumed under
      another stays mass-conserving (the sender reabsorbed the dropped
      weight when the wire actually fired).
    * ``gossip_every`` thinning — non-firing steps launch nothing (a
      zero slot rides the FIFO) and the rotation advances with fired
      rounds only, exactly like the sync thinned path.
    * hierarchical schedules — only the delegate (inter/DCN) share is
      deferred; the cheap ICI-local intra-slice psum runs at consume
      time (it cannot ride in flight), so the expensive collective is
      the hidden one.
    * ``global_avg_every`` / reactive recovery — the exact average FOLDS
      the in-flight FIFO into ``Σx/Σw`` and drains it (zero slots), so
      nothing is double-counted: the averaged value is the true network
      mean including in-flight mass.

    ``wire`` (a :class:`~..parallel.wire.WireCodec`) compresses gossip
    payloads on the ppermute boundary — bf16 or per-block int8; the
    push-sum weight lane always ships exact f32.  ``error_feedback``
    adds the per-rank residual accumulator (``GossipState.ef_residual``)
    that re-injects each round's quantization error into the next send,
    bounding the compression perturbation (parallel/collectives.py
    module docstring).  It composes with ``gossip_every`` thinning (the
    residual waits out non-firing steps), with fault injection (dropped
    edges carry their residual), with hierarchical schedules (the codec
    rides the delegate DCN lane; the intra-slice psum stays exact), and
    with overlap (above).  The residual deliberately SURVIVES exact
    global averages: it is sender-local pending correction, and
    re-injecting it later loses nothing the average computed.

    ``global_avg_every`` interleaves an *exact* global average every k-th
    step (periodic global averaging, Chen et al.): after the gossip
    round, ``x ← Σ x / Σ w`` via one allreduce and the push-sum weight
    resets to 1.  The consensus value of push-sum is exactly that ratio,
    so the operation preserves the mean for any mixing (uniform or
    irregular) while snapping all ranks to consensus — the planner's
    recovery for topologies whose spectral gap is below the floor at the
    requested world size.  Under overlap the average additionally drains
    the in-flight FIFO (see above).
    """

    name = "sgp"

    def __init__(self, schedule: GossipSchedule, axis_name: str,
                 overlap: bool = False, track_weight: bool = True,
                 gossip_every: int = 1, comm_dtype=None,
                 staleness: int = 1, global_avg_every: int = 0,
                 faults=None, wire=None, error_feedback: bool = False,
                 gossip_kernel=None, gossip_buckets: int = 1):
        self.schedule = schedule
        self.axis_name = axis_name
        self.overlap = overlap
        from ..topology.hierarchical import HierarchicalSchedule
        from ..topology.synthesized import SynthesizedSchedule

        if isinstance(schedule, HierarchicalSchedule) and faults is not None:
            # two-level rounds compile to leader ppermute + grouped psum;
            # the psum has no per-edge mask, so this fence REMAINS (the
            # overlap fence was lifted: the delegate share defers cleanly,
            # collectives.overlap_launch + intra_average at consume)
            raise ValueError(
                "inject_faults is not supported on hierarchical "
                "schedules: the intra-slice psum has no per-edge "
                "mask (use a flat topology for fault drills)")
        if isinstance(schedule, SynthesizedSchedule):
            # same psum fence as hierarchical, plus overlap: a searched
            # psum/ppermute composition has no augmented in-flight table
            # form for the double-buffered round to verify against
            if faults is not None:
                raise ValueError(
                    "inject_faults is not supported on synthesized "
                    "schedules: grouped psum phases have no per-edge "
                    "mask (use a flat registry topology for fault "
                    "drills)")
            if overlap:
                raise ValueError(
                    "overlap is not supported on synthesized "
                    "schedules: a psum/ppermute phase composition has "
                    "no single augmented in-flight form (use a "
                    "registry topology for overlap runs)")
        # deterministic fault injection (resilience/faults.py FaultMasks):
        # the mixing boundary applies the plan's keep/corrupt masks with
        # mass-conserving reabsorption.  Composes with overlap — masks
        # are keyed on the LAUNCH tick, so the wire a mask describes is
        # the wire that actually fired, whatever step consumes the share.
        if faults is not None and faults.gossip_every != gossip_every:
            # phase-dependent masks are resolved against the rotation
            # actually active at each tick, which depends on thinning
            raise ValueError(
                f"fault masks were compiled for gossip_every="
                f"{faults.gossip_every} but the algorithm runs "
                f"gossip_every={gossip_every}; rebuild the masks with "
                "the matching thinning factor")
        self.faults = faults
        if staleness < 1:
            raise ValueError("staleness must be >= 1")
        if staleness > 1 and not overlap:
            raise ValueError("staleness is an overlap-mode knob")
        self.staleness = staleness
        # push-pull (D-PSGD) reuses this machinery with no ps-weight
        self.track_weight = track_weight
        # communication thinning: gossip on every k-th step only (the
        # compiled counterpart of the reference's synch_freq intent —
        # fewer communications per optimization step)
        if gossip_every < 1:
            raise ValueError("gossip_every must be >= 1")
        self.gossip_every = gossip_every
        # periodic exact global averaging every k-th step (0 = off);
        # see the class docstring.  Under overlap the average folds and
        # drains the in-flight FIFO, so nothing is double-counted.
        if global_avg_every < 0:
            raise ValueError("global_avg_every must be >= 0")
        self.global_avg_every = global_avg_every
        # wire codec for gossip payloads (parallel/wire.py); comm_dtype
        # is the deprecated bf16-only alias — both resolve to one codec,
        # and a lossless codec compiles to the uncompressed path
        from ..parallel import wire as wire_mod

        if wire is not None and comm_dtype is not None:
            raise ValueError("pass either wire (a WireCodec) or the "
                             "deprecated comm_dtype, not both")
        if wire is None and comm_dtype is not None:
            wire = wire_mod.from_comm_dtype(comm_dtype)
        self.wire = wire
        self.comm_dtype = comm_dtype  # kept for introspection only
        # per-rank error-feedback residual accumulators (wire.py module
        # docstring): quantization error from round t re-injected into
        # round t+1's send — requires a lossy codec to have any error.
        # Composes with overlap: the residual telescopes against the
        # round being SENT at launch time (staleness-aware EF carry), so
        # in-flight shares carry their quantization error pre-accounted.
        if error_feedback:
            if wire is None or not wire.lossy:
                raise ValueError(
                    "error_feedback needs a lossy wire codec "
                    "(wire_dtype bf16/int8); exact wires have no "
                    "quantization error to feed back")
            if not track_weight:
                raise ValueError(
                    "error_feedback rides the push-sum wire "
                    "(track_weight=True); the push-pull path carries "
                    "no residual state")
        self.error_feedback = bool(error_feedback)
        # fused Pallas transport (ops/gossip_kernel.py): accept the CLI
        # flag string ("auto"/"pallas"/"xla") or an already-resolved
        # KernelLane; None = the XLA ppermute lane.  Resolution happens
        # HERE — construction time — so gossip_kernel="pallas" on a
        # backend that cannot lower the kernel fails with the typed
        # KernelBackendError before anything compiles.
        if isinstance(gossip_kernel, str):
            from ..ops.gossip_kernel import resolve_gossip_kernel

            gossip_kernel = resolve_gossip_kernel(gossip_kernel)
        self.gossip_kernel = gossip_kernel
        # transport bucketing (collectives._transport_plan): the kernel
        # lane partitions each round's payload into this many contiguous
        # byte-bounded buckets, each its own start/wait pallas_call pair
        # — more buckets in flight per overlap round, identical wire
        # bytes and numerics.  Inert on the XLA lane.
        if gossip_buckets < 1:
            raise ValueError("gossip_buckets must be >= 1")
        self.gossip_buckets = int(gossip_buckets)

    @property
    def transport_kernel_name(self) -> str:
        """The transport lane the wire ACTUALLY runs, for telemetry.
        One configuration resolves a configured kernel lane back to
        ``"xla"``: a lossy codec with no in-kernel decode spec
        (``kernel_spec() is None`` pins the XLA path at the
        ``collectives._round_fn`` transport seam; a lossless codec
        resolves to the exact-f32 wire, which the kernel does carry).
        Overlap no longer downgrades: the split start/wait kernel
        (ops/gossip_kernel.py) issues its remote DMA at launch and
        lands it at consume, so the pallas lane rides the overlap
        schedule first-class."""
        if self.gossip_kernel is None:
            return "xla"
        if (self.wire is not None and self.wire.lossy
                and self.wire.kernel_spec() is None):
            return "xla"
        return self.gossip_kernel.name

    # -- helpers -----------------------------------------------------------

    def _zeros_like_params(self, params: Params):
        return jax.tree.map(jnp.zeros_like, params)

    def _mix(self, params, ps_weight, phase, tick=None, residual=None):
        """One wire round; returns ``(params, ps_weight, residual)`` —
        residual is None unless error feedback is active."""
        if self.track_weight:
            out = collectives.mix_push_sum(
                params, ps_weight, phase, self.schedule, self.axis_name,
                codec=self.wire, faults=self.faults, tick=tick,
                ef_residual=residual, kernel=self.gossip_kernel,
                buckets=self.gossip_buckets)
            if residual is None:
                return out[0], out[1], None
            return out
        return (collectives.mix_push_pull(
            params, phase, self.schedule, self.axis_name,
            codec=self.wire, kernel=self.gossip_kernel,
            buckets=self.gossip_buckets), ps_weight, None)

    def _launch(self, params, ps_weight, rotation, tick, residual):
        """Launch one double-buffered round (collectives.overlap_launch):
        returns ``(local_params, local_w, incoming, new_residual)`` where
        ``incoming`` is the ``(params, w)`` share to defer in the FIFO —
        a plain tree on the XLA lane, a ``collectives.PendingShares``
        carrying per-bucket transport handles on the kernel lane (the
        split start kernel issued its remote DMA here; post_step lands
        or settles it at the bottom of this same step).
        local = lo·x; incoming = Σ_i ppermute(w_i·x) — their sum is
        exactly the synchronous round, so overlap differs from sync only
        in *when* the incoming share is applied.
        """
        tree = (params, ps_weight)
        if residual is None:
            local, incoming = collectives.overlap_launch(
                tree, rotation, self.schedule, self.axis_name,
                codec=self.wire, faults=self.faults, tick=tick,
                kernel=self.gossip_kernel, buckets=self.gossip_buckets)
            return local[0], local[1], incoming, None
        full_res = (residual, jax.tree.map(jnp.zeros_like, ps_weight))
        local, incoming, new_res = collectives.overlap_launch(
            tree, rotation, self.schedule, self.axis_name,
            codec=self.wire, faults=self.faults, tick=tick,
            ef_residual=full_res, kernel=self.gossip_kernel,
            buckets=self.gossip_buckets)
        return local[0], local[1], incoming, new_res[0]

    # -- algorithm slots ---------------------------------------------------

    def init(self, params: Params) -> GossipState:
        state = GossipState(phase=jnp.int32(0), ps_weight=jnp.float32(1.0))
        if self.error_feedback:
            # pending quantization error starts at zero; the structure
            # mirrors params (the compressed lanes), never the ps-weight
            state = state.replace(
                ef_residual=self._zeros_like_params(params))
        if self.overlap:
            # FIFO of `staleness` (params, weight) slots, each holding one
            # round's incoming share.  A tuple of slots (static pytree
            # structure) rather than a stacked axis keeps the algorithm
            # agnostic to how callers batch/shard the state leaves.
            slot = lambda: (self._zeros_like_params(params),
                            jnp.float32(0.0))
            state = state.replace(
                in_flight=tuple(slot() for _ in range(self.staleness)))
        return state

    def pre_step(self, params, state):
        if not self.overlap:
            return params, state
        # LAUNCH round t at the top of the step: the ppermute is issued
        # before the forward/backward, so XLA schedules the collective
        # behind compute.  Only the local share lo·x stays; the de-bias
        # x/w is invariant to that rescale (both lanes scale by lo), so
        # the gradient is still taken at the exact de-biased iterate.
        # The incoming share fills the FIFO slot post_step freed.
        tick = as_scalar(state.phase)
        if self.gossip_every > 1:
            fire = (tick % self.gossip_every) == 0
            rotation = tick // self.gossip_every

            def launch_branch(op):
                p, w, r = op
                return self._launch(p, w, rotation, tick, r)

            def skip_branch(op):
                # non-firing step: nothing launches; a zero share rides
                # the FIFO so the consume clock stays uniform.  On the
                # kernel lane the zero share is a zero PendingShares —
                # lax.cond arms must hand back the same pytree as the
                # launch arm (waiting a zero handle lands zero)
                p, w, r = op
                return p, w, collectives.empty_incoming(
                    (p, w), self.schedule, codec=self.wire,
                    kernel=self.gossip_kernel,
                    buckets=self.gossip_buckets), r

            local_p, local_w, incoming, residual = jax.lax.cond(
                fire, launch_branch, skip_branch,
                (params, state.ps_weight, state.ef_residual))
        else:
            local_p, local_w, incoming, residual = self._launch(
                params, state.ps_weight, tick, tick, state.ef_residual)
        local_w = jnp.reshape(jnp.asarray(local_w, jnp.float32),
                              jnp.shape(state.ps_weight))
        in_flight = state.in_flight[:-1] + (incoming,)
        return local_p, state.replace(ps_weight=local_w,
                                      in_flight=in_flight,
                                      ef_residual=residual)

    def eval_params(self, params, state):
        if not self.track_weight:
            return params
        w = as_scalar(state.ps_weight)
        return jax.tree.map(lambda p: p / w.astype(p.dtype), params)

    def val_params(self, params, state):
        """Validation view: drain every in-flight share first (≙ the
        reference's ``model.eval()`` blocking drain before validation,
        distributed.py:322-327), then de-bias.  At staleness 1 this
        makes OSGP validation numerically IDENTICAL to sync SGP — the
        local+incoming split is exact, so between-step params differ
        from the synchronous trajectory only by the not-yet-applied
        incoming share this method adds back.  The training state is
        untouched (pure eval-time view)."""
        if not self.overlap:
            return self.eval_params(params, state)
        params, ps_weight, _ = drain_in_flight(params, state.ps_weight,
                                               state.in_flight)
        if not self.track_weight:
            return params
        w = as_scalar(ps_weight)
        return jax.tree.map(lambda p: p / w.astype(p.dtype), params)

    def post_step(self, params, state):
        phase = state.phase
        if not self.overlap:
            if self.gossip_every > 1:
                return self._thinned_post_step(params, state)
            params, ps_weight, residual = self._mix(
                params, state.ps_weight, phase,
                residual=state.ef_residual)
            ps_weight = jnp.reshape(jnp.asarray(ps_weight, jnp.float32),
                                    jnp.shape(state.ps_weight))
            params, ps_weight = self._maybe_global_average(
                params, ps_weight, phase + 1)
            return params, state.replace(phase=phase + 1,
                                         ps_weight=ps_weight,
                                         ef_residual=residual)
        # overlap: CONSUME the oldest in-flight round at the bottom of
        # the step (≙ _query_gossip_queue, distributed.py:336-387:
        # p += r; ps_weight += gossip_ps_weight), launched staleness−1
        # steps ago by pre_step; the freed tail slot takes the next
        # launch.  The round's transport — XLA's async collective
        # permute or the split kernel's per-bucket remote DMA — had the
        # whole forward/backward to complete; land_shares folds a plain
        # share with a tree add and a PendingShares through the wait
        # kernel (in-VMEM decode + per-edge axpy per bucket).
        tick = as_scalar(phase)
        params, ps_weight = collectives.land_shares(
            (params, state.ps_weight), state.in_flight[0])
        ps_weight = jnp.reshape(ps_weight, jnp.shape(state.ps_weight))
        from ..topology.hierarchical import HierarchicalSchedule

        if isinstance(self.schedule, HierarchicalSchedule):
            # the deferred share was the delegate (DCN) half only; the
            # ICI-local intra-slice psum runs now, on the round whose
            # share was just consumed — gated so it fires exactly as
            # often as the sync hierarchical round would
            launch_tick = tick - (self.staleness - 1)
            fired = launch_tick >= 0
            if self.gossip_every > 1:
                fired = jnp.logical_and(
                    fired, (launch_tick % self.gossip_every) == 0)

            def intra_branch(op):
                return collectives.intra_average(op, self.schedule,
                                                 self.axis_name)

            # sgplint: disable=SGPL011 (fired is rank-uniform: step counter + static config)
            params, ps_weight = jax.lax.cond(
                fired, intra_branch, lambda op: op, (params, ps_weight))
        # SETTLE every slot this step does not consume: the slot pushed
        # by pre_step may carry live transport handles (PendingShares),
        # and those exist strictly inside the step that launched them —
        # the wait lands here, at the bottom, with the whole step's
        # compute between start and wait.  Between steps the FIFO holds
        # plain arrays only, so checkpoints, resharding, drains and the
        # monitor are bucketing-agnostic.
        empty = (self._zeros_like_params(params),
                 jnp.zeros_like(state.ps_weight))
        in_flight = tuple(collectives.settle_share(s)
                          for s in state.in_flight[1:]) + (empty,)
        params, ps_weight, in_flight = self._maybe_global_average(
            params, ps_weight, tick + 1, in_flight=in_flight)
        return params, state.replace(phase=phase + 1,
                                     ps_weight=ps_weight,
                                     in_flight=in_flight)

    def _thinned_post_step(self, params, state):
        """Gossip on every ``gossip_every``-th call; the rotation phase
        advances only when a round actually fires, so the graph cycles
        through the same peer sequence as un-thinned gossip."""
        tick = collectives.as_scalar(state.phase)
        fire = (tick % self.gossip_every) == 0
        rotation = tick // self.gossip_every

        def mix_branch(operand):
            p, w, r = operand
            # faults are indexed by the step clock (tick), not the slower
            # rotation counter — a fault window means wall steps
            p, w, r = self._mix(p, w, rotation, tick=tick, residual=r)
            return (p, jnp.reshape(jnp.asarray(w, jnp.float32),
                                   jnp.shape(state.ps_weight)), r)

        # on non-firing steps the residual rides through unchanged —
        # pending error waits for the next wire round
        params, ps_weight, residual = jax.lax.cond(
            fire, mix_branch, lambda o: o,
            (params, state.ps_weight, state.ef_residual))
        params, ps_weight = self._maybe_global_average(
            params, ps_weight, tick + 1)
        return params, state.replace(phase=state.phase + 1,
                                     ps_weight=ps_weight,
                                     ef_residual=residual)

    def global_average(self, params, ps_weight, in_flight=None):
        """Exact push-sum consensus NOW: ``x ← Σ params / Σ ps_weight``
        (one allreduce) and the weight resets to 1.  Mass conservation
        makes that ratio the true parameter average under any
        column-stochastic mixing — including faulted mixing with
        mass-conserving drops — so the trajectory mean is untouched while
        consensus error snaps to zero.  Called per-rank inside
        shard_map; the periodic schedule (:meth:`_maybe_global_average`)
        and the resilience recovery path (resilience/recovery.py) both
        route through here.

        ``in_flight`` (the overlap FIFO) FOLDS pending shares into both
        sums and returns the FIFO drained to zero slots: an in-flight
        share is network mass that has left its sender and not yet
        reached its receiver, so counting it exactly once — here — is
        what keeps the average the true mean.  Returns
        ``(params, ps_weight)`` or ``(params, ps_weight, drained_fifo)``.
        """
        drained = None
        if in_flight is not None:
            params, ps_weight, drained = drain_in_flight(
                params, ps_weight, in_flight)
        tot_p, tot_w = collectives.allreduce_sum((params, ps_weight),
                                                 self.axis_name)
        tw = as_scalar(tot_w)
        params = jax.tree.map(lambda a: (a / tw.astype(a.dtype)), tot_p)
        if drained is None:
            return params, jnp.ones_like(ps_weight)
        return params, jnp.ones_like(ps_weight), drained

    def _maybe_global_average(self, params, ps_weight, tick_next,
                              in_flight=None):
        """Every ``global_avg_every`` steps: fire :meth:`global_average`
        (periodic global averaging, Chen et al.).  With ``in_flight``
        (overlap) the fired average folds and drains the FIFO."""
        if self.global_avg_every <= 0:
            if in_flight is None:
                return params, ps_weight
            return params, ps_weight, in_flight
        fire = (as_scalar(tick_next) % self.global_avg_every) == 0

        # the psum inside global_average returns values that no longer
        # vary over the gossip axis; cond needs both branches to return
        # the operands' own varying type
        if in_flight is None:
            return jax.lax.cond(
                fire, lambda o: _vary_like(self.global_average(*o), o),
                lambda o: o, (params, ps_weight))
        return jax.lax.cond(
            fire,
            lambda o: _vary_like(
                self.global_average(o[0], o[1], in_flight=o[2]), o),
            lambda o: o, (params, ps_weight, in_flight))


class PushPullGossip(PushSumGossip):
    """D-PSGD: doubly-stochastic gossip
    (≙ ``GossipDataParallel(push_sum=False)`` → ``PushPull.mix``,
    gossiper.py:222-275).

    Synchronous mode needs no push-sum weight: a complete doubly-stochastic
    round preserves the mean directly.  Overlap mode *must* track it — the
    parameters are scaled by ``lo`` between launching a round and consuming
    it, and the de-bias division is what keeps gradients evaluated at the
    right point (the reference's ps-weight machinery likewise stays active
    for PushPull, gossiper.py:160-169 with distributed.py:298-314).
    """

    name = "dpsgd"

    def __init__(self, schedule: GossipSchedule, axis_name: str,
                 overlap: bool = False, staleness: int = 1,
                 global_avg_every: int = 0, faults=None,
                 gossip_kernel=None, gossip_buckets: int = 1):
        if not schedule.regular:
            raise ValueError("D-PSGD requires a regular schedule "
                             "(doubly-stochastic mixing)")
        if faults is not None:
            # a dropped edge breaks ROW-stochasticity even with sender
            # reabsorption, and without a ps-weight there is no mass
            # accounting to absorb the asymmetry — the exact failure mode
            # push-sum exists to survive (Assran et al. 2018, §1)
            raise ValueError(
                "inject_faults requires push-sum: D-PSGD's "
                "doubly-stochastic invariant does not survive dropped "
                "edges (use --push_sum True)")
        super().__init__(schedule, axis_name, overlap=overlap,
                         track_weight=overlap, staleness=staleness,
                         global_avg_every=global_avg_every,
                         gossip_kernel=gossip_kernel,
                         gossip_buckets=gossip_buckets)


class BilateralGossip(GossipAlgorithm):
    """AD-PSGD in its synchronous perfect-matching formulation.

    The reference runs bilateral averaging in a separate OS process with its
    own optimizer, shipping gradients through shared memory
    (ad_psgd.py:120-133, 252-366) — host-side asynchrony that cannot (and
    should not) live inside one SPMD program.  The TPU-native counterpart:
    every step, each rank averages parameters with one rotating partner,
    ``x ← (x + x_partner)/2`` (≙ ad_psgd.py:358-361), with the matching
    schedule derived from the same communication graph.  See SURVEY.md §7
    "Hard parts" #4 for the staleness-distribution caveat.
    """

    name = "adpsgd"

    def __init__(self, pairing: np.ndarray, axis_name: str):
        self.pairing = pairing
        self.axis_name = axis_name

    def post_step(self, params, state):
        params = collectives.mix_bilat(
            params, state.phase, self.pairing, self.axis_name)
        return params, state.replace(phase=state.phase + 1)


# -- factory helpers matching the reference's flag surface -------------------

def all_reduce(axis_name: str) -> AllReduce:
    return AllReduce(axis_name)


def sgp(schedule: GossipSchedule, axis_name: str,
        overlap: bool = False, gossip_every: int = 1,
        comm_dtype=None, staleness: int = 1,
        global_avg_every: int = 0, faults=None, wire=None,
        error_feedback: bool = False,
        gossip_kernel=None, gossip_buckets: int = 1) -> PushSumGossip:
    return PushSumGossip(schedule, axis_name, overlap=overlap,
                         gossip_every=gossip_every, comm_dtype=comm_dtype,
                         staleness=staleness,
                         global_avg_every=global_avg_every, faults=faults,
                         wire=wire, error_feedback=error_feedback,
                         gossip_kernel=gossip_kernel,
                         gossip_buckets=gossip_buckets)


def osgp(schedule: GossipSchedule, axis_name: str,
         staleness: int = 1, gossip_kernel=None,
         gossip_buckets: int = 1) -> PushSumGossip:
    return PushSumGossip(schedule, axis_name, overlap=True,
                         staleness=staleness,
                         gossip_kernel=gossip_kernel,
                         gossip_buckets=gossip_buckets)


def dpsgd(schedule: GossipSchedule, axis_name: str,
          overlap: bool = False, staleness: int = 1,
          global_avg_every: int = 0, faults=None,
          gossip_kernel=None, gossip_buckets: int = 1) -> PushPullGossip:
    return PushPullGossip(schedule, axis_name, overlap=overlap,
                          staleness=staleness,
                          global_avg_every=global_avg_every, faults=faults,
                          gossip_kernel=gossip_kernel,
                          gossip_buckets=gossip_buckets)


def adpsgd(pairing: np.ndarray, axis_name: str) -> BilateralGossip:
    return BilateralGossip(pairing, axis_name)


# -- from a job's gossip settings to its algorithm ---------------------------

# also the ``algorithm`` stamp of the harnesses' run_meta events
GOSSIP_MODES = ("all_reduce", "adpsgd", "bilat_async", "sgp", "dpsgd")


def gossip_mode(*, all_reduce: bool, push_sum: bool, bilat: bool = False,
                bilat_async: bool = False) -> str:
    """The selection flags of the module docstring's table as one name."""
    if all_reduce:
        return "all_reduce"
    if bilat_async:
        return "bilat_async"
    if bilat:
        return "adpsgd"
    return "sgp" if push_sum else "dpsgd"


def gossip_algorithm(mode: str, axis_name: str, *, world: int,
                     graph_class=None, peers_per_itr: int = 1, mixing=None,
                     overlap: bool = False, staleness: int = 1,
                     gossip_every: int = 1, wire_dtype: str | None = None,
                     wire_block: int = 64, error_feedback: bool = False,
                     global_avg_every: int = 0,
                     inject_faults: str | None = None,
                     gossip_kernel="xla", gossip_buckets: int = 1,
                     log=None) -> GossipAlgorithm:
    """The algorithm a job's gossip settings describe — the one place
    both harnesses (``train/loop.py::Trainer``, ``run/gossip_lm.py``)
    turn settings into a :class:`GossipAlgorithm`.

    ``mode`` is one of :data:`GOSSIP_MODES`; ``graph_class(world,
    peers_per_itr=...)`` builds the communication graph and ``mixing``
    (a ``MixingStrategy`` or None = uniform) its weights;
    ``inject_faults`` is a fault spec (resilience/faults.py grammar)
    compiled here against the schedule it will run on.  A knob the mode
    does not have is a ``ValueError``, never silently dropped.
    """
    from ..parallel.wire import get_codec
    from ..topology import build_pairing_schedule, build_schedule

    if mode not in GOSSIP_MODES:
        raise ValueError(f"unknown gossip mode {mode!r}; one of "
                         f"{GOSSIP_MODES}")
    codec = get_codec(wire_dtype, wire_block)
    if mode != "sgp":
        if codec is not None and codec.lossy:
            raise ValueError("wire compression (wire_dtype) applies to "
                             "the push-sum family only")
        if error_feedback:
            raise ValueError(
                "error_feedback rides the push-sum gossip wire; "
                "all_reduce/bilateral/D-PSGD modes have none")
        if gossip_every != 1:
            raise ValueError("gossip_every is a push-sum knob")
    if mode not in ("sgp", "dpsgd"):
        if global_avg_every:
            raise ValueError(
                "global_avg_every applies to the push-sum/D-PSGD gossip "
                "family (all_reduce is already exact every step)")
        if inject_faults:
            raise ValueError(
                "inject_faults breaks gossip edges; all_reduce/bilateral "
                "modes have none (use push-sum gossip)")
    if mode == "all_reduce":
        return all_reduce(axis_name)
    if mode == "bilat_async":
        # no collective in the compiled step: the bilateral averaging
        # runs host-side (train/async_bilat.py); pure local SGD here
        return GossipAlgorithm()
    graph = graph_class(world, peers_per_itr=peers_per_itr)
    if mode == "adpsgd":
        return adpsgd(build_pairing_schedule(graph), axis_name)
    schedule = build_schedule(graph, mixing)
    faults = None
    if inject_faults:
        # compiled against THIS schedule: masks are per-(phase, edge), so
        # a peers_per_itr change rebuilds them
        from ..resilience import parse_fault_spec

        fault_plan = parse_fault_spec(inject_faults)
        faults = fault_plan.build_masks(schedule, gossip_every=gossip_every)
        if log is not None:
            log.warning("gossip faults: %s", fault_plan.summary())
    common = dict(overlap=overlap, staleness=staleness,
                  global_avg_every=global_avg_every, faults=faults,
                  gossip_kernel=gossip_kernel,
                  gossip_buckets=gossip_buckets)
    if mode == "sgp":
        return sgp(schedule, axis_name, gossip_every=gossip_every,
                   wire=codec, error_feedback=error_feedback, **common)
    return dpsgd(schedule, axis_name, **common)
