"""Runtime consensus health: in-graph signals + a host-side monitor.

PR 2's planner decides everything at *launch*; nothing in the repo could
see a mesh degrade at *runtime*.  This module is the seeing half of the
resilience loop (recovery.py is the acting half):

* :func:`health_signals` — a handful of cheap reductions computed INSIDE
  the compiled train step (they ride the metrics pytree, no extra host
  round-trip): push-sum weight min/max, total-mass error, non-finite
  element counts, and a consensus-residual estimate on a probe slice of
  the de-biased parameters (same ``‖x − x̄‖`` semantics as
  ``parallel/averaging.py:consensus_error``, but collective — a psum over
  the gossip axis — instead of a host gather of the full state);
* :class:`HealthMonitor` — host-side: consumes the fetched signals,
  emits structured JSONL ``gossip health:`` lines (matching the
  ``gossip plan:`` convention so one grep collects the whole telemetry
  stream), tracks step-time p50/p99 through a bounded
  :class:`~..utils.meter.PercentileMeter` (straggler skew), and flags
  excursions for the recovery policy.

Why these signals detect what they detect:

* ``ps_mass_err`` — column-stochastic mixing preserves ``Σ ps_weight``
  exactly, so ``|Σw/n − 1|`` growing from float-noise to O(edge weight)
  is the signature of a *mass-leaking* implementation (a dropped message
  whose weight nobody reabsorbed).  The regression test pins that naive
  dropping is caught within one ``--health_every`` window.
* ``ps_w_min`` collapsing toward 0 — a rank that keeps sending but
  stops *receiving* mass (dead in-edges) bleeds weight every round.
* ``consensus_residual`` — rising residual means the graph is no longer
  mixing fast enough (dropped edges, partition, below-floor topology);
  this is the signal recovery compares against ``--residual_floor``.
* ``nonfinite_params/grads`` — NaN/Inf anywhere in the network; with a
  corrupted wire the poison arrives through gossip, so the count is
  psum'd to make every rank see it the same step.
"""

from __future__ import annotations

import dataclasses
import json
import typing as tp

from ..utils.meter import PercentileMeter

__all__ = ["health_signals", "HealthMonitor", "HealthReport",
           "HEALTH_KEYS", "EF_HEALTH_KEY"]

# every key health_signals emits, in the order the JSONL line reports them
HEALTH_KEYS = ("consensus_residual", "ps_w_min", "ps_w_max", "ps_mass_err",
               "nonfinite_params", "nonfinite_grads")

# optional: quantization-residual RMS, emitted only by runs whose gossip
# wire runs error-feedback compression (parallel/wire.py).  Under healthy
# EF the residual stays bounded at ~one quantization step; sustained
# growth (or NaN from a corruption drill) means the feedback loop is
# diverging and the wire should be widened
EF_HEALTH_KEY = "ef_residual_rms"

# EF residual RMS above this is an excursion: parameters are O(1) and a
# healthy int8 residual sits 2-3 orders of magnitude below — anything
# approaching parameter scale means compression error is compounding,
# not telescoping.  Coarse by design; tune per run via the monitor knob.
DEFAULT_EF_RESIDUAL_FLOOR = 0.1

DEFAULT_PROBE_SLOTS = 256

# a push-sum weight this close to zero means the rank has effectively
# stopped receiving mass (its de-bias division is about to explode)
DEFAULT_PS_WEIGHT_FLOOR = 1e-2

# tolerance on |Σw/n - 1|: float32 gossip keeps the total exact to
# ~1e-6/round, so anything past this is a real leak, not rounding
DEFAULT_MASS_TOL = 1e-3


def _strided_sample(leaf, slots: int):
    """About ``slots`` elements spread over the whole of ``leaf``, as one
    strided slice in the leaf's own shape.  No reshape of the leaf itself:
    flattening a tiled array is a payload-sized copy on the chip, while a
    strided slice reads only what it keeps."""
    from jax import lax

    if leaf.size <= slots:
        return leaf.reshape(-1)
    counts = [1] * leaf.ndim
    left = slots
    # smallest dimensions first, each taking an equal share of what is left
    order = sorted(range(leaf.ndim), key=lambda d: leaf.shape[d])
    for n, d in enumerate(order):
        share = int(left ** (1.0 / (leaf.ndim - n)) + 1e-9)   # floor of root
        counts[d] = max(1, min(leaf.shape[d], share))
        left = max(1, left // counts[d])
    strides = [size // c for size, c in zip(leaf.shape, counts)]
    limits = [(c - 1) * st + 1 for c, st in zip(counts, strides)]
    return lax.slice(leaf, (0,) * leaf.ndim, limits, strides).reshape(-1)


def _probe(params, slots: int):
    """Deterministic probe of the parameters: strided slots from every
    leaf, ``slots`` of them shared out in proportion to the leaves' sizes
    and at least one a leaf.  Returns the concatenated sample, each slot's
    weight (how many elements of its leaf it stands for) and the number of
    parameters, so that the weighted mean square over the sample estimates
    the mean square over all of them.

    The head of the single largest leaf, which this replaces, read 0.0 on
    ResNet-50 while the replicas differed by 3e-3 (PR 21).  That leaf is a
    3x3 kernel of the last stage, inside a residual branch whose closing
    BatchNorm scale starts at zero: early in training its gradient, and
    with it the replicas' disagreement there, is at float32's resolution
    (1.8e-9 RMS after eight steps on four CPU ranks, against 1.5e-5 on
    the shortcut kernel beside it and 2.4e-5 over all parameters; PR 26),
    and the monitor rounds to eight places."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves = jax.tree.leaves(params)
    if not leaves:
        raise ValueError("health_signals needs at least one param leaf")
    total = sum(leaf.size for leaf in leaves)
    samples = [_strided_sample(
        leaf, max(1, round(slots * leaf.size / total))).astype(jnp.float32)
        for leaf in leaves]
    weights = np.concatenate([
        np.full(s.size, leaf.size / s.size, np.float32)
        for leaf, s in zip(leaves, samples)])
    return jnp.concatenate(samples), weights, total


def health_signals(params, grads, ps_weight, axis_name: str,
                   probe_slots: int = DEFAULT_PROBE_SLOTS,
                   ef_residual=None, in_flight=None) -> dict:
    """In-graph health reductions; call inside the compiled step (within
    shard_map) AFTER ``post_step``.  Returns float32 scalars that are
    identical on every rank (each is a collective over ``axis_name``), so
    the host can read any one shard.

    ``in_flight`` (the overlap FIFO, ``GossipState.in_flight``) makes
    the signals observe the DRAINED view: at staleness ≥ 2 weight mass
    legitimately rides the FIFO across the step boundary, so without
    the fold every overlap window would read as a push-sum mass leak —
    and false-trigger reactive recovery — when conservation actually
    holds.  Pass it whenever the algorithm runs overlap; ``None``/empty
    is the sync no-op.

    Cost: two scalar psums, one pmin/pmax pair, one pmean+psum about
    ``probe_slots`` wide (plus a slot for every small leaf) over strided
    slices of the leaves (no copy of a leaf), and one elementwise
    isfinite sweep — noise next to a
    forward/backward (plus ``staleness`` per-leaf adds under overlap).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..parallel.collectives import as_scalar

    if in_flight:
        from ..algorithms.algorithms import drain_in_flight

        params, ps_weight, _ = drain_in_flight(params, ps_weight,
                                               in_flight)
    w = as_scalar(ps_weight).astype(jnp.float32)
    world = lax.axis_size(axis_name)

    def nonfinite_count(tree):
        total = jnp.float32(0.0)
        for leaf in jax.tree.leaves(tree):
            total = total + jnp.sum(
                ~jnp.isfinite(leaf.astype(jnp.float32))).astype(jnp.float32)
        return lax.psum(total, axis_name)

    probe, slot_weight, probed = _probe(params, probe_slots)
    probe = probe / w   # de-biased view
    center = lax.pmean(probe, axis_name)
    residual = jnp.sqrt(
        lax.psum(jnp.sum(slot_weight * (probe - center) ** 2), axis_name)
        / (world * probed))

    out = {
        "consensus_residual": residual,
        "ps_w_min": lax.pmin(w, axis_name),
        "ps_w_max": lax.pmax(w, axis_name),
        "ps_mass_err": jnp.abs(lax.psum(w, axis_name) / world - 1.0),
        "nonfinite_params": nonfinite_count(params),
        "nonfinite_grads": (nonfinite_count(grads)
                            if grads is not None else jnp.float32(0.0)),
    }
    if ef_residual is not None:
        # network-wide RMS of the pending error-feedback residual: one
        # sum-of-squares sweep + one scalar psum.  A NaN here (poisoned
        # wire under a corruption drill) rides into the same excursion
        # machinery as every other signal.
        sq = jnp.float32(0.0)
        n_el = 0
        for leaf in jax.tree.leaves(ef_residual):
            sq = sq + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
            n_el += leaf.size
        out[EF_HEALTH_KEY] = jnp.sqrt(
            lax.psum(sq, axis_name) / (world * max(1, n_el)))
    return out


@dataclasses.dataclass(frozen=True)
class HealthReport:
    """One observed health snapshot plus the monitor's verdict."""

    step: int
    payload: dict
    reasons: tuple[str, ...]

    @property
    def unhealthy(self) -> bool:
        return bool(self.reasons)


class HealthMonitor:
    """Host-side consumer of :func:`health_signals` outputs.

    Emits one structured ``gossip health: {json}`` line every
    ``health_every`` observed steps — and immediately on any excursion,
    so a fault never waits for the cadence to be seen.  ``last_payload``
    is what the trainer stamps into checkpoint metadata (the run's
    health at save time rides with the state it describes).
    """

    def __init__(self, health_every: int = 100,
                 residual_floor: float = 0.01,
                 mass_tol: float = DEFAULT_MASS_TOL,
                 ps_weight_floor: float = DEFAULT_PS_WEIGHT_FLOOR,
                 log=None, step_window: int = 1024, registry=None,
                 ef_residual_floor: float = DEFAULT_EF_RESIDUAL_FLOOR):
        if health_every < 1:
            raise ValueError("health_every must be >= 1")
        self.health_every = health_every
        self.residual_floor = residual_floor
        self.mass_tol = mass_tol
        self.ps_weight_floor = ps_weight_floor
        self.ef_residual_floor = ef_residual_floor
        self.log = log
        # telemetry registry (telemetry.TelemetryRegistry): when set, the
        # monitor publishes typed `health` events and the registry's
        # LoggerCompatSink owns the legacy `gossip health:` line; when
        # None the pre-telemetry direct-logging path is unchanged
        self.registry = registry
        self.step_time = PercentileMeter(maxlen=step_window, ptag="Step")
        self.last_payload: dict | None = None
        self.reports: int = 0
        self.excursions: int = 0

    def record_step_time(self, seconds: float) -> None:
        self.step_time.update(seconds)

    def _diagnose(self, sig: tp.Mapping[str, float]) -> tuple[str, ...]:
        reasons = []
        if sig["consensus_residual"] > self.residual_floor \
                or not sig["consensus_residual"] == sig["consensus_residual"]:
            # NaN residual counts as an excursion (poisoned probe)
            reasons.append("residual-above-floor")
        if sig["ps_mass_err"] > self.mass_tol \
                or sig["ps_mass_err"] != sig["ps_mass_err"]:
            reasons.append("push-sum-mass-leak")
        if sig["ps_w_min"] < self.ps_weight_floor:
            reasons.append("ps-weight-collapse")
        if sig["nonfinite_params"] > 0 or \
                sig["nonfinite_params"] != sig["nonfinite_params"]:
            reasons.append("nonfinite-params")
        if sig["nonfinite_grads"] > 0 or \
                sig["nonfinite_grads"] != sig["nonfinite_grads"]:
            reasons.append("nonfinite-grads")
        ef = sig.get(EF_HEALTH_KEY)
        if ef is not None and (ef > self.ef_residual_floor or ef != ef):
            # quantization residual no longer bounded (or NaN-poisoned):
            # error feedback is compounding instead of telescoping
            reasons.append("ef-residual-blowup")
        return tuple(reasons)

    def observe(self, step: int, signals: tp.Mapping[str, tp.Any]
                ) -> HealthReport:
        """Digest one step's fetched signals; returns the report (the
        recovery policy consumes it).  Logging happens here so every
        emitted line went through the same diagnosis."""
        sig = {k: float(signals[k]) for k in HEALTH_KEYS}
        if EF_HEALTH_KEY in signals:
            sig[EF_HEALTH_KEY] = float(signals[EF_HEALTH_KEY])
        reasons = self._diagnose(sig)
        payload = {"step": int(step),
                   **{k: round(sig[k], 8) for k in sig},
                   "residual_floor": self.residual_floor,
                   "step_p50_s": round(self.step_time.p50, 5),
                   "step_p99_s": round(self.step_time.p99, 5)}
        if reasons:
            payload["reasons"] = list(reasons)
        self.last_payload = payload
        report = HealthReport(step=int(step), payload=payload,
                              reasons=reasons)
        due = step % self.health_every == 0
        if due or reasons:
            if self.registry is not None:
                # typed event; the compat sink reproduces the exact
                # legacy line from the same payload
                self.registry.emit(
                    "health", payload, step=int(step),
                    severity="warning" if reasons else "info")
            elif self.log is not None:
                line = "gossip health: " + json.dumps(payload,
                                                      sort_keys=True)
                if reasons:
                    self.log.warning(line)
                else:
                    self.log.info(line)
        if due or reasons:
            self.reports += 1
        if reasons:
            self.excursions += 1
        return report
