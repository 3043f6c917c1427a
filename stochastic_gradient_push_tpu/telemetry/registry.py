"""Typed, versioned event registry: one schema for the whole run.

Before this module the run's observability was four ad-hoc channels:
``gossip plan:`` / ``gossip health:`` / ``gossip recovery:`` JSONL lines
(three slightly different producers), train-loop prints, and the
profiler's plain-text stall warnings.  The registry replaces them with
ONE event stream under a versioned schema: every producer (train loop,
resilience monitor, recovery policy, planner, step watchdog, comm
accountant, bench) calls :meth:`TelemetryRegistry.emit` with a declared
``kind``, and the attached sinks fan the event out — to ``events.jsonl``
(:class:`~.sink.JsonlSink`) and, for the three legacy kinds, back to the
exact old ``gossip <kind>: {json}`` line format
(:class:`~.sink.LoggerCompatSink`), so existing grep/restart-harness
consumers keep working unchanged.

Event envelope (schema version |SCHEMA_VERSION|)::

    {"v": 1, "kind": "health", "t": <unix s>, "rank": 0,
     "severity": "info"|"warning"|"error", "step": 123, "data": {...}}

``data`` is the producer's payload, verbatim — for the legacy kinds it
is byte-identical to what the old line formats carried, which is what
makes the compatibility view exact.
"""

from __future__ import annotations

import time

__all__ = ["TelemetryRegistry", "SCHEMA_VERSION", "EVENT_KINDS",
           "LEGACY_PREFIXES", "SEVERITIES"]

SCHEMA_VERSION = 1

# the closed vocabulary of event kinds; emit() rejects anything else so a
# typo'd producer fails its own test instead of minting a private schema
EVENT_KINDS = frozenset({
    "run_meta",     # one per run: world/algorithm/knobs snapshot
    "plan",         # launch-time topology plan (planner.resolve_topology)
    "health",       # consensus health snapshot (resilience.HealthMonitor)
    "recovery",     # recovery decision (resilience.RecoveryPolicy)
    "heartbeat",    # step-watchdog stall (utils.profiling.StepWatchdog)
    "step_stats",   # periodic loop stats (loss, step/data time)
    "comm",         # comm-volume accounting snapshot (telemetry.comm)
    "bench",        # benchmark artifact lines (bench.py modes)
    "supervisor",   # run-supervisor lifecycle decision (supervise/)
    "relaunch",     # one generation boundary: reshard + replan + respawn
    "rendezvous",   # fleet host<->coordinator barrier protocol message
    "fleet",        # pod-coordinator decision (assign/go/complete/halt)
    "serve",        # serving-stack lifecycle (reject/summary; serve/)
    "request",      # one completed serve request (typed-only; serve/)
    "alert",        # SLO rule firing (typed-only; telemetry.aggregate)
    "setup",        # one per process: set-up's totals at the first step
    "compile",      # a program built after set-up (telemetry.setup_ledger)
})

SEVERITIES = ("info", "warning", "error")

# kinds that existed as bespoke `gossip <kind>: {json}` stdout lines
# before the registry; LoggerCompatSink re-emits them in that format
LEGACY_PREFIXES = {
    "plan": "gossip plan",
    "health": "gossip health",
    "recovery": "gossip recovery",
    "supervisor": "gossip supervisor",
    "rendezvous": "gossip rendezvous",
    "fleet": "gossip fleet",
    "serve": "gossip serve",
}


class TelemetryRegistry:
    """Fan-out point for typed events; producers emit, sinks consume."""

    def __init__(self, rank: int = 0, sinks=(), clock=time.time):
        self.rank = int(rank)
        self._sinks = list(sinks)
        self._clock = clock
        self.counts: dict[str, int] = {}

    def add_sink(self, sink) -> None:
        self._sinks.append(sink)

    def emit(self, kind: str, data: dict, step: int | None = None,
             severity: str = "info") -> dict:
        """Validate, envelope, and fan out one event; returns the event.

        Raises ``ValueError`` on an undeclared kind or severity and
        ``TypeError`` on a non-dict payload — the schema is the contract.
        """
        if kind not in EVENT_KINDS:
            raise ValueError(
                f"unknown event kind {kind!r}; declared kinds: "
                f"{sorted(EVENT_KINDS)}")
        if severity not in SEVERITIES:
            raise ValueError(f"unknown severity {severity!r}; "
                             f"use one of {SEVERITIES}")
        if not isinstance(data, dict):
            raise TypeError(f"event data must be a dict, got "
                            f"{type(data).__name__}")
        ev = {"v": SCHEMA_VERSION, "kind": kind,
              "t": round(self._clock(), 6), "rank": self.rank,
              "severity": severity, "data": data}
        if step is not None:
            ev["step"] = int(step)
        self.counts[kind] = self.counts.get(kind, 0) + 1
        for s in self._sinks:
            s.write(ev)
        return ev

    def close(self) -> None:
        for s in self._sinks:
            close = getattr(s, "close", None)
            if close is not None:
                close()
