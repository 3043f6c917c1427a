"""telemetry/ — unified structured tracing, metrics, and run artifacts.

One bundle per run (:class:`RunTelemetry`): a host span tracer writing
``<trace_dir>/trace.json`` (Chrome-trace/Perfetto), a typed event
registry writing ``<trace_dir>/events.jsonl`` under one versioned schema
(with the legacy ``gossip plan/health/recovery:`` lines preserved as a
compatibility view), and a comm-volume accountant pricing the active
plan in bytes.  ``scripts/obsreport.py`` ingests the directory and emits
the run report.

Disabled (no ``--trace_dir``) the whole subsystem is
:data:`NULL_TELEMETRY`: a singleton of constant no-ops — zero clock
reads, zero allocation, zero device syncs added to the train loop
(pinned by tests/test_telemetry.py).
"""

from __future__ import annotations

import os

from . import setup_ledger
from .comm import (
    COMM_CATEGORIES,
    CommAccountant,
    CommModel,
    allreduce_bytes,
    encoded_payload_bytes,
    tree_payload_bytes,
)
from .metrics import (
    METRIC_NAMES,
    MetricsRegistry,
    request_latency_meter,
    step_time_meter,
)
from .registry import (
    EVENT_KINDS,
    LEGACY_PREFIXES,
    SCHEMA_VERSION,
    TelemetryRegistry,
)
from .setup_ledger import setup_phase
from .sink import JsonlSink, LoggerCompatSink, MemorySink
from .tracer import NULL_TRACER, SPAN_PHASES, NullTracer, SpanTracer
from .tracer import _NULL_SPAN

__all__ = [
    "RunTelemetry", "make_run_telemetry", "NULL_TELEMETRY", "setup_phase",
    "SpanTracer", "NullTracer", "NULL_TRACER", "SPAN_PHASES",
    "TelemetryRegistry", "SCHEMA_VERSION", "EVENT_KINDS",
    "LEGACY_PREFIXES", "JsonlSink", "LoggerCompatSink", "MemorySink",
    "CommModel", "CommAccountant", "tree_payload_bytes",
    "encoded_payload_bytes", "allreduce_bytes", "COMM_CATEGORIES",
    "METRIC_NAMES", "MetricsRegistry", "step_time_meter",
    "request_latency_meter",
    "TRACE_FILE", "EVENTS_FILE", "SUPERVISOR_EVENTS_FILE",
    "COORDINATOR_EVENTS_FILE",
]

TRACE_FILE = "trace.json"
EVENTS_FILE = "events.jsonl"
# the run supervisor's own event stream (same envelope, kinds
# supervisor/relaunch).  A separate file, not events.jsonl: the
# supervisor TAILS events.jsonl while the child appends to it, and must
# neither race the child's writes nor read back its own
SUPERVISOR_EVENTS_FILE = "supervisor.jsonl"
# the pod coordinator's broadcast stream (kinds rendezvous/fleet): every
# per-host supervisor tails it for rendezvous calls and fleet decisions,
# while the coordinator tails each host's supervisor.jsonl — the two
# directions never share a file, so nobody reads back its own writes
COORDINATOR_EVENTS_FILE = "coordinator.jsonl"


def _rank_file(name: str, rank: int) -> str:
    """Per-process artifact name: rank 0 keeps the canonical filename,
    other processes get an ``_rN`` suffix — multi-process runs pointing
    every process at one shared --trace_dir must not clobber each
    other's trace or interleave one events file (same convention as the
    per-process CSVs, ``out_p{i}_...``)."""
    if not rank:
        return name
    base, ext = os.path.splitext(name)
    return f"{base}_r{rank}{ext}"


class RunTelemetry:
    """One run's live telemetry: tracer + registry (+ comm accountant).

    Created by the run layer (or the Trainer, for library users) when a
    trace directory is configured; the same registry instance is shared
    by the planner, the resilience monitor/policy, the step watchdog and
    the train loop, so every producer lands in one ``events.jsonl``.
    """

    enabled = True

    def __init__(self, trace_dir: str, rank: int = 0, log=None,
                 metrics_every: int = 0):
        os.makedirs(trace_dir, exist_ok=True)
        self.trace_dir = trace_dir
        self.rank = int(rank)
        self.metrics_every = max(0, int(metrics_every))
        self.tracer = SpanTracer(rank=rank)
        sinks = [JsonlSink(os.path.join(trace_dir,
                                        _rank_file(EVENTS_FILE, rank)))]
        if log is not None:
            # the compatibility view: legacy `gossip <kind>:` lines keep
            # flowing to the same logger the producers used before
            sinks.append(LoggerCompatSink(log))
        self.registry = TelemetryRegistry(rank=rank, sinks=sinks)
        self.comm: CommAccountant | None = None
        self._finished = False
        # set-up's phases reach trace.json, and programs built after
        # set-up become `compile` events, through the process's ledger
        setup_ledger.LEDGER.bind(self)

    # -- tracer passthrough (the loop's hot-path surface) ------------------

    def span(self, name, phase="step", args=None):
        return self.tracer.span(name, phase, args)

    def trace_complete(self, name, phase, start, dur, args=None):
        self.tracer.complete(name, phase, start, dur, args)

    # -- comm accounting ---------------------------------------------------

    def attach_comm(self, model: CommModel) -> CommAccountant:
        """Install the run's comm accountant (idempotent per model)."""
        self.comm = CommAccountant(model)
        return self.comm

    def emit_comm(self, step: int | None = None) -> None:
        if self.comm is not None:
            self.registry.emit("comm", self.comm.snapshot(), step=step)

    # -- lifecycle ---------------------------------------------------------

    def finish(self, step: int | None = None) -> None:
        """Write ``trace.json``, emit the final comm snapshot, close the
        sinks.  Idempotent — safe to call from a ``finally`` and again at
        process exit."""
        if self._finished:
            return
        self._finished = True
        setup_ledger.LEDGER.unbind(self)
        self.emit_comm(step=step)
        self.tracer.write(os.path.join(
            self.trace_dir, _rank_file(TRACE_FILE, self.rank)))
        self.registry.close()


class _NullTelemetry:
    """Disabled telemetry: constant no-ops, one shared instance."""

    enabled = False
    tracer = NULL_TRACER
    registry = None
    comm = None
    metrics_every = 0
    trace_dir = None

    __slots__ = ()

    def span(self, name, phase="step", args=None):
        return _NULL_SPAN

    def trace_complete(self, name, phase, start, dur, args=None):
        pass

    def attach_comm(self, model):
        return None

    def emit_comm(self, step=None):
        pass

    def finish(self, step=None):
        pass


NULL_TELEMETRY = _NullTelemetry()


def make_run_telemetry(trace_dir: str | None, rank: int = 0, log=None,
                       metrics_every: int = 0):
    """The single construction point: a live :class:`RunTelemetry` when
    ``trace_dir`` is set, else the shared :data:`NULL_TELEMETRY`."""
    if not trace_dir:
        return NULL_TELEMETRY
    return RunTelemetry(trace_dir, rank=rank, log=log,
                        metrics_every=metrics_every)
