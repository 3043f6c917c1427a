"""The set-up ledger: everything the process does before its first train
step, on one clock.

Two sides feed it.  JAX publishes every program it builds through
``jax.monitoring`` (names.py ``JAX_*_EVENT``): a trace, a lowering and a
backend compile-or-load, each a ``[start, end]`` on ``time.time()`` with
the function's name, and beside them what the persistent cache said.  The
program marks its own phases with :func:`setup_phase` (names.py
``SETUP_SPANS``), two readings of the same clock.  ``time.time()`` is also
``SpanTracer``'s clock and the benchmark's ``PROCESS_START``, so nothing is
converted.

One row a program, in the order built.  Cache events carry no name; they
fire inside a program's backend interval on the building thread, so they
belong to the row that closes next, and so does the newest trace and
lowering of that name.  Inner ``jit``s trace inside the outer's trace:
every total :meth:`SetupLedger.summary` gives is a union of intervals,
never a sum of durations.

Set-up ends (the cut) when the first train step (names.py
``STEP_MODULES``) has been built.  The totals stop there; a program built
later is a row of its own kind, and once a loop has reported set-up and run
telemetry is bound it becomes a ``compile`` event: which program, how long,
and whether that name had been built before.

The ledger is armed by the entry points (``utils/compile_cache.py``),
once; armed, it costs a few list appends a program built and nothing
between builds (a warm step reaches no listener).
"""

from __future__ import annotations

import os
import time

from . import names
from .tracer import _NULL_SPAN

__all__ = ["LEDGER", "SetupLedger", "arm", "disarm", "setup_phase",
           "first_step", "setup_line"]

# a row's cache: the persistent cache's hit, the step store's load
_LOADED = ("hit", "stored")

_SPAN_KINDS = {names.JAX_TRACE_EVENT: "trace",
               names.JAX_LOWER_EVENT: "lower",
               names.JAX_BACKEND_EVENT: "backend"}


def _program_name(fun_name: str) -> str:
    """``jit(f)``, as the lowering and the backend name a program, to the
    ``f`` its trace carries."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _union(intervals) -> float:
    """Seconds covered by at least one of ``(start, end)``."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _process_start(now: float) -> float:
    """When the kernel started this process, on ``time.time()``'s clock,
    so that the interpreter's start and the imports before the ledger's
    own count as set-up; ``now`` where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        start = now - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now
    return start if start <= now else now


def _length(interval) -> float:
    return interval[1] - interval[0] if interval else 0.0


class SetupLedger:
    """Rows of programs built and spans of phases run, one process's."""

    def __init__(self, clock=time.time):
        self._clock = clock
        self.armed = False
        self._telemetry = None
        self.reset(since=_process_start(clock()))

    def reset(self, since: float | None = None) -> None:
        """Forget everything recorded and count set-up from ``since``
        (now, for the tests, which alone start set-up twice in one
        process); stay armed and bound."""
        self.t0 = self._clock() if since is None else since
        # (kind, name, start, end): JAX's traces and lowerings while
        # set-up is open, the program's phases always (they are few)
        self.spans: list[tuple] = []
        self.rows: list[dict] = []
        self.cut: int | None = None       # the first train step's row
        self.setup_rows: int | None = None   # rows in set-up, once closed
        self.reported = False
        self.reported_at: float | None = None
        self._log = None            # where the report went
        self._pending: dict = {}    # (kind, fun_name) -> span no row took
        self._cache: dict = {}      # what the cache said since a row closed
        self._builds: dict = {}     # program name -> times built
        self.store_notes: list[str] = []   # the step store's, until reported

    @property
    def closed(self) -> bool:
        return self.setup_rows is not None

    # -- JAX's side: the listeners ------------------------------------

    def on_time_span(self, event, start, end, fun_name="", **_) -> None:
        kind = _SPAN_KINDS.get(event)
        if kind == "backend":
            self._close_row(fun_name, start, end)
        elif kind is not None:
            self._pending[kind, fun_name] = (start, end)
            if not self.closed:
                self.spans.append((kind, fun_name, start, end))

    def on_event(self, event, **_) -> None:
        if event == names.JAX_CACHE_HIT_EVENT:
            self._cache["cache"] = "hit"
        elif event == names.JAX_CACHE_MISS_EVENT:
            self._cache["cache"] = "miss"

    def on_duration(self, event, seconds, **_) -> None:
        if event == names.JAX_CACHE_RETRIEVAL_EVENT:
            self._cache["retrieval_s"] = seconds
        elif event == names.JAX_CACHE_SAVED_EVENT:
            self._cache["saved_s"] = seconds

    def _close_row(self, fun_name, start, end) -> None:
        name = _program_name(fun_name)
        row = {"fun_name": name,
               "trace": self._pending.get(("trace", name)),
               "lower": self._pending.get(("lower", fun_name)),
               "backend": (start, end), "cache": "uncached",
               "retrieval_s": 0.0, "saved_s": 0.0, **self._cache}
        # whatever else was pending belonged to no program: a trace for
        # shapes alone, a cached trace looked up again
        self._pending.clear()
        self._cache = {}
        self._add_row(row)

    def stored(self, name, start, end) -> None:
        """A train step the step store (``utils/step_store.py``) loaded:
        never traced nor lowered, its key, read and load from ``start`` to
        ``end`` as its backend interval."""
        if self.armed:
            self._add_row({"fun_name": name, "trace": None, "lower": None,
                           "backend": (start, end), "cache": "stored",
                           "retrieval_s": 0.0, "saved_s": 0.0})

    def note_store(self, text: str) -> None:
        """What the step store did with a step built before the report:
        ``stored (hit)``, ``miss, written``, ``refused: <why>``..."""
        if not self.reported:
            self.store_notes.append(text)

    def _add_row(self, row) -> None:
        name = row["fun_name"]
        row["build"] = self._builds[name] = self._builds.get(name, 0) + 1
        self.rows.append(row)
        if not self.closed and name in names.STEP_MODULES:
            self.cut = len(self.rows) - 1
            self.setup_rows = len(self.rows)
        elif self.reported:
            # the `setup` event lists what was built before it
            self._after_setup(row)

    def _after_setup(self, row) -> None:
        """A program built after set-up: a ``compile`` event where run
        telemetry is bound, and a warning (in the event, and on the log
        the report went to) where it is built a second time and is a
        train step or was dear enough for the persistent cache to keep: a
        shape, a sharding or a constant changed under it.  jax.numpy's
        own helpers are built again for every new shape by design, in
        hundredths of a second: those stay ``info``."""
        again = row["build"] > 1 and (
            row["fun_name"] in names.STEP_MODULES
            or row["cache"] != "uncached")
        view = _row_view(row)
        if again and self._log is not None:
            self._log.warning(f"{view['fun_name']} built again (build "
                              f"{view['build']}): {_row_text(view)}")
        if self._telemetry is not None:
            try:
                self._telemetry.registry.emit(
                    "compile", _rounded(view),
                    severity="warning" if again else "info")
            except (OSError, ValueError):
                # a sink that cannot be written (a full disk, a file
                # closed under us): this runs inside JAX's compile, and a
                # run must not die of its telemetry
                self._telemetry = None

    # -- the program's side -------------------------------------------

    def phase(self, name: str, start: float, end: float) -> None:
        self.spans.append(("phase", name, start, end))
        if self._telemetry is not None:
            self._telemetry.trace_complete(name, "setup", start,
                                           end - start)

    def bind(self, telemetry) -> None:
        """The run's telemetry, for ``trace.json`` and the ``compile``
        events; the phases that ran before it existed (``parse``) are
        handed over now, under the timestamps they were given."""
        self._telemetry = telemetry
        if not self.reported:
            for kind, name, start, end in self.spans:
                if kind == "phase":
                    telemetry.trace_complete(name, "setup", start,
                                             end - start)

    def unbind(self, telemetry) -> None:
        if self._telemetry is telemetry:
            self._telemetry = None

    # -- reading it ---------------------------------------------------

    def summary(self, until: float | None = None) -> dict:
        """Set-up's totals, each a union of intervals cut off at the end
        of set-up: ``until`` where the caller names it (the loops, after
        their first step), else the end of the first train step's build,
        else when a loop reported, else now."""
        n = self.setup_rows if self.closed else len(self.rows)
        rows, later = self.rows[:n], self.rows[n:]
        if until is not None:
            end = until
        elif self.cut is not None:
            end = self.rows[self.cut]["backend"][1]
        else:
            end = self.reported_at or self._clock()

        def clipped(spans):
            return [(s, min(e, end)) for s, e in spans if s < end]

        built = clipped(s[2:] for s in self.spans if s[0] != "phase")
        compiled = clipped(r["backend"] for r in rows
                           if r["cache"] not in _LOADED)
        loaded = clipped(r["backend"] for r in rows
                         if r["cache"] in _LOADED)
        phases: dict[str, float] = {}
        phase_spans = []
        for kind, name, s, e in self.spans:
            if kind == "phase" and s < end:
                phase_spans.append((s, min(e, end)))
                phases[name] = phases.get(name, 0.0) + min(e, end) - s
        trace_lower, compile_s, load_s = (_union(built), _union(compiled),
                                          _union(loaded))
        jax_s = _union(built + compiled + loaded)
        covered = _union(built + compiled + loaded + phase_spans)
        total = end - self.t0
        step = self.rows[self.cut] if self.cut is not None else None
        return {
            "total_s": total,
            "trace_lower_s": trace_lower,
            "compile_s": compile_s,
            "cache_load_s": load_s,
            # seconds two kinds of JAX's intervals share (a program
            # compiled while another was being traced)
            "overlap_s": trace_lower + compile_s + load_s - jax_s,
            "phases_s": phases,
            # under a phase and inside no build: the program's own work
            "phases_outside_builds_s": covered - jax_s,
            "covered_s": covered,
            "other_s": total - covered,
            "programs": len(rows),
            "cache_hits": sum(r["cache"] == "hit" for r in rows),
            "cache_misses": sum(r["cache"] == "miss" for r in rows),
            "uncached": sum(r["cache"] == "uncached" for r in rows),
            "step_store_hits": sum(r["cache"] == "stored" for r in rows),
            "step_store": self.store_notes[0] if self.store_notes else None,
            "step_program": _row_view(step) if step else None,
            "rows": [_row_view(r) for r in rows],
            "later_rows": [_row_view(r) for r in later],
        }

    def report(self, log, telemetry=None, step: int | None = None):
        """Close set-up, log its line and, with run telemetry, emit the
        ``setup`` event.  Once a process; nothing where no entry point
        armed the ledger."""
        if self.reported:
            return None
        self.reported = True
        self.reported_at = self._clock()
        if not self.armed:
            return None
        if not self.closed:       # a step under no name of STEP_MODULES
            self.setup_rows = len(self.rows)
        s = self.summary(until=self.reported_at)
        self._log = log
        log.info(setup_line(s))
        if telemetry is not None and telemetry.enabled:
            telemetry.registry.emit("setup", _rounded(s), step=step)
        return s


def _row_view(row: dict) -> dict:
    """A row by durations, as summaries, events and logs carry it."""
    parts = {k + "_s": _length(row[k]) for k in ("trace", "lower", "backend")}
    return {"fun_name": row["fun_name"], "seconds": sum(parts.values()),
            **parts, "cache": row["cache"],
            "retrieval_s": row["retrieval_s"], "saved_s": row["saved_s"],
            "build": row["build"]}


def _row_text(view: dict) -> str:
    return (f"{view['seconds']:.1f} s (trace {view['trace_s']:.1f}, lower "
            f"{view['lower_s']:.1f}, "
            f"{'load' if view['cache'] in _LOADED else 'compile'} "
            f"{view['backend_s']:.1f})")


def _rounded(value):
    """``value`` with every float in it to the microsecond, for an
    event's payload."""
    if isinstance(value, float):
        return round(value, 6)
    if isinstance(value, dict):
        return {k: _rounded(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_rounded(v) for v in value]
    return value


LEDGER = SetupLedger()


def arm() -> None:
    """Register the ledger's listeners with ``jax.monitoring``; a second
    call is a no-op."""
    if LEDGER.armed:
        return
    import jax.monitoring as monitoring

    monitoring.register_event_time_span_listener(LEDGER.on_time_span)
    monitoring.register_event_listener(LEDGER.on_event)
    monitoring.register_event_duration_secs_listener(LEDGER.on_duration)
    LEDGER.armed = True


def disarm() -> None:
    """Take the listeners away again (the tests')."""
    if not LEDGER.armed:
        return
    import jax.monitoring as monitoring

    monitoring.unregister_event_time_span_listener(LEDGER.on_time_span)
    monitoring.unregister_event_listener(LEDGER.on_event)
    monitoring.unregister_event_duration_listener(LEDGER.on_duration)
    LEDGER.armed = False


class _Phase:
    """One phase of set-up: two clock reads, handed to the ledger (and
    through it to the bound run's ``trace.json``), around a
    ``TraceAnnotation`` for whatever capture is running."""

    __slots__ = ("_name", "_start", "_annotation")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation(
            names.SETUP_SPAN_PREFIX + self._name)
        self._annotation.__enter__()
        self._start = time.time()
        return self

    def __exit__(self, *exc):
        end = time.time()
        self._annotation.__exit__(*exc)
        LEDGER.phase(self._name, self._start, end)
        return False


def setup_phase(name: str):
    """Context manager around one phase of set-up, a name of
    ``names.SETUP_SPANS``.  Put it around work the process does itself: a
    phase inside a function some caller traces times the tracing."""
    if name not in names.SETUP_SPANS:
        raise ValueError(f"unknown set-up phase {name!r}; the phases are "
                         f"{names.SETUP_SPANS}")
    return _Phase(name)


class _FirstStep(_Phase):
    """The ``first_step`` phase, and set-up's report on the way out."""

    __slots__ = ("_report",)

    def __init__(self, *report):
        super().__init__("first_step")
        self._report = report

    def __exit__(self, *exc):
        super().__exit__(*exc)
        if exc[0] is None:
            LEDGER.report(*self._report)
        return False


def first_step(log, telemetry=None, step: int | None = None):
    """What the loops put around a step, dispatch to fence: until set-up
    has been reported, the ``first_step`` phase (the step's program is
    built inside its dispatch) that on its way out logs set-up's line
    and, with run telemetry, emits the ``setup`` event
    (:meth:`SetupLedger.report`); from then on the shared no-op."""
    return _NULL_SPAN if LEDGER.reported else _FirstStep(log, telemetry,
                                                          step)


def setup_line(s: dict) -> str:
    """One line for the operator from :meth:`SetupLedger.summary`; the
    parts after ``=`` add up to the total."""
    phases = ", ".join(f"{k} {s['phases_s'][k]:.1f}"
                       for k in names.SETUP_SPANS if k in s["phases_s"])
    line = (f"set-up: {s['total_s']:.1f} s = trace+lower "
            f"{s['trace_lower_s']:.1f} | compile {s['compile_s']:.1f} | "
            f"cache load {s['cache_load_s']:.1f} | ")
    if s["overlap_s"] >= 0.05:
        line += f"shared by two of these -{s['overlap_s']:.1f} | "
    line += (f"phases outside builds {s['phases_outside_builds_s']:.1f} | "
             f"other {s['other_s']:.1f}; {s['programs']} programs, "
             f"{s['cache_misses']} cache misses")
    step = s["step_program"]
    if step:
        line += f"; step program {step['fun_name']} {_row_text(step)}"
    if s.get("step_store"):
        line += f"; step {s['step_store']}"
    if phases:
        line += f"; whole phases: {phases}"
    return line
