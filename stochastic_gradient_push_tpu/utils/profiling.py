"""Profiling and failure-detection utilities.

The reference's observability is manual wall-clock meters (SURVEY.md §5
"Tracing") and its failure detection is a 300-second heartbeat on the
gossip thread's flag (distributed.py:36, :349-352).  Here:

* :func:`trace` — ``jax.profiler`` trace context producing TensorBoard-
  loadable XPlane dumps of the actual device timeline (compute/collective
  overlap included), something the reference cannot see at all.
  Captures start with the Python tracer off (``python_tracer_level = 0``:
  a frame event per Python call swamps the host plane and slows the loop
  it is meant to observe); the host plane instead holds the loops' own
  spans, which :meth:`ProfileWindow.span` / :meth:`ProfileWindow.step`
  write under the names of ``telemetry/names.py`` — the same clock as the
  device planes, so an idle gap can be put down to the span it fell in.
  The profiler entry points here run the ``jax.profiler`` calls on a
  guarded timeout thread: a ``start_trace``/``stop_trace`` that does not
  return within ``timeout`` seconds, or raises, is logged as an ERROR
  and the run continues untraced — a training job must not die of its
  profiler, but a capture that was asked for and not taken is never
  silent.
* :class:`StepWatchdog` — heartbeat for the compiled step.  A hang inside
  one XLA program can't happen the way a lost NCCL broadcast could, but a
  multi-host collective CAN stall if a peer host dies; the watchdog logs
  loudly (and optionally aborts) when a step exceeds the timeout — the
  moral equivalent of the reference's ``Gossip flag timeout``.
"""

from __future__ import annotations

import contextlib
import threading
import time

from ..telemetry.names import HOST_SPAN_PREFIX, HOST_STEP
from ..telemetry.tracer import _NULL_SPAN
from .logging import make_logger

__all__ = ["trace", "start_trace_guarded", "stop_trace_guarded",
           "ProfileWindow", "StepWatchdog", "HEARTBEAT_TIMEOUT"]

HEARTBEAT_TIMEOUT = 300  # seconds, matching distributed.py:36

_PROFILER_TIMEOUT = 60  # seconds before declaring the profiler RPC hung


def _call_with_timeout(fn, timeout: float, what: str,
                       on_late_completion=None) -> bool:
    """Run ``fn`` on a watchdog thread; False if it didn't return in time.

    A hung C call can't be cancelled — the thread is daemonic and leaks,
    which is the acceptable cost of the RUN not hanging.  If the leaked call
    COMPLETES later, ``on_late_completion`` runs on that thread — e.g. a
    start_trace that eventually succeeded after being declared hung must
    be stopped, or the profiler would silently accumulate events for the
    rest of the process."""
    done = threading.Event()
    err: list[BaseException] = []
    lock = threading.Lock()
    state = {"late": False}

    def run():
        try:
            fn()
        except BaseException as e:  # sgplint: disable=SGPL007
            # (deliberate transport: re-raised verbatim on the caller
            # thread — narrowing here would swallow what the caller sees)
            err.append(e)
        with lock:
            done.set()
            late = state["late"]
        if late and not err and on_late_completion is not None:
            try:
                on_late_completion()
            except (RuntimeError, OSError):
                # RuntimeError: stop_trace with no active trace (the late
                # start lost a race with an explicit stop); OSError: the
                # stop's dump-to-disk failed — either way nothing more to
                # undo, and a leaked daemon thread must not traceback
                pass

    t = threading.Thread(target=run, daemon=True, name=f"profiler-{what}")
    t.start()
    if not done.wait(timeout):
        with lock:
            if not done.is_set():
                state["late"] = True
                make_logger("profiler").error(
                    f"jax.profiler {what} did not return within "
                    f"{timeout:.0f}s; continuing UNTRACED")
                return False
        # completed inside the race window: fall through as a normal return
    if err:
        raise err[0]
    return True


def start_trace_guarded(log_dir: str,
                        timeout: float = _PROFILER_TIMEOUT) -> bool:
    """Guarded ``jax.profiler.start_trace``; False = hung/failed (logged
    as an error), the caller must skip the matching stop."""
    import jax

    def undo_late_start():
        # the hung start eventually succeeded after we gave up on it:
        # stop immediately (on the leaked thread) so the profiler doesn't
        # accumulate events for the rest of the process
        make_logger("profiler").warning(
            "hung start_trace completed late; stopping the trace")
        jax.profiler.stop_trace()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        return _call_with_timeout(
            lambda: jax.profiler.start_trace(
                log_dir, profiler_options=options), timeout, "start",
            on_late_completion=undo_late_start)
    except (RuntimeError, OSError, ValueError) as e:
        # RuntimeError: profiler already active; OSError: unwritable
        # log_dir; ValueError: bad arguments from the caller's config
        make_logger("profiler").error(
            f"start_trace failed, continuing UNTRACED: {e}")
        return False


def stop_trace_guarded(timeout: float = _PROFILER_TIMEOUT) -> bool:
    """Guarded ``jax.profiler.stop_trace``; False = hung/failed (logged
    as an error): no dump was written."""
    import jax

    try:
        return _call_with_timeout(
            lambda: jax.profiler.stop_trace(), timeout, "stop")
    except (RuntimeError, OSError) as e:
        # RuntimeError: no trace running (hung start declared dead);
        # OSError: dump-to-disk failure at stop time
        make_logger("profiler").error(
            f"stop_trace failed, no trace written: {e}")
        return False


@contextlib.contextmanager
def trace(log_dir: str, timeout: float = _PROFILER_TIMEOUT):
    """Profile the enclosed steps into ``log_dir`` (TensorBoard format).

    Degrades to a no-op (with an error line) when the profiler does not
    start — see the module docstring."""
    started = start_trace_guarded(log_dir, timeout)
    try:
        yield
    finally:
        if started:
            stop_trace_guarded(timeout)


class ProfileWindow:
    """Step-indexed ``jax.profiler`` capture window.

    Both run CLIs used to hand-roll the same start/stop-around-steps
    dance (with subtly different hang handling); this is the one shared
    implementation.  Construct it with the run's ``--profile_dir`` (or
    ``None``, in which case every call is a constant no-op) and call
    :meth:`maybe_start`/:meth:`maybe_stop` with the GLOBAL step counter
    around the blocking step call::

        pw = ProfileWindow(profile_dir, start_step=2, num_steps=3)
        ...
        pw.maybe_start(gstep)
        state, metrics = train_fn(state, x, y)
        jax.block_until_ready(state)
        pw.maybe_stop(gstep)

    Capture covers steps ``[start_step, start_step + num_steps)``.  The
    guarded profiler entry points apply (module docstring): a failed
    start is logged as an error, abandoned, and the window is never
    retried — a second 60 s stall would just burn another step.

    While a capture is active :meth:`span` and :meth:`step` put the
    loop's own phases into it (``sgp:<name>`` / ``sgp_step`` on the host
    plane); otherwise both return one shared no-op context — no clock
    read, no allocation, nothing of ``jax.profiler`` touched — so the
    loops wrap their phases unconditionally.
    """

    def __init__(self, profile_dir: str | None, start_step: int = 2,
                 num_steps: int = 3, timeout: float = _PROFILER_TIMEOUT):
        self.profile_dir = profile_dir or None
        self.start_step = int(start_step)
        self.num_steps = max(1, int(num_steps))
        self.timeout = timeout
        self.active = False
        self._done = profile_dir is None

    @property
    def enabled(self) -> bool:
        return self.profile_dir is not None

    def span(self, name: str):
        """Context manager marking a host phase of the loop (a name of
        ``telemetry.names.HOST_SPANS``) in the active capture."""
        if not self.active:
            return _NULL_SPAN
        import jax

        return jax.profiler.TraceAnnotation(HOST_SPAN_PREFIX + name)

    def step(self, step_num: int):
        """Context manager marking one iteration (GLOBAL step number) in
        the active capture."""
        if not self.active:
            return _NULL_SPAN
        import jax

        return jax.profiler.StepTraceAnnotation(HOST_STEP,
                                                step_num=int(step_num))

    def maybe_start(self, step: int) -> bool:
        """Start the trace iff ``step`` enters the window; True while a
        capture is active (idempotent inside the window)."""
        if self._done or self.active:
            return self.active
        if step < self.start_step:
            return False
        # one shot only: a window that was skipped past (resume landing
        # beyond it) or whose start hung must not re-arm later
        self._done = True
        if step >= self.start_step + self.num_steps:
            return False
        self.active = start_trace_guarded(self.profile_dir, self.timeout)
        return self.active

    def maybe_stop(self, step: int) -> bool:
        """Stop the trace once ``step`` completes the window (or
        unconditionally via :meth:`close`); True if a dump was written."""
        if not self.active:
            return False
        if step < self.start_step + self.num_steps - 1:
            return False
        self.active = False
        return stop_trace_guarded(self.timeout)

    def close(self) -> None:
        """Stop any still-open capture (run ended inside the window)."""
        if self.active:
            self.active = False
            stop_trace_guarded(self.timeout)


class StepWatchdog:
    """Wall-clock heartbeat around blocking step calls.

    Usage::

        wd = StepWatchdog(timeout=300)
        with wd.step():
            state, metrics = train_fn(state, x, y)
            jax.block_until_ready(state)

    With a telemetry ``registry`` attached, every stall additionally
    lands as a structured ``heartbeat`` event in ``events.jsonl`` (the
    plain-text error line alone was invisible to any tooling; the
    obsreport counts these events as the run's stall record).
    """

    def __init__(self, timeout: float = HEARTBEAT_TIMEOUT, rank: int = 0,
                 abort_on_timeout: bool = False, registry=None):
        self.timeout = timeout
        self.abort_on_timeout = abort_on_timeout
        self.rank = rank
        self.logger = make_logger(rank)
        self.registry = registry
        self.timed_out = False

    @contextlib.contextmanager
    def step(self):
        fired = threading.Event()
        start = time.monotonic()

        def watch():
            if not fired.wait(self.timeout):
                self.timed_out = True
                elapsed = time.monotonic() - start
                self.logger.error(
                    f"step exceeded heartbeat timeout "
                    f"({elapsed:.0f}s > {self.timeout}s) — device stall, "
                    "or an unreachable peer host on multi-host runs")
                if self.registry is not None:
                    # sinks are thread-safe; this runs on the watchdog
                    # thread while the main thread is (by definition)
                    # stuck in the blocking step
                    self.registry.emit(
                        "heartbeat",
                        {"elapsed_s": round(elapsed, 3),
                         "timeout_s": self.timeout, "rank": self.rank},
                        severity="error")
                if self.abort_on_timeout:
                    import os
                    os._exit(70)

        t = threading.Thread(target=watch, daemon=True,
                             name="StepWatchdog")
        t.start()
        try:
            yield
        finally:
            fired.set()
