"""From a profiler trace to numbers: the reduction every PR shares.

``read_trace`` turns an ``.xplane.pb`` into plain lists of events (device
operations per chip, the benchmark's host annotations); everything else
here is arithmetic on intervals, tested on hand-made events.  Times are
seconds on the trace's own clock.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"            # what the core ran, one operation at a time
ASYNC_LINE = "Async XLA Ops"   # asynchronous operations, start to done
# the chip's trace names an operation by its whole HLO instruction:
# "%fusion.13 = (f32[256]{...}, bf16[256,56,56,256]{...}) fusion(...), kind=..."
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = (.*?)\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_KIND = re.compile(r"kind=k(\w+)")
HOST_PREFIX = "bench:"
STEP_NAME = "bench_step"
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str           # the operation's own name: fusion.13
    start: float
    end: float
    detail: str = ""    # the whole instruction, where the trace gives it

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Event]]   # chip id -> device operations
    host: list[Event]                 # the benchmark's own annotations
    steps: list[Event]                # one per traced step
    # chip id -> asynchronous operations, each from its start to its done
    in_flight: dict[int, list[Event]] = dataclasses.field(
        default_factory=dict)


def short_name(instruction: str) -> str:
    """``%fusion.13 = ... fusion(...)`` -> ``fusion.13``; a plain name is
    itself."""
    return instruction.split(" = ", 1)[0].lstrip("%")


def label(instruction: str) -> str:
    """A name a reader of the ledger can place: the operation's name, its
    opcode (a fusion's kind) and its largest output."""
    m = _INSTRUCTION.match(instruction)
    if not m:
        return short_name(instruction)
    name, outputs, opcode = m.groups()
    kind = _KIND.search(instruction)
    if opcode == "fusion" and kind:
        opcode = f"fusion:{kind.group(1)}"
    shapes = _SHAPE.findall(outputs)
    if not shapes:
        return f"{name} {opcode}"
    dtype, dims = max(shapes, key=lambda sh: math.prod(
        int(d) for d in sh[1].split(",") if d))
    return f"{name} {opcode} {dtype}[{dims}]"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_trace(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices: dict[int, list[Event]] = {}
    in_flight: dict[int, list[Event]] = {}
    host: list[Event] = []
    steps: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        on_device = DEVICE_PLANE.match(plane.name)
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name not in (OP_LINE, ASYNC_LINE):
                continue
            for e in line.events:
                start = e.start_ns * 1e-9
                end = start + e.duration_ns * 1e-9
                if on_device:
                    into = devices if line.name == OP_LINE else in_flight
                    into.setdefault(int(on_device.group(1)), []).append(
                        Event(short_name(e.name), start, end, e.name))
                elif e.name == STEP_NAME:
                    steps.append(Event(e.name, start, end))
                elif e.name.startswith(HOST_PREFIX):
                    host.append(Event(e.name, start, end))
    for events in (*devices.values(), *in_flight.values(), host, steps):
        events.sort(key=lambda ev: (ev.start, -ev.end))
    return Trace(devices, host, steps, in_flight)


# -- interval arithmetic ----------------------------------------------------

Interval = tuple[float, float]


def union(intervals) -> list[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    merged: list[list[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def clip(intervals, lo: float, hi: float) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes) -> list[Interval]:
    """The part of ``intervals`` (disjoint, sorted) outside ``holes``."""
    holes = union(holes)
    out = []
    for lo, hi in intervals:
        at = lo
        for a, b in holes:
            if b <= at:
                continue
            if a >= hi:
                break
            if a > at:
                out.append((at, a))
            at = max(at, b)
        if at < hi:
            out.append((at, hi))
    return out


def spans(events) -> list[Interval]:
    return [(e.start, e.end) for e in events]


# -- the reductions ---------------------------------------------------------

def window_of(trace: Trace) -> Interval:
    """The traced stretch: first traced step's start to the last one's
    end."""
    if not trace.steps:
        raise ValueError("the trace holds no step annotation")
    return trace.steps[0].start, trace.steps[-1].end


def busy_seconds(events, window: Interval) -> float:
    """Seconds of ``window`` in which some operation ran."""
    return total(clip(union(spans(events)), *window))


def is_collective(event: Event) -> bool:
    return COLLECTIVE.match(event.name) is not None


def collective_intervals(events, in_flight=()) -> list[Interval]:
    """When a collective was in flight on one chip: a synchronous
    operation's own interval, and for an asynchronous one the stretch from
    its ``-start`` to the end of its ``-done`` (paired by the number they
    share, else first started, first done).  ``in_flight`` are the chip's
    asynchronous operations as the trace's own line gives them, start to
    done in one event; the collectives among them count too."""
    out = [(e.start, e.end) for e in in_flight if is_collective(e)]
    pending: dict = {}
    for e in events:
        m = COLLECTIVE.match(e.name)
        if not m:
            continue
        kind, phase, number = m.groups()
        if phase == "-start":
            pending.setdefault(kind, []).append((number, e))
        elif phase == "-done":
            started = pending.get(kind, [])
            at = next((i for i, (n, _) in enumerate(started)
                       if n == number), 0)
            if started:
                out.append((started.pop(at)[1].start, e.end))
            else:
                out.append((e.start, e.end))
        else:
            out.append((e.start, e.end))
    # a start whose done fell outside the trace still occupied the chip
    out.extend((s.start, s.end) for started in pending.values()
               for _, s in started)
    return union(out)


def _picoseconds(seconds: float) -> int:
    """An event's end is its start plus its duration in floating point,
    so the end of one operation can read 1e-17 s after the start of the
    next, which follows it back to back.  On whole picoseconds (the
    trace's own unit) the two are equal, and nothing reads as nested that
    is not."""
    return round(seconds * 1e12)


def innermost(events) -> list[Event]:
    """The operations that hold no other operation: a ``conditional`` or a
    ``while`` spans its body and is not itself work."""
    ordered = sorted(events, key=lambda ev: (ev.start, -ev.end))
    return [e for e, after in zip(ordered, ordered[1:] + [None])
            if after is None
            or _picoseconds(after.start) >= _picoseconds(e.end)]


def exposed_collective_intervals(events, in_flight=()) -> list[Interval]:
    """The part of the collective intervals in which no other operation
    ran on that chip."""
    others = [e for e in innermost(events) if not is_collective(e)]
    return subtract(collective_intervals(events, in_flight), spans(others))


def matching_seconds(events, pattern: str, window: Interval) -> float:
    """Device seconds of the operations whose name or instruction match
    ``pattern`` (a kernel by its call target or function name)."""
    rx = re.compile(pattern)
    return total(clip(union(spans(
        e for e in events if rx.search(e.name) or rx.search(e.detail))),
        *window))


def self_seconds(events, key=None) -> dict[str, float]:
    """Seconds by operation (named by ``key(event)``; by default by
    :func:`label`), a nested operation's time taken out of the one that
    holds it (a ``while`` and its body).  Counted on whole picoseconds, so
    the parts add up to the busy time."""
    key = key or (lambda e: label(e.detail) if e.detail else e.name)
    out: dict[str, int] = {}
    stack: list[tuple[int, str]] = []        # (end, name) of open operations
    for start, end, name in sorted(
            ((_picoseconds(e.start), _picoseconds(e.end), key(e))
             for e in events), key=lambda row: (row[0], -row[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= end - start
        out[name] = out.get(name, 0) + end - start
        stack.append((end, name))
    return {name: ps * 1e-12 for name, ps in out.items()}


def idle_gaps(events, window: Interval) -> list[Interval]:
    return subtract([window], spans(events))


def attribute_gaps(gaps, host_events, outside: str = "bench:between"
                   ) -> dict[str, float]:
    """Idle seconds by what the host was doing: each gap is shared among
    the host annotations it overlaps (the innermost wins where they
    nest), and what no annotation covers goes to ``outside``."""
    out: dict[str, float] = {}
    for gap in gaps:
        left = [gap]
        # innermost first: shorter annotations claim their part before
        # the ones that hold them
        for h in sorted(host_events, key=lambda ev: ev.seconds):
            if h.end <= gap[0] or h.start >= gap[1]:
                continue
            claimed = clip(left, h.start, h.end)
            if claimed:
                out[h.name] = out.get(h.name, 0.0) + total(claimed)
                left = subtract(left, [(h.start, h.end)])
        if left:
            out[outside] = out.get(outside, 0.0) + total(left)
    return out


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def mean_over_devices(trace: Trace, per_device) -> float | None:
    """``per_device(operations, in_flight)`` averaged over the chips in
    the trace; None when the trace holds no device plane."""
    if not trace.devices:
        return None
    values = [per_device(ev, trace.in_flight.get(chip, []))
              for chip, ev in trace.devices.items()]
    return sum(values) / len(values)
