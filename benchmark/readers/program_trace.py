"""Readers of what the program names from inside: the phases of the
compiled step (``jax.named_scope``s of
``stochastic_gradient_push_tpu/telemetry/names.py``) and its Pallas
kernels (``pallas_call(name=)``).  Each returns milliseconds a traced
step, mean over the chips, or nothing when the run took no trace, the
trace shows no chip, or no operation carries the name (a program from
before the names).

Where the scope is found (looked at on the chip first, PR 26): the device
plane's events are named by their HLO instruction without its metadata,
and ``jax.profiler.ProfileData`` shows an event's own statistics only.
The operation's ``op_name`` (``jit(sgp_train_step)/transpose(jvp(
sgp.forward))/ResNet/…/conv_general_dilated``) is the ``tf_op`` statistic
of the event's *metadata* in the XPlane, which ``ProfileData`` does not
expose; ``op_names`` below reads it from the file with a protobuf reader
of its own (a few fields of four messages), once a run.

A fusion counts whole under the scope its instruction is named for.
Operations the compiler adds carry no name at all (layout copies,
prefetches and their waits, slices of a parameter): they are the unscoped
time, beside anything a refactor leaves outside the scopes.
"""

from __future__ import annotations

import os
import re
import time

from benchmark import harness
from benchmark import trace_reduce as tr

SCOPE = re.compile(
    r"sgp\.(pre_step|forward|reduce_grads|optimizer|gossip|health)")
# the phases the metrics report; pre_step, reduce_grads and gossip are one
PHASE_OF_SCOPE = {"pre_step": "gossip", "reduce_grads": "gossip",
                  "gossip": "gossip", "optimizer": "optimizer",
                  "health": "health"}
UNSCOPED = "unscoped"
OP_NAME_STAT = "tf_op"      # the XPlane's name for an operation's op_name
HEAVIEST = 5


def phase_of(op_name: str) -> str:
    """``fwd``, ``bwd``, ``optimizer``, ``gossip``, ``health`` or
    ``unscoped`` for one operation's ``op_name``.  The outermost scope
    decides; a forward scope inside a ``transpose(…)`` is the backward
    pass (recomputation under remat included)."""
    m = SCOPE.search(op_name)
    if m is None:
        return UNSCOPED
    if m.group(1) != "forward":
        return PHASE_OF_SCOPE[m.group(1)]
    component = op_name[op_name.rfind("/", 0, m.start()) + 1:m.start()]
    return "bwd" if "transpose(" in component else "fwd"


# -- the XPlane's event metadata, which ProfileData does not show -----------

def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        else:
            if kind == 2:
                size, at = _varint(buf, at)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {kind}")
            value, at = buf[at:at + size], at + size
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane, field: int):
    """Values of a ``map<int64, Message>`` field of a message."""
    for number, entry in _fields(plane):
        if number == field:
            for k, v in _fields(entry):
                if k == 2:
                    yield v


def op_names(xplane_path: str) -> dict[str, str]:
    """HLO instruction (an event's name on a device plane) -> its
    ``op_name``, from the device planes' event metadata.  Field numbers
    are those of tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name = next((_text(v) for k, v in _fields(plane) if k == 2), "")
        if not tr.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for meta in _map_entries(plane, 5):
            found = dict(_fields(meta))
            stat_names[found.get(1, 0)] = _text(found.get(2, b""))
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        for meta in _map_entries(plane, 4):
            instruction = value = None
            for k, v in _fields(meta):
                if k == 2:
                    instruction = _text(v)
                elif k == 5:
                    found = dict(_fields(v))
                    if found.get(1) in wanted:
                        value = (_text(found[5]) if 5 in found
                                 else stat_names.get(found.get(7), ""))
            if instruction and value:
                out[instruction] = value
    return out


# -- the reduction ----------------------------------------------------------

def self_seconds(events) -> dict[str, float]:
    """``trace_reduce.self_seconds`` keyed by the whole instruction, which
    is what ``op_names`` is keyed by."""
    return tr.self_seconds(events, key=lambda e: e.detail or e.name)


def instruction_seconds(trace: tr.Trace, window) -> list[dict[str, float]]:
    """Device self seconds by instruction over ``window``, one table a
    chip; reduced once a trace and window, and kept on the trace."""
    kept = getattr(trace, "program_self_seconds", None)
    if kept is None or kept[0] != window:
        kept = trace.program_self_seconds = (window, [
            self_seconds([e for e in events
                          if e.end > window[0] and e.start < window[1]])
            for events in trace.devices.values()])
    return kept[1]


def phase_seconds(trace: tr.Trace, window, names: dict[str, str]):
    """Device self seconds by phase over ``window``, mean over the chips,
    and each phase's heaviest operations; ``None`` when no operation
    carries a scope."""
    chips = len(trace.devices)
    phases: dict[str, float] = {}
    ops: dict[str, dict[str, float]] = {}
    for table in instruction_seconds(trace, window):
        for instruction, seconds in table.items():
            phase = phase_of(names.get(instruction, ""))
            phases[phase] = phases.get(phase, 0.0) + seconds / chips
            by_name = ops.setdefault(phase, {})
            name = tr.label(instruction)
            by_name[name] = by_name.get(name, 0.0) + seconds / chips
    if not set(phases) - {UNSCOPED}:
        return None
    return phases, {p: tr.top(t, HEAVIEST) for p, t in ops.items()}


def scope_seconds(trace: tr.Trace, window, names: dict[str, str],
                  pattern: str) -> float | None:
    """Device self seconds over ``window``, mean over the chips, of the
    operations whose ``op_name`` matches ``pattern``: a
    ``jax.named_scope`` wherever it stands in the path, forward
    (``jvp(scope)``) and transposed alike.  ``None`` when no operation of
    the window matches."""
    rx = re.compile(pattern)
    found = [seconds for table in instruction_seconds(trace, window)
             for instruction, seconds in table.items()
             if rx.search(names.get(instruction, ""))]
    return sum(found) / len(trace.devices) if found else None


def _op_names(reading) -> dict[str, str]:
    """The run's instruction -> ``op_name`` table, read once from the
    cell's own trace and kept on it."""
    trace = reading.trace
    if not hasattr(trace, "program_op_names"):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        try:
            trace.program_op_names = op_names(tr.find_xplane(os.path.join(
                root, harness.OUT_DIR, "trace", reading.cell.name)))
        except (OSError, ValueError, IndexError):
            trace.program_op_names = {}
    return trace.program_op_names


def _phases(reading):
    """The run's phase table, reduced once and kept on its trace."""
    trace = reading.trace
    if trace is None or not trace.devices or not reading.traced_steps:
        return None
    if not hasattr(trace, "program_phases"):
        clock = time.perf_counter()
        names = _op_names(reading)
        found = phase_seconds(trace, reading.window, names)
        trace.program_phases = found[0] if found else None
        cell, steps = reading.cell.name, reading.traced_steps
        for phase, seconds in sorted(found[0].items()) if found else ():
            print(f"{cell}: phase {phase}: "
                  f"{seconds * 1e3 / steps:9.3f} ms/step; heaviest "
                  + "; ".join(f"{s * 1e3 / steps:.3f} {n}"
                              for n, s in found[1][phase]))
        print(f"{cell}: program_trace: {len(names)} operations named, "
              f"reduced in {time.perf_counter() - clock:.2f} s")
    return trace.program_phases


def phase_ms(reading):
    """Device self time of the operations in phase ``params.phase``."""
    phases = _phases(reading)
    if phases is None:
        return None
    return phases.get(reading.params["phase"], 0.0) * 1e3 \
        / reading.traced_steps


def scope_ms(reading):
    """Device self time of the operations under the scope
    ``params.pattern`` names (a regular expression on ``op_name``): a new
    ``jax.named_scope`` of the program becomes a per-layer metric by a
    ``layer_metrics/*.json`` file alone."""
    trace = reading.trace
    if trace is None or not trace.devices or not reading.traced_steps:
        return None
    seconds = scope_seconds(trace, reading.window, _op_names(reading),
                            reading.params["pattern"])
    return None if seconds is None else \
        seconds * 1e3 / reading.traced_steps


def kernel_ms(reading):
    """Device time of the custom calls whose instruction the program names
    ``params.pattern``; nothing where no call carries the name."""
    if reading.trace is None or not reading.traced_steps:
        return None
    seconds = tr.mean_over_devices(
        reading.trace, lambda events, _: tr.matching_seconds(
            events, reading.params["pattern"], reading.window))
    return seconds * 1e3 / reading.traced_steps if seconds else None
