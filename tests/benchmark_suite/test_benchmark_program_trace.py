"""The readers of the program's own names (benchmark/readers/
program_trace.py and the kernel-name metrics) on hand-made events and a
hand-made XPlane: the phase split, self time under ``while`` /
``conditional``, the three flash kernels by name, several chips averaged,
and nothing read where there is nothing to read."""

import json
import os
import types

import pytest

from bench_toy import REPO
from benchmark import harness, spec
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event

pt = spec.load_plugin(REPO, "readers", "program_trace")
CELL = "gpt2m_sgp_w1_t1024"

JIT = "jit(sgp_lm_train_step)/"
# instruction -> op_name, as the chip's XPlane gives them (tf_op ends in ":")
OPS = {
    "%fusion.1 = bf16[4,8]{1,0} fusion(%p0), kind=kOutput":
        JIT + "jvp(sgp.forward)/TransformerLM/block_0/up/dot_general:",
    "%fusion.2 = bf16[4,8]{1,0} fusion(%p1), kind=kOutput":
        JIT + "transpose(jvp(sgp.forward))/TransformerLM/block_0/up/"
              "dot_general:",
    "%fusion.3 = f32[8]{0} fusion(%p2), kind=kLoop":
        JIT + "sgp.optimizer/sub:",
    "%divide.4 = f32[8]{0} divide(%p3, %w)": JIT + "sgp.pre_step/div:",
    "%conditional.5 = (f32[8]{0}) conditional(%i, %a, %b)":
        JIT + "shard_map/sgp.gossip/cond:",
    "%collective-permute-done.6 = f32[8]{0} collective-permute-done(%s)":
        JIT + "shard_map/sgp.gossip/cond/branch_1_fun/ppermute:",
    "%multiply.7 = s8[8]{0} multiply(%m, %s)":
        JIT + "shard_map/sgp.gossip/cond/branch_1_fun/sgp.gossip.wire/mul:",
    "%reduce.8 = f32[]{:T(128)} reduce(%g, %z)":
        JIT + "sgp.health/reduce_sum:",
    "%add.9 = f32[8]{0} add(%g0, %g1)": JIT + "sgp.reduce_grads/add:",
    "%copy-done.10 = f32[8]{0} copy-done(%copy-start.10)": "",
    "%remat.11 = bf16[4,8]{1,0} fusion(%p4), kind=kLoop":
        JIT + "transpose(jvp(sgp.forward))/checkpoint/"
              "rematted_computation/TransformerLM/block_0/up/tanh:",
}
(FWD, BWD, OPT, PRE, COND, PERMUTE, WIRE, HEALTH, REDUCE, COPY,
 REMAT) = OPS


def _event(instruction, start, end):
    return Event(tr.short_name(instruction), start, end, instruction)


# one chip, one 20 s step: forward 0-3, the conditional 10-16 holds the
# permute 11-13 and the codec 13-14 (so 3 s are its own), idle 9-10
CHIP0 = [
    _event(PRE, 0.0, 0.5), _event(FWD, 0.5, 3.0), _event(BWD, 3.0, 7.0),
    _event(REMAT, 7.0, 8.0), _event(REDUCE, 8.0, 8.25),
    _event(OPT, 8.25, 9.0),
    _event(COND, 10.0, 16.0), _event(PERMUTE, 11.0, 13.0),
    _event(WIRE, 13.0, 14.0),
    _event(HEALTH, 16.0, 16.5), _event(COPY, 16.5, 18.0),
]
EXPECTED = {"fwd": 2.5, "bwd": 5.0, "optimizer": 0.75,
            "gossip": 0.5 + 0.25 + 6.0, "health": 0.5, "unscoped": 1.5}
WINDOW = (0.0, 20.0)
STEPS = [Event(tr.STEP_NAME, 0.0, 20.0)]


@pytest.mark.parametrize("op_name,phase", [
    (OPS[FWD], "fwd"), (OPS[BWD], "bwd"), (OPS[REMAT], "bwd"),
    (OPS[OPT], "optimizer"), (OPS[PRE], "gossip"), (OPS[REDUCE], "gossip"),
    (OPS[COND], "gossip"), (OPS[WIRE], "gossip"), (OPS[HEALTH], "health"),
    ("", "unscoped"), ("jit(sgp_train_step)/squeeze:", "unscoped"),
    # the outermost scope decides: a launch under overlap is pre_step's
    (JIT + "sgp.pre_step/sgp.gossip.wire/mul:", "gossip"),
    # a transpose elsewhere in the path does not make a forward op backward
    (JIT + "jvp(sgp.forward)/attn/transpose:", "fwd"),
], ids=lambda v: v[-40:] if "/" in v else v or "none")
def test_an_op_name_falls_in_one_phase(op_name, phase):
    assert pt.phase_of(op_name) == phase


def test_phases_split_the_busy_time_with_nested_self_time():
    trace = tr.Trace({0: CHIP0}, [], STEPS)
    phases, heaviest = pt.phase_seconds(trace, WINDOW, OPS)
    assert phases == pytest.approx(EXPECTED)
    # the parts add up to the device's busy time: nothing lost or doubled
    assert sum(phases.values()) == pytest.approx(
        tr.busy_seconds(CHIP0, WINDOW))
    # a reader of the run sees what fell where: the conditional's own 3 s
    # and the permute's 2 lead the gossip phase
    assert [n.split(" ")[0] for n, _ in heaviest["gossip"][:2]] == [
        "conditional.5", "collective-permute-done.6"]
    assert len(heaviest["gossip"]) <= pt.HEAVIEST


def test_back_to_back_operations_are_not_taken_for_nested():
    """An event's end is start + duration in floating point: 0.1 + 0.2
    reads a hair after 0.3, where the next operation starts."""
    events = [_event(FWD, 0.1, 0.1 + 0.2), _event(BWD, 0.3, 0.5)]
    assert events[0].end > events[1].start          # the hair
    got = pt.self_seconds(events)
    assert got[FWD] == pytest.approx(0.2) and got[BWD] == pytest.approx(0.2)


def test_several_chips_are_averaged():
    slow = [_event(e.detail, e.start, e.end + (1.0 if e.detail == COPY
                                               else 0.0)) for e in CHIP0]
    trace = tr.Trace({0: CHIP0, 1: slow}, [], STEPS)
    phases, _ = pt.phase_seconds(trace, WINDOW, OPS)
    assert phases["unscoped"] == pytest.approx(2.0)     # (1.5 + 2.5) / 2
    assert phases["fwd"] == pytest.approx(EXPECTED["fwd"])


def test_a_program_without_scopes_reads_nothing():
    trace = tr.Trace({0: CHIP0}, [], STEPS)
    assert pt.phase_seconds(trace, WINDOW, {}) is None
    unnamed = {k: "jit(sharded)/jit(main)/mul:" for k in OPS}
    assert pt.phase_seconds(trace, WINDOW, unnamed) is None


# -- a hand-made XPlane -------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xplane(plane_name: str, ops: dict[str, str]) -> bytes:
    """An XSpace with one plane whose event metadata carry ``tf_op``:
    every second one as a string, the others as a reference to a stat
    metadata's name (the profiler uses both)."""
    stats = {1: "device_offset_ps", 26: "tf_op", 24: "hlo_category"}
    plane = _field(1, 7) + _field(2, plane_name)
    metadata = b""
    for at, (instruction, op_name) in enumerate(ops.items()):
        stat = _field(1, 24) + _field(5, "fusion")
        if op_name and at % 2:
            stats[100 + at] = op_name
            tf_op = _field(1, 26) + _field(7, 100 + at)
        elif op_name:
            tf_op = _field(1, 26) + _field(5, op_name)
        else:
            tf_op = b""
        meta = (_field(1, at + 1) + _field(2, instruction)
                + _field(4, tr.short_name(instruction)) + _field(5, stat)
                + (_field(5, tf_op) if tf_op else b""))
        metadata += _field(4, _field(1, at + 1) + _field(2, meta))
    for number, name in stats.items():
        plane += _field(5, _field(1, number) + _field(
            2, _field(1, number) + _field(2, name)))
    # a line with one event, to be skipped over: id, name, one XEvent
    plane += _field(3, _field(1, 1) + _field(2, "XLA Ops")
                    + _field(4, _field(1, 1) + _field(2, 5) + _field(3, 9)))
    return _field(1, plane + metadata)


def _write_trace(root, cell, planes: bytes) -> str:
    where = os.path.join(root, harness.OUT_DIR, "trace", cell, "plugins",
                         "profile", "2026_01_01")
    os.makedirs(where)
    path = os.path.join(where, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(planes)
    return path


def test_op_names_come_from_the_device_planes_event_metadata(tmp_path):
    path = _write_trace(
        str(tmp_path), CELL,
        _xplane("/host:CPU", {"%fusion.99 = f32[] fusion()": "host/op:"})
        + _xplane("/device:TPU:0", OPS))
    assert pt.op_names(path) == {k: v for k, v in OPS.items() if v}


def _reading(root, trace, phase):
    return types.SimpleNamespace(
        trace=trace, window=WINDOW, params={"phase": phase},
        traced_steps=len(trace.steps) if trace is not None else 0,
        cell=types.SimpleNamespace(name=CELL))


def test_phase_ms_reads_the_cells_own_trace_once(tmp_path, capsys):
    """Through the files a run leaves: the reader module beside a
    ``.bench_out`` of its own (the toy root), two steps, one chip."""
    import shutil

    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = _write_trace(root, CELL, _xplane("/device:TPU:0", OPS))
    reader = spec.load_plugin(root, "readers", "program_trace")
    trace = tr.Trace({0: CHIP0}, [], [Event(tr.STEP_NAME, 0.0, 10.0),
                                      Event(tr.STEP_NAME, 10.0, 20.0)])
    got = {p: reader.phase_ms(_reading(root, trace, p)) for p in EXPECTED}
    assert got == pytest.approx({p: s * 1e3 / 2
                                 for p, s in EXPECTED.items()})
    assert capsys.readouterr().out.count("phase gossip:") == 1
    # reduced once: the file is not needed again
    os.remove(path)
    again = spec.load_plugin(root, "readers", "program_trace")
    assert again.phase_ms(_reading(root, trace, "fwd")) == got["fwd"]


@pytest.mark.parametrize("trace", [
    None, tr.Trace({}, [], STEPS), tr.Trace({0: CHIP0}, [], []),
    tr.Trace({0: CHIP0}, [], STEPS)],
    ids=["no-trace", "no-chip", "no-step", "no-xplane-to-name-the-ops"])
def test_nothing_to_read_is_none_and_never_raises(tmp_path, trace):
    assert pt.phase_ms(_reading(str(tmp_path), trace, "fwd")) is None


def _kernel_metric(name):
    with open(spec.data_path(REPO, "layer_metrics", name + "_ms")) as f:
        return json.load(f)


# the pair (a shard beyond 8192 tokens still runs it) has no metric since
# PR 28: its pattern is given here, so that the pattern reader stays tested
PAIR = {"reader": "program_trace:kernel_ms",
        "params": {"pattern": r"^flash_d(q|kv)(\.\d+)?$"}}


@pytest.mark.parametrize("kernel,metric,ms", [
    ("flash_fwd", None, 2e3), ("flash_bwd", None, 1.5e3),
    ("flash_dq+flash_dkv", PAIR, 7e3),
    # every flash kernel, and no other kernel of the step
    ("flash", None, 2e3 + 7e3 + 1.5e3),
    ("delta_kernel", None, 3e3), ("ssd_kernel", None, 1.5e3)],
    ids=["flash_fwd", "flash_bwd", "the-pair-inline", "flash_ms",
         "delta_kernel_ms", "ssd_kernel_ms"])
def test_the_three_flash_kernels_are_told_apart_by_name(kernel, metric, ms):
    """The metrics' own files give the kernel reader a pattern on the
    custom call's name: each kernel's calls and no other, and nothing
    (not zero) for a program whose kernels carry no name yet. The step may
    hold other kernels beside the flash ones (the delta rule's, the scan's,
    the experts')."""
    metric = metric or _kernel_metric(kernel)
    assert metric["reader"] == "program_trace:kernel_ms"
    call = ('%{0} = bf16[64,1024,64]{{2,1,0}} custom-call(%q), '
            'custom_call_target="tpu_custom_call"')
    events = [
        _event(call.format("flash_fwd.24"), 0.0, 1.0),
        _event(call.format("flash_fwd"), 1.0, 2.0),
        _event(call.format("flash_dq.3"), 2.0, 5.0),
        _event(call.format("flash_dkv.3"), 5.0, 9.0),
        _event(call.format("attn.7"), 9.0, 10.0),
        _event(call.format("flash_fwd_tail.1"), 10.0, 11.0),
        _event(call.format("flash_bwd.31"), 11.0, 12.5),
        _event(call.format("delta_fwd.4"), 12.5, 13.5),
        _event(call.format("delta_bwd"), 13.5, 15.5),
        _event(call.format("ssd_fwd.2"), 15.5, 16.0),
        _event(call.format("ssd_bwd.9"), 16.0, 17.0),
        _event(call.format("grouped_matmul.5"), 17.0, 18.0),
    ]
    reading = types.SimpleNamespace(
        trace=tr.Trace({0: events}, [], STEPS), traced_steps=1,
        window=WINDOW, params=metric["params"])
    assert spec.load_reader(REPO, metric)(reading) == pytest.approx(ms)
    reading.trace = tr.Trace(
        {0: [_event(call.format("attn.7"), 0.0, 1.0)]}, [], STEPS)
    assert spec.load_reader(REPO, metric)(reading) is None
    reading.trace = None
    assert spec.load_reader(REPO, metric)(reading) is None


# -- a scope of the program's own, by a metric file alone ---------------

def _scope_reading(root, trace, pattern):
    return types.SimpleNamespace(
        trace=trace, window=WINDOW, params={"pattern": pattern},
        traced_steps=len(trace.steps) if trace is not None else 0,
        cell=types.SimpleNamespace(name=CELL))


@pytest.mark.parametrize("pattern,seconds", [
    # forward and transposed alike: FWD 2.5 + BWD 4 + REMAT 1
    (r"sgp\.forward", 7.5),
    # a scope inside a scope, wherever it stands in the path
    (r"/block_0/up/", 7.5), (r"sgp\.gossip\.wire", 1.0),
    # the conditional's own 3 s, the permute's 2, the codec's 1
    (r"sgp\.gossip(/|$)", 6.0),
    (r"sgp\.(pre_step|reduce_grads)", 0.75),
    (r"rematted_computation", 1.0)])
def test_scope_seconds_sums_self_time_under_a_pattern(pattern, seconds):
    trace = tr.Trace({0: CHIP0}, [], STEPS)
    assert pt.scope_seconds(trace, WINDOW, OPS, pattern) == \
        pytest.approx(seconds)


def test_scope_seconds_is_a_mean_over_chips_and_none_where_nothing_matches():
    trace = tr.Trace({0: CHIP0, 1: [e for e in CHIP0 if e.detail != REMAT]},
                     [], STEPS)
    assert pt.scope_seconds(trace, WINDOW, OPS, "rematted_computation") == \
        pytest.approx(0.5)                               # (1.0 + 0.0) / 2
    assert pt.scope_seconds(trace, WINDOW, OPS, r"sgp\.forward") == \
        pytest.approx(7.0)                               # (7.5 + 6.5) / 2
    assert pt.scope_seconds(trace, WINDOW, OPS, r"sgp\.scan") is None
    assert pt.scope_seconds(trace, WINDOW, {}, r"sgp\.forward") is None
    # an operation the window leaves out is not read
    assert pt.scope_seconds(trace, (0.0, 8.0), OPS, r"sgp\.health") is None


def test_scope_ms_reads_the_cells_own_trace_beside_phase_ms(tmp_path):
    """A metric file alone: the reader, the pattern, and the trace the run
    left; it shares the trace's names and self times with ``phase_ms``."""
    import shutil

    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    _write_trace(root, CELL, _xplane("/device:TPU:0", OPS))
    metric = {"reader": "program_trace:scope_ms",
              "params": {"pattern": r"sgp\.forward"}}
    reader = spec.load_reader(root, metric)
    trace = tr.Trace({0: CHIP0}, [], [Event(tr.STEP_NAME, 0.0, 10.0),
                                      Event(tr.STEP_NAME, 10.0, 20.0)])
    reading = _scope_reading(root, trace, metric["params"]["pattern"])
    assert reader(reading) == pytest.approx(7.5e3 / 2)
    # what the pattern reads is the forward and the backward phase together
    reading.params = {"phase": "fwd"}
    fwd = pt.phase_ms(reading)
    reading.params = {"phase": "bwd"}
    assert fwd + pt.phase_ms(reading) == pytest.approx(7.5e3 / 2)


@pytest.mark.parametrize("trace", [
    None, tr.Trace({}, [], STEPS), tr.Trace({0: CHIP0}, [], []),
    tr.Trace({0: CHIP0}, [], STEPS)],
    ids=["no-trace", "no-chip", "no-step", "no-xplane-to-name-the-ops"])
def test_scope_ms_with_nothing_to_read_is_none(tmp_path, trace):
    assert pt.scope_ms(_scope_reading(str(tmp_path), trace,
                                      r"sgp\.forward")) is None
