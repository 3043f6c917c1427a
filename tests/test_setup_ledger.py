"""The set-up ledger (telemetry/setup_ledger.py): JAX's build events and
the program's ``setup_phase`` spans on one clock, totals as unions, the cut
at the first train step, nothing heard between builds, and what an entry
point with ``--trace_dir`` leaves behind."""

import json
import os
import re
import types

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

import stochastic_gradient_push_tpu as package
from stochastic_gradient_push_tpu.telemetry import (
    EVENTS_FILE, NULL_TELEMETRY, TRACE_FILE, make_run_telemetry, names,
    setup_ledger, setup_phase)
from stochastic_gradient_push_tpu.telemetry.setup_ledger import (
    LEDGER, SetupLedger, setup_line)

STEP = names.MODULE_LM_TRAIN_STEP


@pytest.fixture
def ledger():
    """The process's ledger, armed and empty; left as it was found."""
    was_armed = LEDGER.armed
    setup_ledger.arm()
    LEDGER.reset()
    yield LEDGER
    if not was_armed:
        setup_ledger.disarm()
    LEDGER.reset()


def _build(led, name, at, trace=1.0, lower=1.0, backend=1.0, cache=None):
    """One program's events, back to back from ``at``; returns its end."""
    led.on_time_span(names.JAX_TRACE_EVENT, at, at + trace, fun_name=name)
    at += trace
    led.on_time_span(names.JAX_LOWER_EVENT, at, at + lower,
                     fun_name=f"jit({name})")
    at += lower
    if cache is not None:
        led.on_event(cache)
    led.on_time_span(names.JAX_BACKEND_EVENT, at, at + backend,
                     fun_name=f"jit({name})")
    return at + backend


def _synthetic(clock=100.0):
    led = SetupLedger(clock=lambda: clock)
    led.armed = True
    led.reset()
    return led


# -- unions, not sums ------------------------------------------------------


def test_nested_traces_are_counted_once():
    led = _synthetic()
    # two kernel wrappers traced inside the step's trace, one of them twice
    led.on_time_span(names.JAX_TRACE_EVENT, 101.0, 102.0, fun_name="kernel")
    led.on_time_span(names.JAX_TRACE_EVENT, 102.5, 103.0, fun_name="kernel")
    led.on_time_span(names.JAX_TRACE_EVENT, 100.5, 104.0, fun_name=STEP)
    led.on_time_span(names.JAX_LOWER_EVENT, 104.0, 106.0,
                     fun_name=f"jit({STEP})")
    led.on_time_span(names.JAX_BACKEND_EVENT, 106.0, 107.0,
                     fun_name=f"jit({STEP})")
    s = led.summary()
    assert s["trace_lower_s"] == 5.5            # the sum would be 7.0
    assert s["step_program"]["trace_s"] == 3.5  # its own trace, not a kernel's
    assert s["step_program"]["seconds"] == 6.5
    assert s["total_s"] == 7.0 and s["covered_s"] == 6.5
    assert s["other_s"] == 0.5 and s["overlap_s"] == 0.0


def test_a_real_two_level_jit_gives_a_union(ledger):
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    def outer(x):
        return inner(x) + inner(x * 2.0)

    jax.block_until_ready(jax.jit(outer)(jnp.ones((8,))))
    traces = [s for s in ledger.spans if s[0] == "trace"]
    assert {"outer", "inner"} <= {s[1] for s in traces}
    out = next(s for s in traces if s[1] == "outer")
    assert all(out[2] <= s[2] and s[3] <= out[3]
               for s in traces if s[1] == "inner")
    s = ledger.summary()
    by_sum = sum(e - b for kind, _, b, e in ledger.spans
                 if kind in ("trace", "lower"))
    assert 0 < s["trace_lower_s"] < by_sum
    row = next(r for r in s["rows"] if r["fun_name"] == "outer")
    assert row["trace_s"] == out[3] - out[2] and row["backend_s"] > 0
    assert s["covered_s"] <= s["total_s"]


def test_a_compile_inside_a_trace_is_the_overlap_the_summary_states():
    led = _synthetic()
    # an eager helper built while the step was being traced
    led.on_time_span(names.JAX_TRACE_EVENT, 101.0, 101.5, fun_name="iota")
    led.on_time_span(names.JAX_BACKEND_EVENT, 101.5, 102.0,
                     fun_name="jit(iota)")
    led.on_time_span(names.JAX_TRACE_EVENT, 100.0, 103.0, fun_name=STEP)
    led.on_time_span(names.JAX_BACKEND_EVENT, 103.0, 104.0,
                     fun_name=f"jit({STEP})")
    s = led.summary()
    assert (s["trace_lower_s"], s["compile_s"]) == (3.0, 1.5)
    assert s["overlap_s"] == 0.5 and s["covered_s"] == 4.0
    assert s["programs"] == 2


# -- the cache's verdict lands on the right row ------------------------------


def test_hit_miss_and_uncached_attach_to_the_row_that_closes_next(ledger):
    """JAX's own events, recorded the way ``compiler.py`` records them:
    inside the backend interval, with no name."""
    record = jax.monitoring

    def build(name, *cache_events):
        for event, args in (
                (names.JAX_TRACE_EVENT, (1.0, 2.0)),
                (names.JAX_LOWER_EVENT, (2.0, 3.0))):
            record.record_event_time_span(
                event, *args,
                fun_name=name if event == names.JAX_TRACE_EVENT
                else f"jit({name})")
        for e in cache_events:
            if isinstance(e, tuple):
                record.record_event_duration_secs(*e)
            else:
                record.record_event(e)
        record.record_event_time_span(names.JAX_BACKEND_EVENT, 3.0, 4.0,
                                      fun_name=f"jit({name})")

    build("tiny")
    build("held", names.JAX_CACHE_HIT_EVENT,
          (names.JAX_CACHE_SAVED_EVENT, 41.0),
          (names.JAX_CACHE_RETRIEVAL_EVENT, 0.75))
    build("tiny2")
    build("written", names.JAX_CACHE_MISS_EVENT)
    rows = {r["fun_name"]: r for r in ledger.summary()["rows"]}
    assert [rows[n]["cache"] for n in ("tiny", "held", "tiny2", "written")] \
        == ["uncached", "hit", "uncached", "miss"]
    assert rows["held"]["retrieval_s"] == 0.75
    assert rows["held"]["saved_s"] == 41.0
    assert rows["written"]["retrieval_s"] == 0.0
    s = ledger.summary()
    assert (s["cache_hits"], s["cache_misses"], s["uncached"]) == (1, 1, 2)


def test_the_persistent_cache_on_the_cpu_reads_miss_then_hit(
        ledger, tmp_path):
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        compilation_cache.reset_cache()
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0.0)
        jax.config.update(keys[2], -1)

        def cached_program(x):
            return jnp.cos(x) + 3.0

        x = jnp.ones((16,))
        jax.block_until_ready(x)
        ledger.reset()
        jax.block_until_ready(jax.jit(cached_program)(x))
        jax.config.update(keys[1], 1e9)     # under the threshold: not asked
        jax.block_until_ready(jax.jit(lambda x: x - 7.0)(x))
        jax.config.update(keys[1], 0.0)
        jax.clear_caches()
        jax.block_until_ready(jax.jit(cached_program)(x))
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    rows = [(r["fun_name"], r["cache"], r["build"])
            for r in ledger.summary()["rows"]]
    assert ("cached_program", "miss", 1) in rows
    assert ("<lambda>", "uncached", 1) in rows
    assert ("cached_program", "hit", 2) in rows
    hit = next(r for r in ledger.summary()["rows"] if r["cache"] == "hit")
    assert hit["retrieval_s"] > 0


# -- the cut -----------------------------------------------------------------


@pytest.mark.parametrize("module", names.STEP_MODULES)
def test_the_cut_falls_at_the_first_train_step(module):
    led = _synthetic()
    at = _build(led, "init", 101.0, cache=names.JAX_CACHE_HIT_EVENT)
    at = _build(led, "batches", at)
    led.phase("state_init", 100.5, at)
    end = _build(led, module, at + 1.0, cache=names.JAX_CACHE_MISS_EVENT)
    # built after set-up: the comparison's program, and the step again
    _build(led, "both", end + 30.0, backend=5.0)
    _build(led, module, end + 40.0, trace=0.0)
    s = led.summary()
    assert led.cut == 2 and s["step_program"]["fun_name"] == module
    assert s["programs"] == 3
    assert [r["fun_name"] for r in s["later_rows"]] == ["both", module]
    assert s["later_rows"][1]["build"] == 2
    assert s["total_s"] == end - 100.0
    # nothing of the later rows in the totals
    assert s["trace_lower_s"] == 6.0
    assert (s["compile_s"], s["cache_load_s"]) == (2.0, 1.0)
    assert (s["cache_hits"], s["cache_misses"], s["uncached"]) == (1, 1, 1)
    # phases and builds overlap by design: the union says how far
    assert s["covered_s"] == 9.5 and s["phases_outside_builds_s"] == 0.5
    assert s["phases_s"] == {"state_init": 6.5}
    line = setup_line(s)
    assert line.startswith(f"set-up: {s['total_s']:.1f} s = trace+lower 6.0")
    assert "3 programs, 1 cache misses" in line
    assert f"step program {module} 3.0 s (trace 1.0, lower 1.0, compile" \
        in line


def test_without_a_train_step_the_totals_run_to_the_report():
    led = _synthetic()
    _build(led, "pp_step", 101.0)
    assert led.cut is None and not led.closed
    lines = []
    led._clock = lambda: 110.0
    s = led.report(types.SimpleNamespace(info=lines.append))
    assert led.closed and s["programs"] == 1 and s["step_program"] is None
    assert s["total_s"] == 10.0 and lines == [setup_line(s)]
    assert led.report(None) is None             # once a process


# -- armed once, silent between builds ---------------------------------------


def _registered():
    from jax._src import monitoring

    return (monitoring.get_event_time_span_listeners().count(
                LEDGER.on_time_span),
            monitoring.get_event_listeners().count(LEDGER.on_event),
            monitoring.get_event_duration_listeners().count(
                LEDGER.on_duration))


def test_arming_twice_registers_once(ledger):
    from stochastic_gradient_push_tpu.utils.compile_cache import (
        place_compile_cache)

    setup_ledger.arm()
    before = jax.config.jax_compilation_cache_dir
    try:
        place_compile_cache()       # the entry points' call arms too
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert _registered() == (1, 1, 1)
    setup_ledger.disarm()
    assert _registered() == (0, 0, 0) and not LEDGER.armed
    setup_ledger.disarm()           # and twice is as quiet


def test_fifty_warm_steps_call_no_listener(ledger):
    calls = []

    def heard(*args, **kwargs):
        calls.append(args[0])

    registrations = (
        (jax.monitoring.register_event_time_span_listener,
         jax.monitoring.unregister_event_time_span_listener),
        (jax.monitoring.register_event_listener,
         jax.monitoring.unregister_event_listener),
        (jax.monitoring.register_event_duration_secs_listener,
         jax.monitoring.unregister_event_duration_listener),
        (jax.monitoring.register_scalar_listener,
         jax.monitoring.unregister_scalar_listener))

    def step(state, x):
        return state * 0.9 + x.sum(), {"loss": (state * state).mean()}

    step.__name__ = names.MODULE_TRAIN_STEP
    step = jax.jit(step, donate_argnums=0)
    state, x = jnp.ones((32,)), jnp.ones((4, 32))
    for register, _ in registrations:
        register(heard)
    try:
        for _ in range(3):
            state, metrics = step(state, x)
            jax.block_until_ready(state)
            float(metrics["loss"])
        assert names.JAX_BACKEND_EVENT in calls and ledger.cut is not None
        rows = len(ledger.rows)
        del calls[:]
        for _ in range(50):
            state, metrics = step(state, x)
            jax.block_until_ready(state)
            float(metrics["loss"])
        assert calls == [] and len(ledger.rows) == rows
    finally:
        for _, unregister in registrations:
            unregister(heard)


# -- setup_phase: one call, three sinks --------------------------------------


def test_setup_phase_hands_ledger_and_tracer_the_same_two_timestamps(
        ledger, tmp_path):
    with setup_phase("parse"):      # before the run's telemetry exists
        pass
    rt = make_run_telemetry(str(tmp_path))
    try:
        with setup_phase("model"):
            pass
        in_ledger = [(n, s, e - s) for kind, n, s, e in ledger.spans
                     if kind == "phase"]
        in_tracer = [(n, s, d) for n, phase, s, d, _ in rt.tracer._events
                     if phase == "setup"]
        assert [n for n, _, _ in in_ledger] == ["parse", "model"]
        assert in_tracer == in_ledger
    finally:
        rt.finish()
    with open(tmp_path / TRACE_FILE) as f:
        on_track = [e["name"] for e in json.load(f)["traceEvents"]
                    if e.get("cat") == "setup"]
    assert on_track == ["parse", "model"]
    # a finished run hears no more
    with setup_phase("data"):
        pass
    assert len(rt.tracer) == 2 and ledger.spans[-1][1] == "data"


def test_setup_phase_is_a_plain_span_without_run_telemetry(ledger):
    assert make_run_telemetry(None) is NULL_TELEMETRY
    with setup_phase("mesh") as span:
        assert span is not None
    (kind, name, start, end), = ledger.spans
    assert (kind, name) == ("phase", "mesh") and start <= end
    assert ledger._telemetry is None
    with pytest.raises(ValueError, match="unknown set-up phase"):
        setup_phase("warm_up")


def test_a_capture_holds_the_phase_under_its_sgp_name(ledger, tmp_path):
    from test_trace_names import _host_events

    jax.profiler.start_trace(str(tmp_path))
    try:
        with setup_phase("data"):
            jax.block_until_ready(jnp.ones((4,)) + 1.0)
    finally:
        jax.profiler.stop_trace()
    assert any(e[0] == names.SETUP_SPAN_PREFIX + "data"
               for e in _host_events(str(tmp_path)))


def test_every_setup_phase_literal_is_a_name_of_setup_spans():
    root = os.path.dirname(package.__file__)
    literal = re.compile(
        r"""(?:setup_phase|_Phase|__init__)\(\s*["']([^"']+)["']""")
    used = {}
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    for phase in literal.findall(f.read()):
                        used.setdefault(phase, []).append(
                            os.path.relpath(path, root))
    assert set(used) == set(names.SETUP_SPANS), used
    # where the work is: the two CLIs and the Trainer
    assert any(p.startswith("run") for p in used["parse"])
    assert "train/loop.py" in used["state_init"]
    assert used["first_step"] == ["telemetry/setup_ledger.py"]


# -- after set-up ------------------------------------------------------------


def test_a_program_built_after_the_report_emits_one_compile_event(
        ledger, tmp_path):
    rt = make_run_telemetry(str(tmp_path))
    said = []
    log = types.SimpleNamespace(info=lambda line: None, warning=said.append)
    try:
        def step(x):
            return x * 2.0

        step.__name__ = STEP
        x8, x9 = jnp.ones((8,)), jnp.ones((9,))
        jax.block_until_ready(jax.jit(step)(x8))
        assert ledger.cut is not None
        assert rt.registry.counts.get("compile") is None    # still set-up
        assert ledger.report(log, rt, step=1) is not None
        assert rt.registry.counts["setup"] == 1
        jax.block_until_ready(jax.jit(lambda x: x + 5.0)(x8))
        assert rt.registry.counts["compile"] == 1
        # the step again, under another shape: a warning
        jax.block_until_ready(jax.jit(step)(x9))
        assert rt.registry.counts["compile"] == 2
        assert len(said) == 1 and said[0].startswith(
            f"{STEP} built again (build 2): ")
    finally:
        rt.finish()
    with open(tmp_path / EVENTS_FILE) as f:
        events = [json.loads(line) for line in f]
    first, again = [e for e in events if e["kind"] == "compile"]
    assert first["severity"] == "info"
    assert first["data"]["fun_name"] == "<lambda>"
    assert first["data"]["seconds"] > 0 and first["data"]["build"] == 1
    assert first["data"]["cache"] in ("hit", "miss", "uncached")
    assert again["severity"] == "warning"
    assert (again["data"]["fun_name"], again["data"]["build"]) == (STEP, 2)
    # bound no longer: a later build is nobody's event
    jax.block_until_ready(jax.jit(lambda x: x - 9.0)(x8))


def test_an_entry_point_leaves_one_setup_event_equal_to_its_line(
        ledger, tmp_path, capfd):
    from stochastic_gradient_push_tpu.run.gossip_lm import main
    from stochastic_gradient_push_tpu.utils import reset_logger

    reset_logger("lm")
    trace_dir = str(tmp_path / "trace")
    before = jax.config.jax_compilation_cache_dir
    try:
        main(["--world_size", "2", "--seq_len", "16", "--d_model", "16",
              "--n_layers", "1", "--n_heads", "2", "--d_ff", "32",
              "--num_steps", "4", "--print_freq", "2",
              "--checkpoint_dir", str(tmp_path / "run"),
              "--trace_dir", trace_dir])
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    printed = capfd.readouterr()
    with open(os.path.join(trace_dir, EVENTS_FILE)) as f:
        events = [json.loads(line) for line in f]
    setup, = [e for e in events if e["kind"] == "setup"]
    data = setup["data"]
    assert setup["step"] == 1 and data["programs"] == len(data["rows"])
    assert data["step_program"]["fun_name"] == STEP
    assert data["rows"][-1] == data["step_program"]
    lines = [line for line in (printed.out + printed.err).splitlines()
             if "set-up: " in line]
    assert len(lines) == 1 and lines[0].endswith(setup_line(data))
    # the parts of the line add up to its total
    assert data["total_s"] == pytest.approx(
        data["trace_lower_s"] + data["compile_s"] + data["cache_load_s"]
        - data["overlap_s"] + data["phases_outside_builds_s"]
        + data["other_s"], abs=1e-5)
    assert data["other_s"] >= 0
    assert set(data["phases_s"]) == set(names.SETUP_SPANS)
    with open(os.path.join(trace_dir, TRACE_FILE)) as f:
        spans = [e["name"] for e in json.load(f)["traceEvents"]
                 if e.get("cat") == "setup"]
    assert set(spans) == set(names.SETUP_SPANS)
