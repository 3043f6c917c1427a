"""The trace vocabulary (telemetry/names.py) where the program writes it:
the compiled steps' scopes and module names, the loops' host spans on the
profiler's clock, and the shared no-op when no capture runs."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.models import TinyMLP
from stochastic_gradient_push_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from stochastic_gradient_push_tpu.parallel import (
    GOSSIP_AXIS, make_gossip_mesh, wire)
from stochastic_gradient_push_tpu.telemetry import names
from stochastic_gradient_push_tpu.telemetry.tracer import _NULL_SPAN
from stochastic_gradient_push_tpu.topology import (
    NPeerDynamicDirectedExponentialGraph, build_schedule)
from stochastic_gradient_push_tpu.train import (
    LRSchedule, build_train_step, init_train_state, replicate_state, sgd,
    shard_scanned_train_step, shard_train_step)
from stochastic_gradient_push_tpu.train.lm import (
    build_lm_train_step, init_lm_state, make_dp_sp_mesh,
    shard_lm_train_step, shard_scanned_lm_step)
from stochastic_gradient_push_tpu.utils import profiling
from stochastic_gradient_push_tpu.utils.profiling import ProfileWindow

WORLD = 2


def _algorithm(codec=None):
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(WORLD, peers_per_itr=1))
    return sgp(sched, GOSSIP_AXIS, wire=codec)


def _image_step(scan: int = 0, codec=None):
    mesh = make_gossip_mesh(WORLD)
    model, alg = TinyMLP(num_classes=4), _algorithm(codec)
    tx = sgd(momentum=0.9, weight_decay=1e-4)
    lrs = LRSchedule(ref_lr=0.1, batch_size=4, world_size=WORLD,
                     decay_schedule={}, warmup=False)
    # two microbatches: push-sum on a flat mesh reduces no gradients, and
    # the microbatch sums are the one thing left under that scope
    step = build_train_step(model, alg, tx, lrs, itr_per_epoch=10,
                            num_classes=4, grad_accum=2,
                            health_axis=GOSSIP_AXIS)
    state = replicate_state(init_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((4, 8, 8, 3)), tx, alg),
        WORLD)
    lead = (scan,) if scan else ()
    x = jnp.zeros(lead + (WORLD, 4, 8, 8, 3))
    y = jnp.zeros(lead + (WORLD, 4), jnp.int32)
    fn = (shard_scanned_train_step(step, mesh, scan) if scan
          else shard_train_step(step, mesh))
    return fn, (state, x, y)


def _lm_step(scan: int = 0):
    mesh = make_dp_sp_mesh(WORLD, 1)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_len=16))
    alg = _algorithm()
    tx = sgd(momentum=0.9, weight_decay=0.0)
    lrs = LRSchedule(ref_lr=0.1, batch_size=2, world_size=WORLD,
                     decay_schedule={}, warmup=False)
    step = build_lm_train_step(model, alg, tx, lrs, itr_per_epoch=10,
                               seq_axis=None, grad_accum=2,
                               health_axis=GOSSIP_AXIS)
    state = init_lm_state(model, mesh, alg, tx, dp=WORLD, sp=1,
                          batch_size=2, block_len=16, seq_axis=None)
    lead = (scan,) if scan else ()
    toks = jnp.zeros(lead + (WORLD, 2, 16), jnp.int32)
    fn = (shard_scanned_lm_step(step, mesh, scan) if scan
          else shard_lm_train_step(step, mesh, seq_axis=None))
    return fn, (state, toks, toks)


STEPS = {
    "image": (_image_step, names.MODULE_TRAIN_STEP),
    "lm": (_lm_step, names.MODULE_LM_TRAIN_STEP),
}


@pytest.fixture(scope="module", params=sorted(STEPS))
def lowered(request):
    build, module = STEPS[request.param]
    fn, args = build()
    return fn.lower(*args).as_text(debug_info=True), module


def _locations(text: str, operation: str) -> list[str]:
    """The ``loc("…")`` names of every ``operation`` in a lowered module
    printed with debug info (an operation refers to ``#locN``; the table
    at the end spells it out, nested call sites included)."""
    table = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))

    def spell(ref: str, depth: int = 0) -> str:
        body = table.get(ref, "")
        if depth > 8:
            return body
        return re.sub(r"#loc\d+", lambda m: spell(m.group(0), depth + 1),
                      body)

    return [spell(ref) for ref in re.findall(
        rf"{re.escape(operation)}.*loc\((#loc\d+)\)", text)]


@pytest.mark.parametrize("scope", names.STEP_SCOPES)
def test_the_lowered_step_holds_every_scope(lowered, scope):
    text, _ = lowered
    # "sgp.optimizer/mul", "…/sgp.gossip/cond", "jvp(sgp.forward)/…"
    assert re.search(rf'[("/]{re.escape(scope)}[)/]', text), scope


def test_autodiff_marks_the_forward_scopes_transpose(lowered):
    text, _ = lowered
    assert f"transpose(jvp({names.SCOPE_FORWARD}))" in text
    assert f"jvp({names.SCOPE_FORWARD})" in text


def test_the_gossip_scope_encloses_the_collective_permute(lowered):
    text, _ = lowered
    where = _locations(text, "stablehlo.collective_permute")
    assert where, "the two-rank step moves nothing between ranks"
    assert all(names.SCOPE_GOSSIP + "/" in loc for loc in where), where


def test_the_jitted_step_has_a_stable_module_name(lowered):
    text, module = lowered
    assert f"module @jit_{module} " in text


@pytest.mark.parametrize("build,module", [
    (_image_step, names.MODULE_TRAIN_STEP_SCAN),
    (_lm_step, names.MODULE_LM_TRAIN_STEP_SCAN)], ids=["image", "lm"])
def test_the_scanned_steps_are_named_too(build, module):
    fn, args = build(scan=2)
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{module} " in text
    assert names.SCOPE_GOSSIP + "/" in text


def test_the_wire_codec_is_scoped_inside_the_gossip_round():
    fn, args = _image_step(codec=wire.Int8Codec(64))
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"{names.SCOPE_GOSSIP}/{names.SCOPE_WIRE}/" in text


def test_scopes_change_no_arithmetic(monkeypatch):
    """Same state and batch through the scoped step and through the same
    body with every scope a no-op: bit-equal outputs."""
    fn, (state, x, y) = _image_step()
    x = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    scoped_state, scoped = fn(jax.tree.map(jnp.copy, state), x, y)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain_fn, _ = _image_step()
    plain_state, plain = plain_fn(jax.tree.map(jnp.copy, state), x, y)
    for a, b in zip(jax.tree.leaves((scoped_state, scoped)),
                    jax.tree.leaves((plain_state, plain))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- host side ----------------------------------------------------------

class _Poisoned:
    def __getattr__(self, name):
        raise AssertionError(f"an idle ProfileWindow touched .{name}")


@pytest.mark.parametrize("window", [
    ProfileWindow(None),
    ProfileWindow("/nonexistent/never-started", start_step=5)],
    ids=["disabled", "enabled-not-active"])
def test_an_idle_profile_window_hands_out_the_shared_no_op(
        window, monkeypatch):
    """No capture active: ``span`` and ``step`` return the one shared
    no-op context — no clock read, no allocation, nothing of
    ``jax.profiler`` touched (as the null tracer, test_telemetry.py)."""
    monkeypatch.setattr(jax, "profiler", _Poisoned())
    monkeypatch.setattr(profiling, "time", _Poisoned())
    assert not window.active
    spans = [window.span(n) for n in names.HOST_SPANS]
    assert all(s is _NULL_SPAN for s in spans)
    assert window.step(7) is _NULL_SPAN
    with window.step(7), window.span("dispatch"):
        pass


def _host_events(profile_dir):
    from jax.profiler import ProfileData

    from benchmark.trace_reduce import find_xplane

    events = []
    for plane in ProfileData.from_file(find_xplane(profile_dir)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, dict(e.stats), e.start_ns, e.end_ns)
                           for e in line.events]
    return events


def test_an_active_window_writes_sgp_spans_and_steps(tmp_path):
    """Three steps captured on the CPU and read back: the spans and the
    step markers are in the XPlane's host plane, the python tracer's
    frames are not."""
    pw = ProfileWindow(str(tmp_path), start_step=2, num_steps=3)
    f = jax.jit(lambda a: a * 2.0)
    x = jnp.ones((8,))
    for step in range(1, 7):
        pw.maybe_start(step)
        with pw.step(step):
            with pw.span("dispatch"):
                y = f(x)
            with pw.span("fence"):
                jax.block_until_ready(y)
        pw.maybe_stop(step)
    assert not pw.active
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == names.HOST_STEP]
    assert sorted(e[1]["step_num"] for e in steps) == [2, 3, 4]
    for name in ("dispatch", "fence"):
        spans = [e for e in events
                 if e[0] == names.HOST_SPAN_PREFIX + name]
        assert len(spans) == 3
        # on the steps' own clock: every span lies inside a step
        assert all(any(s[2] <= e[2] and e[3] <= s[3] for s in steps)
                   for e in spans)
    assert not any(e[0].startswith("$") for e in events)   # python frames


@pytest.mark.parametrize("cli", ["gossip_sgd", "gossip_lm"])
def test_the_loops_put_their_phases_into_a_profile_dir_capture(
        cli, tmp_path):
    """Both training loops, through their entry points, with
    ``--profile_dir``: the capture holds ``sgp_step`` with the global step
    numbers and the loop's ``sgp:`` spans beside the step's module."""
    run_dir, prof = str(tmp_path / "run"), str(tmp_path / "prof")
    if cli == "gossip_sgd":
        from stochastic_gradient_push_tpu.run.gossip_sgd import main
        argv = ["--dataset", "synthetic", "--model", "tiny_cnn",
                "--num_classes", "10", "--image_size", "16",
                "--batch_size", "4", "--world_size", "4",
                "--num_epochs", "1", "--num_itr_ignore", "0",
                "--num_iterations_per_training_epoch", "6",
                "--health_every", "1"]
        expected = {"data_fetch", "dispatch", "fence", "metrics_fetch",
                    "health"}
        module = names.MODULE_TRAIN_STEP
    else:
        from stochastic_gradient_push_tpu.run.gossip_lm import main
        argv = ["--world_size", "4", "--seq_len", "32", "--d_model", "32",
                "--n_layers", "2", "--num_steps", "6", "--print_freq", "1"]
        expected = {"data_fetch", "dispatch", "fence", "metrics_fetch"}
        module = names.MODULE_LM_TRAIN_STEP
    main(argv + ["--checkpoint_dir", run_dir, "--profile_dir", prof,
                 "--profile_start_step", "2", "--profile_steps", "3"])
    events = _host_events(prof)
    steps = sorted(e[1]["step_num"] for e in events
                   if e[0] == names.HOST_STEP)
    assert steps == [2, 3, 4]
    seen = {e[0][len(names.HOST_SPAN_PREFIX):] for e in events
            if e[0].startswith(names.HOST_SPAN_PREFIX)}
    assert expected <= seen <= set(names.HOST_SPANS), seen
    assert any(dict(e[1]).get("hlo_module") == f"jit_{module}"
               for e in events)
