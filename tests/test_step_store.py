"""The step store (``utils/step_store.py``): a train step kept under a key
made before tracing, loaded by a later process without a trace.

Round trips run in subprocesses, as a relaunch does; the key's sensitivity
and the failures run here, on the toy cells' real builders
(``tests/benchmark_suite/bench_toy.py``) where a builder is the point."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from stochastic_gradient_push_tpu.telemetry import names, setup_ledger
from stochastic_gradient_push_tpu.utils import step_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark_suite"))

from bench_toy import TOY_CELL, TOY_LM_CELL, make_toy_root  # noqa: E402

LEDGER = setup_ledger.LEDGER
SEED = 2 ** 31 + 11


@pytest.fixture
def store(tmp_path, monkeypatch):
    """The store armed in a temporary cache directory, on the CPU too, and
    the ledger armed and empty; both left as they were found."""
    monkeypatch.setattr(step_store, "PLATFORMS", ("tpu", "cpu"))
    was_armed = LEDGER.armed
    setup_ledger.arm()
    LEDGER.reset()
    step_store.arm(str(tmp_path / "cache"))
    yield str(tmp_path / "cache" / step_store.STORE_SUBDIR)
    step_store.disarm()
    if not was_armed:
        setup_ledger.disarm()
    LEDGER.reset()


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(str(tmp_path_factory.mktemp("bench")))


def _build(root, name, seed=SEED, flags=()):
    from benchmark import spec

    cell = spec.load_cell(root, name)
    cell = dataclasses.replace(cell, flags=cell.flags + list(flags))
    return spec.load_plugin(root, "builders", cell.builder).build(cell, seed)


def _toy_step(material=("toy",)):
    def step(state, x):
        return {"w": state["w"] * 0.5 + x.sum()}, {"loss": state["w"].sum()}

    step.__name__ = names.MODULE_TRAIN_STEP
    return step_store.jit(step, material=material, donate_argnums=(0,))


def _state():
    return {"w": jnp.arange(4.0)}


# -- round trip ---------------------------------------------------------------

_ROUND_TRIP = textwrap.dedent("""
    import hashlib, json, sys
    sys.path[:0] = [{repo!r}, {suite!r}]
    import jax, numpy as np
    from benchmark import spec
    from stochastic_gradient_push_tpu.telemetry import setup_ledger
    from stochastic_gradient_push_tpu.utils import step_store
    if {armed}:
        setup_ledger.arm()
        step_store.arm({cache!r})
        step_store.PLATFORMS = ("cpu",)
    cell = spec.load_cell({root!r}, {cell!r})
    job = spec.load_plugin({root!r}, "builders", cell.builder).build(
        cell, {seed})
    built = []
    listen = lambda event, seconds, **_: built.append(event) if event == (
        "/jax/core/compile/backend_compile_duration") else None
    jax.monitoring.register_event_duration_secs_listener(listen)
    compiled = job.step.lower(job.state, *job.batches[0]).compile()
    text = "ENTRY" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes >= 0
    state, losses = job.state, []
    for i in range(3):
        first = jax.tree.leaves(state)[0]
        state, metrics = job.step(state, *job.batches[i % len(job.batches)])
        losses.append(np.asarray(metrics["loss"]).tolist())
    jax.block_until_ready(state)
    digest = hashlib.sha256(b"".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(state)))
    s = setup_ledger.LEDGER.summary()
    print(json.dumps({{"built": len(built), "losses": losses,
                      "state": digest.hexdigest(), "donated":
                      first.is_deleted(), "text": text, "temp": temp,
                      "hits": s["step_store_hits"],
                      "note": s["step_store"]}}))
""")


def _round_trip(root, cell, cache, armed=True):
    code = _ROUND_TRIP.format(
        repo=REPO, suite=os.path.join(REPO, "tests", "benchmark_suite"),
        root=root, cell=cell, cache=cache, seed=SEED, armed=armed)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [TOY_LM_CELL, TOY_CELL])
def test_a_second_process_loads_the_step_and_never_builds_it(
        toy_root, tmp_path, cell):
    cache = str(tmp_path)
    plain = _round_trip(toy_root, cell, cache, armed=False)
    first = _round_trip(toy_root, cell, cache)
    second = _round_trip(toy_root, cell, cache)
    assert first["note"] == "miss, written" and first["hits"] == 0
    assert first["built"] == plain["built"] >= 1
    assert second["note"] == "stored (hit)" and second["hits"] == 1
    # nothing compiled nor loaded by JAX: not at compile(), not at the call
    assert second["built"] == 0
    for run in (first, second):
        assert (run["losses"], run["state"]) == (plain["losses"],
                                                 plain["state"])
        assert run["donated"] and run["text"] and run["temp"]
    assert len(os.listdir(os.path.join(cache, step_store.STORE_SUBDIR))) == 1


# -- the key ---------------------------------------------------------------

_KEY = textwrap.dedent("""
    import sys
    sys.path[:0] = [{repo!r}, {suite!r}]
    from benchmark import spec
    from stochastic_gradient_push_tpu.utils import step_store
    step_store.arm({cache!r})
    for seed in {seeds}:
        cell = spec.load_cell({root!r}, {cell!r})
        job = spec.load_plugin({root!r}, "builders", cell.builder).build(
            cell, seed)
        print("key", job.step.key(job.state, *job.batches[0]))
""")


@pytest.mark.parametrize("cell", [TOY_LM_CELL, TOY_CELL])
def test_the_key_is_one_across_seeds_and_processes(toy_root, tmp_path, cell):
    keys = []
    for seeds in ([SEED, 3], [2 ** 31 + 901]):
        code = _KEY.format(
            repo=REPO, suite=os.path.join(REPO, "tests", "benchmark_suite"),
            root=toy_root, cell=cell, cache=str(tmp_path), seeds=seeds)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=300,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr[-3000:]
        keys += [line.split()[1] for line in out.stdout.splitlines()
                 if line.startswith("key ")]
    assert len(keys) == 3 and len(set(keys)) == 1


def _key(job, batch=None):
    return job.step.key(job.state, *(batch or job.batches[0]))


@pytest.mark.parametrize("flags", [["--lr", "4.0"], ["--remat", "True"],
                                   ["--attn_block", "16"]])
def test_a_flag_changes_the_key(toy_root, store, flags):
    assert _key(_build(toy_root, TOY_LM_CELL)) != _key(
        _build(toy_root, TOY_LM_CELL, flags=flags))


def test_an_arguments_shape_or_sharding_changes_the_key(toy_root, store):
    job = _build(toy_root, TOY_LM_CELL)
    tokens, targets = job.batches[0]
    other = jax.device_put(tokens, NamedSharding(job.mesh, P()))
    assert len({_key(job), _key(job, (tokens[:, :4], targets[:, :4])),
                _key(job, (other, targets))}) == 3


def test_one_byte_of_package_source_changes_the_key(store, tmp_path,
                                                    monkeypatch):
    step, args = _toy_step(), (_state(), jnp.ones(3))
    keys = []
    for flip in (False, True):
        copy = str(tmp_path / f"package{flip}")
        shutil.copytree(step_store._PACKAGE_DIR, copy,
                        ignore=shutil.ignore_patterns("__pycache__"))
        path = os.path.join(copy, "train", "lm.py")
        with open(path, "rb") as f:
            data = bytearray(f.read())
        data[-2] ^= flip
        with open(path, "wb") as f:
            f.write(data)
        monkeypatch.setattr(step_store, "_PACKAGE_DIR", copy)
        keys.append(step.key(*args))
    assert keys[0] != keys[1]


def test_xla_flags_and_a_config_value_change_the_key(store, monkeypatch):
    step, args = _toy_step(), (_state(), jnp.ones(3))
    before = step.key(*args)
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                       + " --xla_cpu_enable_fast_math=false")
    flagged = step.key(*args)
    monkeypatch.undo()
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        configured = step.key(*args)
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    assert step.key(*args) == before
    assert len({before, flagged, configured}) == 3


def test_closed_over_values_are_walked(store):
    args = (_state(), jnp.ones(3))

    def step_with(scale):
        def per_rank(s, x):
            return s, x * scale
        return _toy_step(material=(per_rank,))

    assert step_with(np.float32(2.0)).key(*args) != step_with(
        np.float32(3.0)).key(*args)
    assert step_with(np.arange(3)).key(*args) == step_with(
        np.arange(3)).key(*args)


# -- refusal and failure -----------------------------------------------------


def test_an_unencodable_closure_value_refuses_and_runs_as_plain_jit(store):
    lock = threading.Lock()

    def per_rank(s, x):
        with lock:
            return s, x

    step = _toy_step(material=(per_rank,))
    with pytest.raises(step_store.Refused, match="lock"):
        step.key(_state(), jnp.ones(3))
    state, _ = step(_state(), jnp.ones(3))
    assert np.allclose(state["w"], np.arange(4.0) * 0.5 + 3)
    assert LEDGER.summary()["step_store"].startswith("refused: cannot encode")
    assert not os.path.exists(store)


def test_a_failed_write_is_a_miss_and_no_error(tmp_path, store):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    step_store.arm(str(blocker))
    state, _ = _toy_step()(_state(), jnp.ones(3))
    assert np.allclose(state["w"], np.arange(4.0) * 0.5 + 3)
    assert LEDGER.store_notes[-1].startswith("miss, not written")


def test_a_corrupt_entry_is_a_miss_and_is_written_again(store):
    args = lambda: (_state(), jnp.ones(3))
    step = _toy_step()
    step(*args())
    (entry,) = os.listdir(store)
    with open(os.path.join(store, entry), "wb") as f:
        f.write(b"\x00 not an entry")
    LEDGER.reset()
    state, _ = _toy_step()(*args())
    assert np.allclose(state["w"], np.arange(4.0) * 0.5 + 3)
    unreadable, written = LEDGER.store_notes
    assert unreadable.startswith("entry unreadable") and written == \
        "miss, written"
    LEDGER.reset()
    _toy_step()(*args())
    assert LEDGER.store_notes == ["stored (hit)"]


def test_arguments_the_loaded_step_was_not_built_for_are_looked_up_once(
        store):
    for n in (3, 5):
        _toy_step()(_state(), jnp.ones(n))     # two entries
    LEDGER.reset()
    step = _toy_step()
    w = np.arange(4.0)
    for n in (3, 5, 3, 3):     # loaded, loaded after a miss-fit, jit, jit
        state, _ = step({"w": jnp.asarray(w)}, jnp.ones(n))
        w = w * 0.5 + n
        assert np.allclose(state["w"], w)
    assert LEDGER.store_notes == ["stored (hit)"] * 2
    assert step._call is step._jit


def test_off_the_tpu_or_over_processes_it_is_the_jit(store, monkeypatch):
    monkeypatch.setattr(step_store, "PLATFORMS", ("tpu",))
    state, _ = _toy_step()(_state(), jnp.ones(3))
    assert np.allclose(state["w"], np.arange(4.0) * 0.5 + 3)
    assert LEDGER.store_notes == ["off (cpu, 1 processes)"]
    assert not os.path.exists(store)


def test_unarmed_the_store_is_plain_jit():
    step_store.disarm()
    assert not isinstance(_toy_step(), step_store.StoredStep)


def test_the_store_keeps_its_newest_entries(store):
    os.makedirs(store)
    for i in range(step_store.KEEP + 3):
        path = os.path.join(store, f"{i:02d}{step_store._SUFFIX}")
        with open(path, "w") as f:
            f.write("x")
        os.utime(path, (1000 + i, 1000 + i))
    step_store._evict(store)
    assert sorted(os.listdir(store)) == [
        f"{i:02d}{step_store._SUFFIX}"
        for i in range(3, step_store.KEEP + 3)]


# -- the ledger ---------------------------------------------------------------


def test_a_hit_is_the_step_row_and_the_cut(store):
    _toy_step()(_state(), jnp.ones(3))
    LEDGER.reset()
    before = time.time()
    _toy_step()(_state(), jnp.ones(3))
    s = LEDGER.summary()
    step = s["step_program"]
    assert (step["fun_name"], step["cache"]) == (names.MODULE_TRAIN_STEP,
                                                 "stored")
    assert step["trace_s"] == step["lower_s"] == 0.0 < step["backend_s"]
    assert LEDGER.rows[LEDGER.cut]["backend"][0] >= before
    assert s["step_store_hits"] == 1 and s["cache_hits"] == 0
    assert s["cache_load_s"] >= step["backend_s"] and s["programs"] == 1
    line = setup_ledger.setup_line(s)
    assert line.endswith("; step stored (hit)") and "load" in line


def test_a_miss_says_written_in_the_line(store):
    _toy_step()(_state(), jnp.ones(3))
    s = LEDGER.summary()
    assert s["step_store_hits"] == 0 and s["step_program"]["cache"] != \
        "stored"
    assert setup_ledger.setup_line(s).endswith("; step miss, written")


_WARM_RUN = textwrap.dedent("""
    import json, re, sys, time
    sys.path[:0] = [{repo!r}, {suite!r}]
    from benchmark import harness
    from stochastic_gradient_push_tpu.telemetry import setup_ledger
    from stochastic_gradient_push_tpu.utils import step_store
    setup_ledger.arm()
    step_store.arm({cache!r})
    step_store.PLATFORMS = ("cpu",)
    printed = []
    for _ in range(2):
        setup_ledger.LEDGER.reset()
        result = harness.run_cell({root!r}, {cell!r}, {seed}, 0.0, False,
                                  time.time(), log=printed.append,
                                  min_steps=45)
        built = int(re.search(r"(\\d+) programs built in set-up",
                              "\\n".join(printed))[1])
        printed.clear()
        s = setup_ledger.LEDGER.summary()
        print(json.dumps({{"built": built, "programs": s["programs"],
                          "hits": s["step_store_hits"],
                          "note": s["step_store"],
                          "correct": result["correct"],
                          "in_window":
                              result["checks"]["compilations_in_window"],
                          "losses": result["checks"]["loss_every_10_steps"],
                          "placement": result["checks"].get("placement")}}))
""")


@pytest.mark.parametrize("cell", [TOY_CELL, TOY_LM_CELL])
def test_a_warm_toy_run_loads_its_step_and_reads_the_same(toy_root, tmp_path,
                                                          cell):
    """The harness's own run, twice in one process: the second's step is
    the stored one, a program fewer is built, and it trains the same."""
    code = _WARM_RUN.format(
        repo=REPO, suite=os.path.join(REPO, "tests", "benchmark_suite"),
        root=toy_root, cell=cell, cache=str(tmp_path), seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    cold, warm = [json.loads(line) for line in out.stdout.splitlines()
                  if line.startswith("{")]
    assert warm["correct"] and cold["correct"]
    assert (cold["note"], cold["hits"]) == ("miss, written", 0)
    assert (warm["note"], warm["hits"]) == ("stored (hit)", 1)
    assert cold["built"] == cold["programs"]
    assert warm["built"] == warm["programs"] - 1
    assert warm["in_window"] == cold["in_window"] == 0
    assert (warm["losses"], warm["placement"]) == (cold["losses"],
                                                   cold["placement"])
