"""The per-layer metrics under ``setup_s`` read from the program's set-up
ledger (seven totals and the step store's hits): their entries and files,
a traced toy run that reports all of them, and readers that find nothing
to read wherever there is no ledger to read."""

import json
import os
import re
import sys
import time
import types

import pytest

from bench_toy import REPO, TOY_CELL, TOY_LM_CELL, make_toy_root
from benchmark import harness, spec
from stochastic_gradient_push_tpu import telemetry
from stochastic_gradient_push_tpu.telemetry import setup_ledger

# the set-up ledger's seven, side by side in this order
SETUP_METRICS = {
    "setup_trace_lower_s": ("s", "program_span"),
    "setup_compile_s": ("s", "program_span"),
    "setup_cache_load_s": ("s", "program_span"),
    "setup_step_program_s": ("s", "program_span"),
    "setup_programs": ("count", "program_counter"),
    "setup_cache_misses": ("count", "program_counter"),
    "setup_unaccounted_s": ("s", "program_span"),
}
# the step store's hits: rows of the ledger that no JAX build made
STORE_HITS = "setup_step_store_hits"
LEDGER_METRICS = {**SETUP_METRICS, STORE_HITS: ("count", "program_counter")}
# the cells each list holds at least: a later PR appends more
CELLS = ["resnet50_sgp_w1", "gpt2m_sgp_w1_t1024", "gpt2m_sgp_w1_t8192",
         "resnet50_sgp_w4", "lfm2moe_sgp_w1_t4096_b2",
         "granite4hm_sgp_w1_t4096", "olmohyb_sgp_w1_t4096"]
TOY_STEPS = 45


def entries_hold(root):
    """The ledger's metrics in the ``BENCHMARK.json`` at ``root``: one entry
    each with these fields and a file naming a ledger reader; the seven side
    by side in their order; every cell of ``CELLS`` in each list, and every
    cell a list names loads the metric."""
    per_layer = spec.load_benchmark(root)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert all(names.count(name) == 1 for name in LEDGER_METRICS)
    first = names.index(next(iter(SETUP_METRICS)))
    assert names[first:first + len(SETUP_METRICS)] == list(SETUP_METRICS)
    entries = {m["name"]: m for m in per_layer}
    loaded = {}
    for name, (unit, source) in LEDGER_METRICS.items():
        m = entries[name]
        assert (m["unit"], m["source"]) == (unit, source)
        better = "higher" if name == STORE_HITS else "lower"
        assert (m["layer"], m["moves"], m["better"]) == (
            "Entry points", "setup_s", better)
        assert set(CELLS) <= set(m["workloads"])
        with open(spec.data_path(root, "layer_metrics", name)) as f:
            file = json.load(f)
        assert file["reader"].startswith("setup_ledger:") and file["what"]
        for cell in m["workloads"]:
            if cell not in loaded:
                loaded[cell] = {x["name"] for x in
                                spec.load_cell(root, cell).per_layer}
            assert name in loaded[cell], (name, cell)
    with open(spec.data_path(root, "layer_metrics", STORE_HITS)) as f:
        assert json.load(f)["params"] == {"key": "step_store_hits"}


@pytest.fixture
def ledger():
    """The process's ledger, armed and empty; left as it was found."""
    was_armed = setup_ledger.LEDGER.armed
    setup_ledger.arm()
    setup_ledger.LEDGER.reset()
    yield setup_ledger.LEDGER
    if not was_armed:
        setup_ledger.disarm()
    setup_ledger.LEDGER.reset()


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The toy root with the toy cells appended to the new entries, the
    way a later PR appends its cell."""
    root = make_toy_root(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in LEDGER_METRICS:
            m["workloads"] += [TOY_CELL, TOY_LM_CELL]
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def _reading(name, **values):
    cell = spec.load_cell(REPO, CELLS[0])
    metric = next(m for m in cell.per_layer if m["name"] == name)
    reading = types.SimpleNamespace(
        cell=cell, params=metric.get("params", {}),
        values={"setup_s": 40.0, **values})
    return spec.load_reader(REPO, metric), reading


def test_the_seven_are_entry_points_metrics_under_setup_s_in_five_cells():
    """Every cell, and the step store's hits beside the seven."""
    entries_hold(REPO)


@pytest.mark.parametrize("cell", [TOY_CELL, TOY_LM_CELL])
def test_a_traced_toy_run_reports_all_seven(toy_root, ledger, cell, capsys):
    result = harness.run_cell(toy_root, cell, 2 ** 31 + 11, 0.2, True,
                              time.time(), min_steps=TOY_STEPS)
    printed = capsys.readouterr().out
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(LEDGER_METRICS) <= set(metrics)
    for name, (unit, _) in LEDGER_METRICS.items():
        assert result["metrics"][name]["unit"] == unit
    built = int(re.search(r"(\d+) programs built in set-up", printed)[1])
    # the step store keeps to a TPU: on the CPU every program is built
    assert metrics[STORE_HITS] == 0
    assert metrics["setup_programs"] == built + metrics[STORE_HITS] > 0
    assert result["checks"]["compilations_in_window"] == 0
    assert result["correct"] is True, result["checks"]["verdicts"]
    setup_s = result["end_to_end_of_this_run"]["setup_s"]
    assert 0 <= metrics["setup_unaccounted_s"] < setup_s
    assert metrics["setup_step_program_s"] > 0
    # whether a persistent cache is on is the process's own affair (an
    # entry point run in it earlier places one): compiled or loaded
    assert metrics["setup_compile_s"] + metrics["setup_cache_load_s"] > 0
    assert 0 <= metrics["setup_cache_misses"] <= built
    parts = (metrics["setup_trace_lower_s"] + metrics["setup_compile_s"]
             + metrics["setup_cache_load_s"])
    assert metrics["setup_step_program_s"] < parts
    assert parts + metrics["setup_unaccounted_s"] <= setup_s + \
        setup_ledger.LEDGER.summary()["overlap_s"] + 1e-6
    # the rows by name, for whoever reads the log; the step closes them
    rows = re.findall(r"set-up built (\S+): trace", printed)
    assert len(rows) == built
    assert rows[-1] == ledger.rows[ledger.cut]["fun_name"]
    # the comparison's program came after the cut and is in no total
    later = [r["fun_name"] for r in ledger.summary()["later_rows"]]
    assert ("both" in later) == ("reference" in result["checks"])


@pytest.mark.parametrize("name", sorted(LEDGER_METRICS))
def test_with_the_ledger_unarmed_every_reader_returns_none(name):
    was_armed = setup_ledger.LEDGER.armed
    setup_ledger.disarm()
    try:
        reader, reading = _reading(name)
        assert reader(reading) is None
    finally:
        if was_armed:
            setup_ledger.arm()


def test_armed_with_no_train_step_built_there_is_no_cut_to_read_up_to(
        ledger):
    ledger.phase("mesh", time.time() - 1.0, time.time())
    for name in LEDGER_METRICS:
        reader, reading = _reading(name)
        assert reader(reading) is None


def test_a_program_from_before_the_ledger_reads_nothing(monkeypatch, ledger):
    """The parent's program has no ``telemetry.setup_ledger``: the import
    fails, the reader returns nothing and the line leaves the metric out."""
    monkeypatch.delattr(telemetry, "setup_ledger")
    monkeypatch.setitem(sys.modules, setup_ledger.__name__, None)
    for name in LEDGER_METRICS:
        reader, reading = _reading(name)
        assert reader(reading) is None
