"""The seven per-layer metrics under ``setup_s`` (PR 38): their entries and
files, a traced toy run that reports all of them from the program's set-up
ledger, and readers that find nothing to read wherever there is no ledger
to read."""

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

SETUP_METRICS = {
    "setup_trace_lower_s": ("s", "program_span"),
    "setup_compile_s": ("s", "program_span"),
    "setup_cache_load_s": ("s", "program_span"),
    "setup_step_program_s": ("s", "program_span"),
    "setup_programs": ("count", "program_counter"),
    "setup_cache_misses": ("count", "program_counter"),
    "setup_unaccounted_s": ("s", "program_span"),
}
CELLS = ["resnet50_sgp_w1", "gpt2m_sgp_w1_t1024", "gpt2m_sgp_w1_t8192",
         "resnet50_sgp_w4", "lfm2moe_sgp_w1_t4096_b2"]
TOY_STEPS = 45


def _entries(root=REPO):
    return {m["name"]: m for m in spec.load_benchmark(root)["per_layer"]
            if m["name"] in SETUP_METRICS}


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
        if m["name"] in SETUP_METRICS:
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
    entries = _entries()
    assert set(entries) == set(SETUP_METRICS)
    last = [m["name"] for m in spec.load_benchmark(REPO)["per_layer"]][-7:]
    assert set(last) == set(SETUP_METRICS)      # appended, nothing moved
    for name, (unit, source) in SETUP_METRICS.items():
        m = entries[name]
        assert (m["unit"], m["source"]) == (unit, source)
        assert (m["layer"], m["moves"], m["better"]) == (
            "Entry points", "setup_s", "lower")
        assert m["workloads"] == CELLS
        with open(spec.data_path(REPO, "layer_metrics", name)) as f:
            file = json.load(f)
        assert file["reader"].startswith("setup_ledger:") and file["what"]
    for cell in CELLS:
        loaded = {m["name"] for m in spec.load_cell(REPO, cell).per_layer}
        assert set(SETUP_METRICS) <= loaded
    granite = spec.load_cell(REPO, "granite4hm_sgp_w1_t4096").per_layer
    assert not set(SETUP_METRICS) & {m["name"] for m in granite}


@pytest.mark.parametrize("cell", [TOY_CELL, TOY_LM_CELL])
def test_a_traced_toy_run_reports_all_seven(toy_root, ledger, cell, capsys):
    result = harness.run_cell(toy_root, cell, 2 ** 31 + 11, 0.2, True,
                              time.time(), min_steps=TOY_STEPS)
    printed = capsys.readouterr().out
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(SETUP_METRICS) <= set(metrics)
    for name, (unit, _) in SETUP_METRICS.items():
        assert result["metrics"][name]["unit"] == unit
    built = int(re.search(r"(\d+) programs built in set-up", printed)[1])
    assert metrics["setup_programs"] == built > 0
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


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
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
    for name in SETUP_METRICS:
        reader, reading = _reading(name)
        assert reader(reading) is None


def test_a_program_from_before_the_ledger_reads_nothing(monkeypatch, ledger):
    """The parent's program has no ``telemetry.setup_ledger``: the import
    fails, the reader returns nothing and the line leaves the metric out."""
    monkeypatch.delattr(telemetry, "setup_ledger")
    monkeypatch.setitem(sys.modules, setup_ledger.__name__, None)
    for name in SETUP_METRICS:
        reader, reading = _reading(name)
        assert reader(reading) is None
