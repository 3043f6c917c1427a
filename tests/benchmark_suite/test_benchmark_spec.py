"""``BENCHMARK.json`` and the data files it names: everything loads, every
name refers to something that exists, and a later PR's new files are found
without editing one that is there."""

import os
import re

import pytest

from bench_toy import (REPO, TOY_CELL, TOY_CUT_CELL, TOY_LM_CELL,
                       TOY_LM_CUT, TOY_LM_CUT_REDUCED, make_toy_root)
from benchmark import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in spec.load_benchmark(REPO)["workloads"]]


def keeps_to_the_contracts_shape(root):
    """The checks of this file, each a function of the root whose
    ``BENCHMARK.json`` it reads: the tests hold the repo's to them, and
    test_appending.py a copy with an entry and a cell appended."""
    bench = spec.load_benchmark(root)
    cells = [w["name"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with 24 cells has to fit the driver's 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(root, path))
    assert all(os.path.exists(os.path.join(root, w)) for w in
               bench["command"] if "/" in w)
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in names
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(cells) // 4)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        # what is cut is listed, and the file keeps the contract for cuts
        assert len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"])
        spec.check_cut(c, spec._load_json(os.path.join(root, c["file"])))


def test_benchmark_json_keeps_to_the_contracts_shape():
    keeps_to_the_contracts_shape(REPO)


def _without(config, *keys):
    return {k: v for k, v in config.items() if k not in keys}


@pytest.mark.parametrize("config,reduced,refusal", [
    (TOY_LM_CUT, TOY_LM_CUT_REDUCED, None),
    (TOY_LM_CUT, TOY_LM_CUT_REDUCED + ["n_experts"],
     "reduced key 'n_experts' is no key of"),
    (_without(TOY_LM_CUT, "published"), TOY_LM_CUT_REDUCED,
     "reduced key 'n_layer' has no value under 'published'"),
    ({**TOY_LM_CUT, "published": {"n_layer": 8}}, TOY_LM_CUT_REDUCED,
     "reduced key 'vocab_size' has no value under 'published'"),
    ({**TOY_LM_CUT, "published": {"n_layer": 1, "vocab_size": 512}},
     TOY_LM_CUT_REDUCED, "'n_layer' holds 2, above the published 1"),
    (_without(TOY_LM_CUT, "deployment"), TOY_LM_CUT_REDUCED,
     "needs a 'deployment'"),
    ({**TOY_LM_CUT, "deployment": "  "}, TOY_LM_CUT_REDUCED,
     "needs a 'deployment'"),
    (TOY_LM_CUT, ["n_layer"],
     r"'published' states \['vocab_size'\], which 'reduced' does not list"),
    (TOY_LM_CUT, [], "nothing cut has no 'published'"),
    ({**TOY_LM_CUT, "published": {}}, [], "nothing cut has no 'published'"),
], ids=["keeps-the-contract", "reduced-key-the-file-lacks",
        "published-missing", "published-lacks-a-reduced-key",
        "held-above-published", "no-deployment", "blank-deployment",
        "published-states-what-reduced-does-not-list",
        "published-with-nothing-reduced", "empty-published-with-nothing-reduced"])
def test_a_cut_configuration_loads_and_a_malformed_one_is_refused(
        tmp_path, config, reduced, refusal):
    """The contract for ``reduced`` (``spec.check_cut``), through
    ``load_cell``: a run refuses what this test refuses, naming the key."""
    root = make_toy_root(str(tmp_path / "bench"), config, reduced)
    # the configurations beside it load whatever this one holds
    assert spec.load_cell(root, TOY_LM_CELL).config["n_layer"] == 2
    if refusal is None:
        cut = spec.load_cell(root, TOY_CUT_CELL)
        assert cut.config["published"] == {"n_layer": 8, "vocab_size": 512}
        assert cut.config["n_layer"] == 2 and cut.config["deployment"]
        argv = spec.load_plugin(root, "builders", cut.builder).argv_of(cut, 1)
        assert argv[argv.index("--n_layers") + 1] == "2"
    else:
        with pytest.raises(ValueError, match=refusal) as raised:
            spec.load_cell(root, TOY_CUT_CELL)
        assert "toy_lm_cut" in str(raised.value)


def test_a_cut_may_shorten_a_layer_pattern_and_never_lengthen_it():
    entry = {"name": "hybrid", "file": "f.json", "reduced": ["layer_types"]}
    config = {"layer_types": ["mamba", "attention"], "deployment": "a period",
              "published": {"layer_types": ["mamba", "attention"] * 4}}
    spec.check_cut(entry, config)
    config["published"]["layer_types"] = ["mamba"]
    with pytest.raises(ValueError, match="'layer_types' holds"):
        spec.check_cut(entry, config)


def every_file_the_cell_names_loads_and_agrees(root, cell):
    loaded = spec.load_cell(root, cell)
    assert loaded.traffic["ranks"] == loaded.chips
    assert loaded.loss_n >= 10
    builder = spec.load_plugin(root, "builders", loaded.builder)
    argv = builder.argv_of(loaded, 2 ** 31 + 7)
    assert "--seed" in argv and all(isinstance(a, str) for a in argv)
    reported = {m["name"] for m in loaded.end_to_end}
    assert {"setup_s", "step_ms"} <= reported and loaded.per_layer
    for m in loaded.per_layer:
        # a layer metric is reported only where the metric it moves is
        assert m["moves"] in reported, m["name"]
        assert callable(spec.load_reader(root, m))


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_loads_and_agrees(cell):
    every_file_the_cell_names_loads_and_agrees(REPO, cell)


def every_name_refers_to_something_that_exists(root):
    bench = spec.load_benchmark(root)
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in end_to_end
        assert os.path.isfile(spec.data_path(root, "layer_metrics",
                                             m["name"]))
    # no file without an entry: a metric or cell file nobody names
    for kind, names in (("layer_metrics", {m["name"]
                                           for m in bench["per_layer"]}),
                        ("workloads", cells),
                        ("configs", {c["name"] for c in bench["configs"]}),
                        ("traffic", {w["traffic"]
                                     for w in bench["workloads"]})):
        on_disk = {f[:-5] for f in os.listdir(
            os.path.join(root, "benchmark", kind)) if f.endswith(".json")}
        assert on_disk == names, kind
    with pytest.raises(KeyError, match="no workload named"):
        spec.load_cell(root, "no_such_cell")


def test_every_name_refers_to_something_that_exists():
    every_name_refers_to_something_that_exists(REPO)


def test_a_later_pr_adds_files_and_entries_and_edits_none(tmp_path):
    root = make_toy_root(str(tmp_path / "bench"))
    toy = spec.load_cell(root, TOY_CELL)
    assert toy.config["model"] == "tiny_cnn" and toy.chips == 2
    added = [m for m in toy.per_layer if m["name"] == "toy_steps"]
    assert added and added[0]["params"] == {"scale": 1e3}
    assert spec.load_reader(root, added[0]).__name__ == "steps_per_s"
    # the accepted files are byte for byte what they were
    for kind in ("configs", "workloads", "traffic", "layer_metrics",
                 "readers", "builders"):
        for f in os.listdir(os.path.join(REPO, "benchmark", kind)):
            if f.startswith("__"):
                continue
            with open(os.path.join(REPO, "benchmark", kind, f), "rb") as a, \
                    open(os.path.join(root, "benchmark", kind, f),
                         "rb") as b:
                assert a.read() == b.read(), f
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert TOY_CELL not in f.read()
    with pytest.raises(FileNotFoundError):
        spec.load_plugin(root, "readers", "no_such_reader")
