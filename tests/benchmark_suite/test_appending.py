"""A later PR adds to the benchmark by appending: a per-layer metric is one
file and one entry at the end of ``per_layer``, a cell its files and one
entry, and a cell joins a metric by being appended to its ``workloads``.
The checks the other tests apply to the repo's ``BENCHMARK.json`` hold on a
copy with all three done, with no file of the benchmark edited."""

import json
import os
import shutil

import pytest

import test_benchmark_spec
import test_hybrid_cell
import test_lfm2_cell
import test_olmo_hybrid_cell
import test_setup_metrics
from bench_toy import REPO, TOY_LM, TOY_TOKENS, _write
from benchmark import spec

ACCEPTED_CELL = test_olmo_hybrid_cell.CELL
NEW_CELL = "toy_lm_sgp_w1"
NEW_METRIC = "toy_kernel_ms"
# accepted lists the new cell joins: an LM cell's head, flash kernels and
# set-up ledger
JOINED = ("lm_head_ms", "flash_ms", "flash_fwd_ms", "flash_bwd_ms",
          *test_setup_metrics.LEDGER_METRICS)


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A copy of ``BENCHMARK.json`` and of its ``paths`` with one metric,
    one cell and the cell in accepted lists, each by appending."""
    root = str(tmp_path_factory.mktemp("appended"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    data = os.path.join(root, "benchmark")
    _write(os.path.join(data, "configs", "toy_lm.json"), TOY_LM)
    _write(os.path.join(data, "traffic", "toy_tokens_w1.json"), TOY_TOKENS)
    _write(os.path.join(data, "workloads", NEW_CELL + ".json"),
           {"flags": ["--lr", "8.0"], "loss_n": 40})
    _write(os.path.join(data, "layer_metrics", NEW_METRIC + ".json"),
           {"reader": "program_trace:kernel_ms",
            "params": {"pattern": "^toy_(fwd|bwd)(\\.\\d+)?$"},
            "what": "trace: device time a step of the calls named toy_fwd "
                    "or toy_bwd"})
    bench["configs"].append(
        {"name": "toy_lm", "source": "test", "reduced": [], "why": "toy",
         "file": "benchmark/configs/toy_lm.json"})
    bench["workloads"].append(
        {"name": NEW_CELL, "config": "toy_lm", "traffic": "toy_tokens_w1",
         "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            m["workloads"].append(NEW_CELL)
    bench["per_layer"].append(
        {"name": NEW_METRIC, "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "Kernels", "moves": "step_ms",
         "workloads": [ACCEPTED_CELL, NEW_CELL]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _every_cell_loads_and_agrees(root):
    for w in spec.load_benchmark(root)["workloads"]:
        test_benchmark_spec.every_file_the_cell_names_loads_and_agrees(
            root, w["name"])


CHECKS = {
    "contract": test_benchmark_spec.keeps_to_the_contracts_shape,
    "names": test_benchmark_spec.every_name_refers_to_something_that_exists,
    "cells": _every_cell_loads_and_agrees,
    "setup": test_setup_metrics.entries_hold,
    "granite": test_hybrid_cell.the_cell_and_every_file_it_names_load,
    "lfm2": test_lfm2_cell.the_cell_and_every_file_it_names_load,
    "olmo": test_olmo_hybrid_cell.the_cell_and_every_file_it_names_load,
    "olmo_lists": test_olmo_hybrid_cell.the_rules_entries_and_lists_hold,
}


@pytest.mark.parametrize("check", sorted(CHECKS))
def test_the_checks_of_the_real_file_hold_on_an_appended_copy(
        appended, check):
    CHECKS[check](appended)


def test_what_was_appended_loads_where_it_was_appended(appended):
    new = {m["name"] for m in spec.load_cell(appended, NEW_CELL).per_layer}
    assert {NEW_METRIC, *JOINED} <= new
    accepted = spec.load_cell(appended, ACCEPTED_CELL).per_layer
    metric = next(m for m in accepted if m["name"] == NEW_METRIC)
    assert callable(spec.load_reader(appended, metric))
    # the entries are the repo's, in their order, with the new one last
    names = [m["name"] for m in spec.load_benchmark(appended)["per_layer"]]
    repo = [m["name"] for m in spec.load_benchmark(REPO)["per_layer"]]
    assert names == repo + [NEW_METRIC]

