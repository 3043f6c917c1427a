"""Find a cell and everything it names, by name, in data files.

``<root>/BENCHMARK.json`` lists cells, configurations and metrics; each
name resolves to a file of its own under ``<root>/benchmark/``:

* ``configs/<config>.json``        the model's sizes and its builder; what is
  cut from the source under ``published`` and ``deployment`` (``check_cut``)
* ``workloads/<cell>.json``        the program's flags for the cell, ``loss_n``
* ``traffic/<traffic>.json``       parameters of the one batch generator
* ``layer_metrics/<metric>.json``  the reader a per-layer metric names
* ``builders/<builder>.py``, ``readers/<module>.py``  code found by path

so a later PR adds files and entries and edits nothing.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import typing as tp

DATA_DIR = "benchmark"


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def data_path(root: str, kind: str, name: str, ext: str = ".json") -> str:
    return os.path.join(root, DATA_DIR, kind, name + ext)


def load_benchmark(root: str) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_plugin(root: str, kind: str, name: str):
    """Import ``<root>/benchmark/<kind>/<name>.py`` by path: a builder or
    a reader module a data file names."""
    path = data_path(root, kind, name, ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic_name: str
    traffic: dict         # traffic/<traffic>.json
    file: dict            # workloads/<cell>.json, whole
    flags: list[str]      # the program's flags only this cell sets
    loss_n: int           # loss_at_n averages steps n-9..n
    end_to_end: list[dict]
    per_layer: list[dict]  # BENCHMARK.json entry merged with its file

    @property
    def builder(self) -> str:
        return self.config["builder"]


def _size(value):
    """What a cut may only lower: a number itself, a list (a layer
    pattern) or a group by its length."""
    return value if isinstance(value, (int, float)) else len(value)


def check_cut(config_entry: dict, config: dict) -> None:
    """The contract for a cut configuration.  Every key the entry lists
    under ``reduced`` is a key of the configuration's file; the file states
    under ``published`` the source's value of exactly those keys, none
    smaller than what is held here; and a cut names the ``deployment`` it
    stands for.  A configuration with nothing cut keeps ``reduced: []`` and
    no ``published``.  Raises ``ValueError`` naming the key."""
    name, reduced = config_entry["name"], list(config_entry["reduced"])
    published = config.get("published", {})
    for key in reduced:
        if key not in config:
            raise ValueError(f"configuration {name}: reduced key {key!r} "
                             f"is no key of {config_entry['file']}")
        if key not in published:
            raise ValueError(f"configuration {name}: reduced key {key!r} "
                             "has no value under 'published'")
        if _size(config[key]) > _size(published[key]):
            raise ValueError(
                f"configuration {name}: {key!r} holds {config[key]}, above "
                f"the published {published[key]}: a cut only takes away")
    extra = sorted(set(published) - set(reduced))
    if extra or ("published" in config and not reduced):
        raise ValueError(f"configuration {name}: 'published' states "
                         f"{extra}, which 'reduced' does not list: a "
                         "configuration with nothing cut has no 'published'")
    if reduced and not str(config.get("deployment", "")).strip():
        raise ValueError(f"configuration {name}: reduced {reduced} needs a "
                         "'deployment' that says what the cut stands for")


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = load_benchmark(root)
    entry = _by_name(bench["workloads"], name, "workload")
    config_entry = _by_name(bench["configs"], entry["config"],
                            "configuration")
    config = _load_json(os.path.join(root, config_entry["file"]))
    check_cut(config_entry, config)
    cell_file = _load_json(data_path(root, "workloads", name))
    per_layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, name):
            per_layer.append(
                {**_load_json(data_path(root, "layer_metrics", m["name"])),
                 **m})
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        traffic_name=entry["traffic"],
        traffic=_load_json(data_path(root, "traffic", entry["traffic"])),
        file=cell_file, flags=[str(f) for f in cell_file["flags"]],
        loss_n=int(cell_file["loss_n"]),
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=per_layer)


def load_reader(root: str, metric: dict) -> tp.Callable:
    """The function a layer metric's file names as ``module:function``."""
    module, _, func = metric["reader"].partition(":")
    return getattr(load_plugin(root, "readers", module), func)
