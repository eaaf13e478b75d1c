"""Find a cell's files by the names in BENCHMARK.json.

A cell is ``<config>.<traffic>``.  Its configuration is
``benchmark/configs/<config>.json``, its traffic mix
``benchmark/traffic/<traffic>.json`` (which names its driver,
``benchmark/drivers/<driver>.py``), and each metric it reports is read by
``benchmark/metrics/<metric>.py``.  The configuration names its model
family (``program.family``); what depends on the architecture is in
``benchmark/families/<family>.py``, which names the plain reference,
``benchmark/reference/<name>.py``.  A later PR adds a cell, a mix, a
metric or a family by adding files and entries; nothing here lists
them.

What a configuration brings (its file, its family and reference, the
family's rehearsal file, its traffic files) is looked for under the
tree the cell was loaded from, ``load_cell(..., root=)``: the checkout
for the command, a tree of fixture files for a test.  What the harness
is (drivers, metric readers) is this package's own.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def tree(root: str, *parts: str) -> str:
    """``<root>/benchmark/<parts>``: where a tree keeps what this
    package keeps under its own directory."""
    return os.path.join(root, os.path.basename(HERE), *parts)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    #: the metric entries of BENCHMARK.json this cell reports
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    #: the tree its files were found under
    root: str = ROOT

    @property
    def family(self):
        """``benchmark/families/<program.family>.py``."""
        return load_family(self.config["program"]["family"], self.root)

    @property
    def reference(self):
        """The family's plain reference, ``benchmark/reference/*.py``."""
        return _load_module("reference", self.family.REFERENCE, self.root)

    @property
    def reference_kwargs(self) -> Dict[str, Any]:
        """What the reference needs beyond the parameter tree, as the
        family reads it from the configuration; nothing where the
        family states none."""
        stated = getattr(self.family, "reference_kwargs", None)
        return dict(stated(self.config)) if stated else {}


def _reported(metrics: List[Dict[str, Any]], cell: str
              ) -> List[Dict[str, Any]]:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, bench: Dict[str, Any] | None = None,
              root: str = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(
            f"benchmark: no workload {name!r} in BENCHMARK.json (have "
            f"{[w['name'] for w in bench['workloads']]})")
    w = found[0]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=load_json(root, cfg_entry["file"]),
        traffic=load_json(tree(root, "traffic", w["traffic"] + ".json")),
        end_to_end=_reported(bench["end_to_end"], name),
        per_layer=_reported(bench["per_layer"], name), root=root)


def _load_module(kind: str, name: str, root: str = ROOT):
    """``<root>/benchmark/<kind>/<name>.py``, loaded once (the cache
    below sees `root` whether or not the caller gave it)."""
    return _load(kind, name, root)


@functools.lru_cache(maxsize=None)
def _load(kind: str, name: str, root: str):
    path = tree(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str):
    """The module ``benchmark/drivers/<name>.py``; it has ``run(ctx)``."""
    return _load_module("drivers", name)


def load_family(name: str, root: str = ROOT):
    """The module ``benchmark/families/<name>.py`` (the docstring of
    ``families/gpt2.py`` lists what a family file holds)."""
    return _load_module("families", name, root)


def load_reader(metric: str) -> Callable[[Any], Any]:
    """``read(run)`` of ``benchmark/metrics/<metric>.py``: the metric's
    value from one run's records, or None where there is nothing to
    read (the metric is then left out of the line).  A name with a
    suffix (``device_idle_share.chat``: the same quantity split by the
    end-to-end metric it moves) is read by its own file where there is
    one, else by the file of the name before the last dot."""
    base = metric.rsplit(".", 1)[0]
    own = os.path.isfile(os.path.join(HERE, "metrics", metric + ".py"))
    return _load_module("metrics", metric if own else base).read
