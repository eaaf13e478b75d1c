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

    @property
    def family(self):
        """``benchmark/families/<program.family>.py``."""
        return load_family(self.config["program"]["family"])

    @property
    def reference(self):
        """The family's plain reference, ``benchmark/reference/*.py``."""
        return _load_module("reference", self.family.REFERENCE)


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
        traffic=load_json(HERE, "traffic", w["traffic"] + ".json"),
        end_to_end=_reported(bench["end_to_end"], name),
        per_layer=_reported(bench["per_layer"], name))


@functools.lru_cache(maxsize=None)
def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
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


def load_family(name: str):
    """The module ``benchmark/families/<name>.py`` (its docstring lists
    what a family file holds)."""
    return _load_module("families", name)


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
