"""Find a cell's files by name.

``BENCHMARK.json`` at the root of the checkout names every cell, config and
metric.  Everything that belongs to one of them sits in a file of its own
under this directory, found by name:

* ``configs/<file>.json`` (the config entry's ``file``) holds the sizes as
  they are run, and ``configs/<same stem>.py`` beside it the plain
  reference (``init`` and ``forward``);
* ``systems/<system>.py`` (the config's ``system``) holds the ``Driver``
  that builds and drives the system under test for such a config;
* ``traffic/<traffic>.json`` holds the parameters of a traffic mix, and
  ``traffic/<arrivals>.py`` (the mix's ``arrivals``) its arrival model;
* ``metrics/<metric>.py`` holds the reader of one per-layer metric
  (``read(ctx) -> float | None``).

A later PR adds a cell, a mix, an arrival model, a system or a metric by
adding such files and entries, with no edit to this code.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything found by name."""

    name: str
    entry: Dict[str, Any]
    config: Dict[str, Any]
    config_path: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @property
    def reference_path(self) -> str:
        return os.path.splitext(self.config_path)[0] + ".py"

    def reference(self):
        """The config's plain reference module (``init``, ``forward``)."""
        return load_module(self.reference_path)

    def _file(self, *parts: str) -> str:
        return os.path.join(self.root, os.path.relpath(BENCH_DIR, ROOT), *parts)

    def metric_reader(self, name: str) -> Callable[[Any], Optional[float]]:
        return load_module(self._file("metrics", name + ".py")).read

    def arrivals(self):
        """The mix's arrival model (``schedule(p, seed, seconds)``)."""
        return load_module(self._file("traffic", self.traffic["arrivals"] + ".py"))

    def driver(self):
        """The ``Driver`` class of the config's system."""
        return load_module(self._file("systems", self.config["system"] + ".py")).Driver


def load_module(path: str):
    """Import a file by path (names may hold dots, so not by module name)."""
    key = "bench_file:" + os.path.abspath(path)
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], workload: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def find(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``root``'s ``BENCHMARK.json``."""
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r}; have {sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    config_path = os.path.join(root, cfg_entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    bench_rel = os.path.relpath(BENCH_DIR, ROOT)
    with open(os.path.join(root, bench_rel, "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, [])]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, reported)]
    return Cell(
        name=workload, entry=entry, config=config, config_path=config_path,
        traffic=traffic, end_to_end=e2e, per_layer=per_layer, root=root,
    )


def merged(base: Dict[str, Any], override: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """``base`` with ``override``'s keys replaced, nested dicts merged (the
    test hook shrinks a cell this way; runs never override)."""
    out = dict(base)
    for k, v in (override or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = v
    return out
