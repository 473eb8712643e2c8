"""Find a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by name, so a later PR adds a
cell by adding files and entries and edits nothing that is there:

- configuration: the ``file`` its ``configs`` entry names (JSON);
- traffic mix: ``perfbench/traffic/<traffic>.json``;
- plain reference: ``perfbench/reference/<config["reference"]>.py``;
- metric reader: ``perfbench/metrics/<metric name>.py``, a module with
  ``read(run) -> float | None``.

Every function takes the checkout root, so tests can point it at a
scratch tree.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import List, Optional

BENCH_DIR = "perfbench"
#: what a traffic mix may say; the harness runs each of these and
#: refuses a mix that asks for anything else (an open loop, say)
TRAFFIC_KEYS = {"inputs", "share_keys", "warmup_jobs_per_input",
                "traced_jobs", "why"}


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]   # metric entries this cell reports, trace 0
    per_layer: List[dict]    # metric entries this cell reports, trace 1


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str,
              bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    w = _named(bench["workloads"], workload, "workload")
    c = _named(bench["configs"], w["config"], "config")
    config = load_json(os.path.join(root, c["file"]))
    config.setdefault("name", c["name"])
    traffic = load_json(os.path.join(root, BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic {w['traffic']!r}: the harness does not "
                         f"run {sorted(unknown)}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric with no ``workloads`` key is reported wherever
    # the end-to-end metric it moves is
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def _load_module(path: str, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: str, name: str) -> ModuleType:
    """``perfbench/metrics/<name>.py``."""
    return _load_module(os.path.join(root, BENCH_DIR, "metrics",
                                     name + ".py"),
                        f"perfbench_metric_{name}")


def reference(root: str, name: str) -> ModuleType:
    return _load_module(os.path.join(root, BENCH_DIR, "reference",
                                     name + ".py"),
                        f"perfbench_reference_{name}")
