"""Finds what ``BENCHMARK.json`` names: configuration, traffic and metric
files, by name, so that a later PR adds a cell or a metric by adding
files and entries, without editing any that exist.

* configuration ``<name>``: the ``file`` of its entry under ``configs``;
* traffic ``<name>``: ``bench/traffic/<name>.json``;
* per-layer metric ``<name>`` (or ``<name>.<suffix>``): the reader module
  ``bench/metrics/<name>.py``, whose ``read(record)`` returns the value or
  None when the run holds nothing to read;
* reference ``<name>`` (a configuration's ``reference``):
  ``bench/references/<name>.py``;
* peaks: ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def config(name: str, bm: dict = None) -> dict:
    bm = bm or benchmark()
    for c in bm["configs"]:
        if c["name"] == name:
            return _json(ROOT / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def reader(metric: str):
    """The reader module of a per-layer metric (suffix after '.' ignored)."""
    return importlib.import_module(f"bench.metrics.{metric.split('.')[0]}")


def reference(name: str):
    return importlib.import_module(f"bench.references.{name}")


def peaks(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json: no peaks, no roofline")
    return table[device_kind]


@dataclass
class Cell:
    name: str
    cfg: dict
    traffic: dict
    chips: int = 1
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Cell:
    bm = benchmark()
    for w in bm["workloads"]:
        if w["name"] == name:
            e2e = [m for m in bm["end_to_end"] if _reports(m, name)]
            moved = {m["name"] for m in e2e}
            # a per-layer metric without ``workloads`` goes wherever the
            # end-to-end metric it moves is reported
            layer = [m for m in bm["per_layer"]
                     if name in m.get("workloads", ())
                     or ("workloads" not in m and m["moves"] in moved)]
            return Cell(name=name, cfg=config(w["config"], bm),
                        traffic=traffic(w["traffic"]), chips=w["chips"],
                        end_to_end=e2e, per_layer=layer)
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")
