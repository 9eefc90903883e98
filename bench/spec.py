"""Find what a cell needs by name: its entry in ``BENCHMARK.json``, its
configuration, its traffic mix, its serving settings and its metrics.

Nothing here knows a cell, configuration or metric by name.  A later
change adds one by adding files and an entry:

* ``configs/<config>.json``  — the model as it is run;
* ``traffic/<traffic>.json`` — the parameters of a traffic mix;
* ``cells/<workload>.json``  — engine settings, offered rate, ramp and
  the limits of the correctness check;
* ``metrics/<metric>.py``    — one reader per metric.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Any]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    settings: Dict[str, Any]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_reader(bench_dir: Path, name: str) -> Callable[[Any], Any]:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(bench_dir: Path, entries: List[Dict[str, Any]],
             workload: str) -> List[Metric]:
    """The entries whose ``workloads`` (all cells, when absent) hold
    ``workload``, each with its reader."""
    return [Metric(e["name"], e["unit"], load_reader(bench_dir, e["name"]))
            for e in entries
            if workload in e.get("workloads", [workload])]


def load_cell(workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``workload`` of the ``BENCHMARK.json`` beside
    ``bench_dir``, with every file it names loaded."""
    top = _json(bench_dir.parent / "BENCHMARK.json")
    entry = next((w for w in top["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        known = [w["name"] for w in top["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; known: {known}")
    conf = next(c for c in top["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_json(bench_dir.parent / conf["file"]),
        traffic=_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        settings=_json(bench_dir / "cells" / f"{workload}.json"),
        end_to_end=_metrics(bench_dir, top["end_to_end"], workload),
        per_layer=_metrics(bench_dir, top["per_layer"], workload))


def load_peaks(kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    """Peaks of the device JAX calls ``kind``; an unknown kind raises."""
    table = _json(bench_dir / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]
