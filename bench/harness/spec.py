"""Find every part of a cell by its name in ``BENCHMARK.json``.

A cell (``workloads[i]``) names a configuration and a traffic mix.  Each
part lives in a file of its own under ``bench/``:

* configuration ``<c>``: the file named by its ``configs`` entry (sizes as
  run), whose ``reference`` key names ``bench/reference/<ref>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json`` (data only);
* cell ``<w>``: ``bench/cells/<w>.json`` (engine geometry, offered load,
  correctness limits);
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, or, for a metric
  split by suffix (``decode_step_ms.serve``), the reader of its stem
  (``bench/metrics/decode_step_ms.py``).

A later cell, mix, configuration or metric is added by adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a Python file by path (file names may hold dots)."""
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(BENCH_DIR)))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_file(name: str) -> Path:
    """The reader of a per-layer metric: its own file, else its stem's."""
    stem = name
    while True:
        path = BENCH_DIR / "metrics" / f"{stem}.py"
        if path.exists():
            return path
        if "." not in stem:
            raise FileNotFoundError(
                f"no reader for metric {name!r} under bench/metrics/")
        stem = stem.rsplit(".", 1)[0]


@dataclass
class Cell:
    """Everything one run of a cell needs, resolved from the spec."""
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic mix's contents
    cell: dict              # engine geometry, load and limits
    end_to_end: List[dict]  # metrics this cell reports with --trace 0
    per_layer: List[dict]   # metrics this cell reports with --trace 1
    reference: Path


def _reports(metric: dict, cell: str) -> bool:
    """An end-to-end metric without ``workloads`` (``setup_s``) is
    reported in every cell; a per-layer metric has to list its cells."""
    return cell in metric.get("workloads", [cell])


def resolve(name: str, spec_path: Optional[Path] = None) -> Cell:
    spec = load_json(spec_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    cell = load_json(BENCH_DIR / "cells" / f"{name}.json")
    missing = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    if missing:
        raise ValueError(f"per-layer metrics without `workloads`: {missing}")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, cell=cell, end_to_end=e2e,
                per_layer=per_layer,
                reference=BENCH_DIR / "reference" / f"{config['reference']}.py")
