"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell is an entry of ``workloads``.  Its parameters are
``cells/<cell>.json``, its configuration the ``file`` of its entry in
``configs``, its traffic mix ``traffic/<traffic>.json``, and each
per-layer metric a reader ``metrics/<name>.py`` (a metric named
``a.b`` is read by ``metrics/a.b.py`` when that exists, else by
``metrics/a.py``).  Adding a cell, a mix, a configuration or a metric
adds files and entries; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
REL = BENCH_DIR.relative_to(ROOT)


@dataclass
class Metric:
    name: str
    unit: str


@dataclass
class Cell:
    name: str
    chips: int
    params: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _load_json(path: Path) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _applies(entry: Dict[str, Any], cell: str,
             e2e_names: Optional[List[str]] = None) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    if e2e_names is None:            # an end-to-end metric with no list
        return True
    return entry["moves"] in e2e_names


def _metric(entry: Dict[str, Any]) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bench_dir = root / REL
    e2e = [_metric(m) for m in bench["end_to_end"] if _applies(m, name)]
    names = [m.name for m in e2e]
    layer = [_metric(m) for m in bench["per_layer"]
             if _applies(m, name, names)]
    return Cell(
        name=name, chips=int(entry["chips"]),
        params=_load_json(bench_dir / "cells" / f"{name}.json"),
        config=_load_json(root / cfg["file"]),
        traffic=_load_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=e2e, per_layer=layer)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """The ``read(ctx)`` function of a per-layer metric's own file."""
    candidates = [bench_dir / "metrics" / f"{name}.py",
                  bench_dir / "metrics" / f"{name.split('.', 1)[0]}.py"]
    path = next((p for p in candidates if p.exists()), None)
    if path is None:
        raise FileNotFoundError(f"no reader for metric {name!r} under "
                                f"{bench_dir / 'metrics'}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
