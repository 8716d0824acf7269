"""A copy of the benchmark with one tiny extra cell, for CPU tests of
the harness: ``make_root(tmp)`` lays out ``BENCHMARK.json`` and the
benchmark's files under ``tmp`` and adds ``tiny-<traffic>`` cells of a
small ResNet, without touching any file of the repository."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Dict, Optional

from chipbench import spec

TINY_CONFIG = spec.BENCH_DIR / "testdata" / "tiny-resnet.json"


def tiny_params(**over: Any) -> Dict[str, Any]:
    p = json.loads((spec.BENCH_DIR / "cells" / "r18-steady.json")
                   .read_text(encoding="utf-8"))
    p.update(goal=4, queue_quota=8, pool_updates=4, pool_stride=7,
             rate_per_s=200.0, warm_rounds=2, check_rounds=2)
    p.update(over)
    return p


def make_root(tmp: Path, params: Optional[Dict[str, Any]] = None) -> Path:
    root = Path(tmp) / "checkout"
    bench = root / spec.REL
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    doc = json.loads((spec.ROOT / "BENCHMARK.json").read_text("utf-8"))
    doc["configs"].append({
        "name": "tiny-resnet", "source": "https://arxiv.org/abs/1512.03385",
        "file": str(spec.REL / "testdata" / "tiny-resnet.json"),
        "reduced": ["stage_blocks", "width", "num_classes"],
        "why": "CPU tests"})
    for traffic, metric, unit in (("backlog", "updates_per_s", "updates/s"),
                                  ("steady", "publish_p95_s", "s")):
        name = f"tiny-{traffic}"
        doc["workloads"].append({"name": name, "config": "tiny-resnet",
                                 "traffic": traffic, "chips": 1,
                                 "why": "CPU tests"})
        entry = next((m for m in doc["end_to_end"] if m["name"] == metric),
                     None)
        if entry is None:
            entry = {"name": metric, "unit": unit, "better": "lower",
                     "bound": 0.25, "source": "host_clock", "workloads": []}
            doc["end_to_end"].append(entry)
        entry["workloads"].append(name)
        (bench / "cells" / f"{name}.json").write_text(
            json.dumps(params or tiny_params()), "utf-8")
    (root / "BENCHMARK.json").write_text(json.dumps(doc), "utf-8")
    return root
