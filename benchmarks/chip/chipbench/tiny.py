"""A copy of the benchmark with tiny extra cells, for CPU tests of the
harness: ``make_root(tmp)`` lays out ``BENCHMARK.json`` and the
benchmark's files under ``tmp`` and adds the cells of ``TINY_CELLS``
(a small ResNet, and a small MLA + MoE model of the program's
registry), without touching any file of the repository."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Dict, Optional

from chipbench import spec

# configuration file under testdata/ -> its cells, (cell, traffic)
TINY_CELLS = {
    "tiny-resnet": (("tiny-backlog", "backlog"), ("tiny-steady", "steady")),
    "tiny-mla-moe": (("tiny-mla-moe-backlog", "backlog"),),
}
METRIC_OF = {"backlog": ("updates_per_s", "updates/s"),
             "steady": ("publish_p95_s", "s")}


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
    for stem, cells in TINY_CELLS.items():
        rel = spec.REL / "testdata" / f"{stem}.json"
        cfg = json.loads((root / rel).read_text("utf-8"))
        doc["configs"].append({"name": stem, "source": cfg["source"],
                               "file": str(rel), "reduced": cfg["reduced"],
                               "why": "CPU tests"})
        for name, traffic in cells:
            doc["workloads"].append({"name": name, "config": stem,
                                     "traffic": traffic, "chips": 1,
                                     "why": "CPU tests"})
            metric, unit = METRIC_OF[traffic]
            entry = next((m for m in doc["end_to_end"]
                          if m["name"] == metric), None)
            if entry is None:
                entry = {"name": metric, "unit": unit, "better": "lower",
                         "bound": 0.25, "source": "host_clock",
                         "workloads": []}
                doc["end_to_end"].append(entry)
            entry["workloads"].append(name)
            (bench / "cells" / f"{name}.json").write_text(
                json.dumps(params or tiny_params()), "utf-8")
    (root / "BENCHMARK.json").write_text(json.dumps(doc), "utf-8")
    return root
