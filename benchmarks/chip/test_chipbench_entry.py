"""CPU tests of the benchmark's entry and of how it finds its files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spec, tiny  # noqa: E402

RUN = spec.BENCH_DIR / "run.py"


def _run(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, str(cwd / spec.REL / "run.py"), "--workload",
         "r18-backlog", "--seed", "3", "--seconds", "1", "--trace", "0",
         *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cpu_only_machine_exits_nonzero_before_measuring():
    out = _run(spec.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
    assert "[window]" not in out.stderr and "[setup]" not in out.stderr


def test_bare_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.BENCH_DIR, tmp_path / spec.REL,
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    out = _run(tmp_path, env_extra={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_every_cell_in_the_benchmark_has_its_files():
    doc = json.loads((spec.ROOT / "BENCHMARK.json").read_text("utf-8"))
    for w in doc["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m.name))


def test_new_config_traffic_cell_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = root / spec.REL
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    # a later change adds only files and entries
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"arrivals": "poisson", "close_out": "goal", "block": 64}), "utf-8")
    (bench / "cells" / "tiny-trickle.json").write_text(json.dumps(
        tiny.tiny_params(rate_per_s=3.0)), "utf-8")
    (bench / "metrics" / "queue_depth.py").write_text(
        "def read(ctx):\n    return 42.0\n", "utf-8")
    doc = json.loads((root / "BENCHMARK.json").read_text("utf-8"))
    doc["workloads"].append({"name": "tiny-trickle", "config": "tiny-resnet",
                             "traffic": "trickle", "chips": 1, "why": "t"})
    doc["per_layer"].append({"name": "queue_depth", "unit": "updates",
                             "better": "lower", "source": "program_counter",
                             "layer": "gateway", "moves": "publish_p95_s",
                             "workloads": ["tiny-trickle"]})
    next(m for m in doc["end_to_end"]
         if m["name"] == "publish_p95_s")["workloads"].append("tiny-trickle")
    (root / "BENCHMARK.json").write_text(json.dumps(doc), "utf-8")
    cell = spec.load_cell("tiny-trickle", root)
    assert cell.traffic["block"] == 64 and cell.params["rate_per_s"] == 3.0
    assert cell.config["name"] == "tiny-resnet"
    assert [m.name for m in cell.per_layer] == ["queue_depth"]
    assert {m.name for m in cell.end_to_end} == {"publish_p95_s", "setup_s"}
    assert spec.metric_reader("queue_depth", bench)(None) == 42.0
    # a split metric name falls back to its base reader
    assert spec.metric_reader("queue_depth.steady", bench)(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
