"""CPU tests of how the harness builds a configuration: a ResNet from
its own keys, exactly as before; any id of the program's model registry
from ``architecture`` and ``overrides``, added with new files and
appended entries alone; and a file whose stated size, override fields
or update dtype do not hold is refused before any round runs."""
import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, spec, tiny  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.models import build_model as build_arch  # noqa: E402

SEED = 2**32 + 12345
# sha256 of the tiny ResNet's initial parameters and of its update
# pool's buffer at SEED, as the harness made them before it built other
# architectures: the ResNet cells read the same params and pool
PARAMS_SHA = "286662c3f34e19cf279767326ca9304db7e9a1c69e041efc4a1ebb08d2e2248d"
POOL_SHA = "8acd70be6c333c3f68f6468896663f9e6d835adcbe44e686a05ebb1ca521a947"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench"))


def _add_cell(root, cfg, name):
    """A later change's configuration: one new file, appended entries."""
    rel = spec.REL / "configs" / f"{cfg['name']}.json"
    (root / rel).write_text(json.dumps(cfg), "utf-8")
    (root / spec.REL / "cells" / f"{name}.json").write_text(
        json.dumps(tiny.tiny_params()), "utf-8")
    doc = json.loads((root / "BENCHMARK.json").read_text("utf-8"))
    doc["configs"].append({"name": cfg["name"], "source": "t",
                           "file": str(rel), "reduced": [], "why": "t"})
    doc["workloads"].append({"name": name, "config": cfg["name"],
                             "traffic": "backlog", "chips": 1, "why": "t"})
    next(m for m in doc["end_to_end"]
         if m["name"] == "updates_per_s")["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(doc), "utf-8")


def test_tiny_resnet_builds_the_same_params_and_pool(root):
    cell = spec.load_cell("tiny-backlog", root)
    _model, shapes, n = harness.build_model(cell.config)
    p0 = harness.flat_host(harness.make_params(
        shapes, harness.seed32(SEED), float(cell.params["param_scale"])))
    base, _rows = harness.make_pool(n, cell.params, SEED)
    assert hashlib.sha256(p0.tobytes()).hexdigest() == PARAMS_SHA
    assert hashlib.sha256(base.tobytes()).hexdigest() == POOL_SHA


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_mla_moe_config_runs_the_whole_harness(root, control):
    rc, res = harness.run("tiny-mla-moe-backlog", SEED, 0.5, False,
                          root=root, require_chip=False, control=control)
    assert rc == 0 and res is not None and list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    c = res["checks"]
    assert 0 < c["param_gap"]["value"] <= c["param_gap"]["limit"]
    if control:
        ctl = c["control_param_gap"]
        assert ctl["value"] > ctl["limit"] and not res["correct"]
    else:
        assert res["correct"], c
        assert res["metrics"]["updates_per_s"]["value"] > 0


def _shrunk(arch):
    """Overrides that cut any registry architecture to a few thousand
    parameters, touching only the nested objects it has."""
    ov = {"num_layers": 2, "d_model": 32, "num_heads": 4,
          "num_kv_heads": 1 if arch.num_kv_heads == 1 else 2,
          "head_dim": 8, "d_ff": 64, "vocab_size": 256, "dtype": "float32"}
    if arch.encoder_layers:
        ov["encoder_layers"] = 1
    if arch.frontend_tokens:
        ov["frontend_tokens"] = 4
    if arch.moe is not None:
        ov["moe"] = {"num_experts": 4, "top_k": 2, "expert_d_ff": 16,
                     "first_moe_layer": min(arch.moe.first_moe_layer, 1)}
        if arch.moe.num_shared_experts:
            ov["moe"]["shared_d_ff"] = 16
        if arch.moe.dense_d_ff:
            ov["moe"]["dense_d_ff"] = 64
    if arch.mla is not None:
        ov["mla"] = {"kv_lora_rank": 8, "qk_nope_head_dim": 8,
                     "qk_rope_head_dim": 4, "v_head_dim": 8}
        if arch.mla.q_lora_rank:
            ov["mla"]["q_lora_rank"] = 8
    if arch.ssm is not None:
        ov["ssm"] = {"d_state": 4}
    return ov


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_every_registry_arch_is_added_by_files_and_entries(tmp_path,
                                                           arch_id):
    root = tiny.make_root(tmp_path)
    # every file but BENCHMARK.json, whose lists gain entries at the end
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    ov = _shrunk(ARCHS[arch_id])
    # what the program's own builder makes of the overridden config
    rep = {k: (dataclasses.replace(getattr(ARCHS[arch_id], k), **v)
               if isinstance(v, dict) else v) for k, v in ov.items()}
    want = jax.tree.leaves(jax.eval_shape(
        build_arch(dataclasses.replace(ARCHS[arch_id], **rep)).init,
        jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for s in want)
    cfg = {"name": f"tiny-{arch_id}", "architecture": arch_id,
           "overrides": ov, "n_params": n, "n_leaves": len(want),
           "update_dtype": "float32", "server_opt": "fedavg",
           "server_lr": 1.0}
    _add_cell(root, cfg, f"tiny-{arch_id}-backlog")
    cell = spec.load_cell(f"tiny-{arch_id}-backlog", root)
    model, shapes, got_n = harness.build_model(cell.config)
    assert got_n == n and model.cfg.num_layers == 2
    assert [s.shape for s in jax.tree.leaves(shapes)] == \
        [s.shape for s in want]
    assert {p: p.read_bytes() for p in before} == before


def _mla_moe(**change):
    cfg = json.loads((spec.BENCH_DIR / "testdata" / "tiny-mla-moe.json")
                     .read_text("utf-8"))
    cfg.update(change)
    return cfg


@pytest.mark.parametrize("change, words", [
    ({"n_params": 116321}, "116320 parameters in 27 leaves"),
    ({"n_leaves": 28}, "116320 parameters in 27 leaves"),
    ({"update_dtype": "bfloat16"}, "update_dtype 'bfloat16'"),
    ({"overrides": {"num_layerz": 2}}, "unknown override field 'num_layerz'"),
    ({"overrides": {"moe": {"num_expertz": 4}}},
     "unknown override field 'num_expertz'"),
    ({"overrides": {"ssm": {"d_state": 4}}}, "override field 'ssm'"),
], ids=["n_params", "n_leaves", "update_dtype", "field", "nested_field",
        "no_such_object"])
def test_a_config_that_does_not_hold_is_refused_before_any_round(
        tmp_path, capfd, change, words):
    root = tiny.make_root(tmp_path)
    _add_cell(root, _mla_moe(name="bad", **change), "bad-backlog")
    with pytest.raises(ValueError, match=words):
        harness.run("bad-backlog", SEED, 0.5, False, root=root,
                    require_chip=False)
    err = capfd.readouterr().err
    assert "warm round" not in err and "[window]" not in err
