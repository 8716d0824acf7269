"""The comparison that decides ``correct``, rehearsed on the CPU at a
tiny size: a sound run passes it, the bfloat16 control fails it, and so
does each fault the cells can have, planted in the program underneath
an otherwise complete run."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, tiny  # noqa: E402

SECONDS = 0.5
SEED = 2**32 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench"))


def _run(root, cell="tiny-backlog", **kw):
    rc, res = harness.run(cell, SEED, SECONDS, False, root=root,
                          require_chip=False, **kw)
    assert rc == 0 and res is not None
    assert list(res)[-1] == "checks"
    return res


@pytest.mark.parametrize("cell", ["tiny-backlog", "tiny-steady"])
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert m["setup_s"]["value"] > 0
    key = "updates_per_s" if cell == "tiny-backlog" else "publish_p95_s"
    assert m[key]["value"] > 0
    assert res["checks"]["param_gap"]["value"] > 0


def test_bf16_control_is_not_correct(root):
    res = _run(root, control=True)
    c = res["checks"]
    assert c["param_gap"]["value"] <= c["param_gap"]["limit"]
    assert c["control_param_gap"]["value"] > c["control_param_gap"]["limit"]
    assert not res["correct"]


def _unchanged_server_step(monkeypatch):
    import repro.runtime.trainer as trainer

    monkeypatch.setattr(trainer, "apply_server_opt",
                        lambda name, params, state, delta, **kw:
                        (params, state))


def _half_the_batch(monkeypatch):
    from repro.core.aggregation import FedAvgState

    orig = FedAvgState.fold

    def fold(self, update, w):
        if self.count % 2 == 0:
            return orig(self, update, w)
        self.count += 1              # counted, never folded nor weighed

    monkeypatch.setattr(FedAvgState, "fold", fold)


def _altered_answer(monkeypatch):
    import repro.runtime.trainer as trainer

    orig = trainer.apply_server_opt

    def step(*a, **kw):
        params, state = orig(*a, **kw)
        leaves, treedef = jax.tree.flatten(params)
        leaves[0] = leaves[0].ravel().at[0].add(1e-2).reshape(
            leaves[0].shape)
        return jax.tree.unflatten(treedef, leaves), state

    monkeypatch.setattr(trainer, "apply_server_opt", step)


@pytest.mark.parametrize("fault", [_unchanged_server_step, _half_the_batch,
                                   _altered_answer])
def test_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root)
    assert not res["correct"]
    gap = res["checks"]["param_gap"]
    assert gap["value"] > gap["limit"]
