"""Fused FL round semantics on the host mesh: eager==lazy, server
optimizers, compression, metrics."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_opts
from repro.configs import ARCHS
from repro.fl.compression import dequantize_tree, quantize_tree
from repro.fl.round import AggregationConfig, accumulate_updates, build_train_step
from repro.fl.server import apply_server_opt, init_server_state
from repro.launch.mesh import make_host_mesh
from repro.models import build_model


def _setup(timing="eager", micro=4, opt="fedavg"):
    cfg = ARCHS["llama3.2-3b"].reduced(dtype="float32")
    mesh = make_host_mesh()
    agg = AggregationConfig(
        hierarchy="flat", timing=timing, num_microbatches=micro, server_opt=opt
    )
    step, model = build_train_step(cfg, mesh, agg, opts=tiny_opts(vocab_axis=None))
    return cfg, mesh, agg, step, model


def _batch(cfg, B=8, S=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(B, S))
    return {
        "tokens": jnp.asarray(toks, jnp.int32),
        "labels": jnp.asarray(np.roll(toks, -1, 1), jnp.int32),
    }


def test_eager_equals_lazy_aggregation():
    """The paper's precondition: eager (cumulative) and lazy (batch)
    produce the same aggregated update."""
    cfg, mesh, _, _, model = _setup()
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    with jax.set_mesh(mesh):
        de, we, _ = accumulate_updates(
            model, params, batch, AggregationConfig(timing="eager", num_microbatches=4)
        )
        dl, wl, _ = accumulate_updates(
            model, params, batch, AggregationConfig(timing="lazy", num_microbatches=4)
        )
    assert float(we) == float(wl)
    for a, b in zip(jax.tree.leaves(de), jax.tree.leaves(dl)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=1e-6)


def test_train_step_decreases_loss():
    cfg, mesh, agg, step, model = _setup(micro=2)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        state = init_server_state("fedavg", params)
        jstep = jax.jit(step)
        losses = []
        for r in range(8):
            params, state, m = jstep(params, state, _batch(cfg, seed=r % 2))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert m["updates_aggregated"] == 2
    assert float(m["update_norm"]) > 0


@pytest.mark.parametrize("opt", ["fedavg", "fedavgm", "fedadam"])
def test_server_optimizers_progress(opt):
    cfg, mesh, agg, step, model = _setup(opt=opt, micro=2)
    lr = {"fedavg": 1.0, "fedavgm": 0.7, "fedadam": 0.01}[opt]
    agg = dataclasses.replace(agg, server_lr=lr)
    step, model = build_train_step(cfg, mesh, agg, opts=tiny_opts(vocab_axis=None))
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        state = init_server_state(opt, params)
        jstep = jax.jit(step)
        losses = []
        for r in range(6):
            params, state, m = jstep(params, state, _batch(cfg, seed=0))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], (opt, losses)


def test_int8_tree_compression_roundtrip():
    tree = {
        "w": jnp.asarray(np.random.default_rng(0).normal(size=(33, 7)), jnp.float32),
        "b": jnp.asarray(np.random.default_rng(1).normal(size=(5,)), jnp.bfloat16),
    }
    qs, meta, treedef = quantize_tree(tree)
    back = dequantize_tree(qs, meta, treedef)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        a32 = np.asarray(a, np.float32)
        b32 = np.asarray(b, np.float32)
        denom = max(np.abs(a32).max(), 1e-6)
        assert np.abs(a32 - b32).max() / denom < 0.02  # <2% of block max


def test_server_opt_shapes_preserved():
    cfg, mesh, _, _, model = _setup()
    params = model.init(jax.random.PRNGKey(0))
    delta = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32) * 0.01, params)
    for opt in ("fedavg", "fedavgm", "fedadam"):
        st = init_server_state(opt, params)
        newp, st2 = apply_server_opt(opt, params, st, delta, lr=0.5)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(newp)):
            assert a.shape == b.shape and a.dtype == b.dtype
        assert int(st2["step"]) == 1
