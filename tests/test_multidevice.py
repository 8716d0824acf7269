"""Multi-device semantics, via subprocesses (XLA_FLAGS must be set
before jax import, so these tests don't share the test process)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_forced(code: str, ndev: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_moe_ep_matches_dense_dispatch():
    """Expert-parallel shard_map dispatch == dense oracle (no drops at
    high capacity factor)."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.launch.mesh import make_debug_mesh
        from repro.configs import ARCHS
        from repro.models import moe as moe_mod

        mesh = make_debug_mesh((2,2), ('data','model'))
        cfg = ARCHS['deepseek-v2-lite-16b'].reduced(dtype='float32')
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
        key = jax.random.PRNGKey(0)
        params = moe_mod.init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        with jax.set_mesh(mesh):
            yd, _ = moe_mod.moe_block(cfg, params, x, impl='dense')
            ye, _ = moe_mod.moe_block(cfg, params, x, impl='ep',
                                       dp_axes=('data',), model_axis='model')
        err = float(jnp.max(jnp.abs(yd - ye)))
        rel = err / float(jnp.max(jnp.abs(yd)))
        print('REL', rel)
        assert rel < 2e-4, rel
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_vocab_matches_dense():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_debug_mesh
        from repro.models.sharded_vocab import (
            chunked_lm_loss_sharded, decode_logits, embed_lookup)

        mesh = make_debug_mesh((2,2), ('data','model'))
        V, D, B, S = 512, 16, 4, 16
        key = jax.random.PRNGKey(0)
        table = jax.random.normal(key, (V, D), jnp.float32) * 0.05
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V - 7)
        hid = jax.random.normal(jax.random.PRNGKey(2), (B, S, D))
        labels = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, V - 7)
        with jax.set_mesh(mesh):
            # production paths are always jitted (eager shard_map with
            # partial-manual axes rejects unmentioned auto axes)
            e_sh = jax.jit(lambda t, k: embed_lookup(t, k, 'model'))(table, toks)
            e_dn = jnp.take(table, toks, axis=0)
            assert float(jnp.max(jnp.abs(e_sh - e_dn))) < 1e-5

            ce_sh = jax.jit(lambda h, t, y: chunked_lm_loss_sharded(
                h, t, y, vocab=V-7, tied=True, model_axis='model', chunk=8))
            ce_dn = jax.jit(lambda h, t, y: chunked_lm_loss_sharded(
                h, t, y, vocab=V-7, tied=True, model_axis=None, chunk=8))
            l_sh = ce_sh(hid, table, labels)
            l_dn = ce_dn(hid, table, labels)
            assert abs(float(l_sh) - float(l_dn)) < 1e-4, (float(l_sh), float(l_dn))

            g_sh = jax.jit(jax.grad(lambda t: chunked_lm_loss_sharded(
                hid, t, labels, vocab=V-7, tied=True, model_axis='model',
                chunk=8)))(table)
            g_dn = jax.jit(jax.grad(lambda t: chunked_lm_loss_sharded(
                hid, t, labels, vocab=V-7, tied=True, model_axis=None,
                chunk=8)))(table)
            assert float(jnp.max(jnp.abs(g_sh - g_dn))) < 1e-5

            d_sh = jax.jit(lambda h, t: decode_logits(
                h, t, vocab=V-7, tied=True, model_axis='model'))(hid[:, :1], table)
            d_dn = decode_logits(hid[:, :1], table, vocab=V-7, tied=True,
                                  model_axis=None)
            assert float(jnp.max(jnp.abs(d_sh - d_dn))) < 1e-4
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_hierarchical_equals_flat_aggregation_numerics():
    """LIFL hierarchical (manual-pod) round == flat GSPMD round: the
    schedule changes, the math must not."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from functools import partial
        from repro.configs import ARCHS, ShapeConfig
        from repro.fl.round import (AggregationConfig, build_train_step,
            input_specs, train_shardings, abstract_params)
        from repro.fl.server import init_server_state
        from repro.launch.mesh import make_debug_mesh, dp_axes as mdp
        from repro.sharding import batch_specs, divisibility_fix, to_named

        mesh = make_debug_mesh((2,2,2), ('pod','data','model'))
        cfg = ARCHS['llama3.2-3b'].reduced(dtype='float32')
        dp = mdp(mesh)
        rng = np.random.default_rng(0)
        B, S = 8, 16
        toks = rng.integers(0, cfg.vocab_size, size=(B, S))
        batch = {'tokens': jnp.asarray(toks, jnp.int32),
                 'labels': jnp.asarray(np.roll(toks, -1, 1), jnp.int32)}
        results = {}
        with jax.set_mesh(mesh):
            for hier in ('flat', 'hierarchical'):
                agg = AggregationConfig(hierarchy=hier, num_microbatches=2)
                step, model = build_train_step(cfg, mesh, agg)
                params = model.init(jax.random.PRNGKey(0))
                state = init_server_state('fedavg', params)
                p2, s2, m = jax.jit(step)(params, state, batch)
                results[hier] = (jax.tree.map(np.asarray, p2), float(m['loss']))
        pf, lf = results['flat']
        ph, lh = results['hierarchical']
        assert abs(lf - lh) < 1e-4, (lf, lh)
        errs = [float(np.max(np.abs(a - b)))
                for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(ph))]
        assert max(errs) < 5e-5, max(errs)
        print('OK flat==hier, loss', lf)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_int8_pod_compression_small_error():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.fl.round import AggregationConfig, build_train_step
        from repro.fl.server import init_server_state
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh((2,2,2), ('pod','data','model'))
        cfg = ARCHS['llama3.2-3b'].reduced(dtype='float32')
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size, size=(8, 16))
        batch = {'tokens': jnp.asarray(toks, jnp.int32),
                 'labels': jnp.asarray(np.roll(toks, -1, 1), jnp.int32)}
        outs = {}
        with jax.set_mesh(mesh):
            for comp in ('none', 'int8'):
                agg = AggregationConfig(hierarchy='hierarchical',
                                         compress=comp, num_microbatches=2)
                step, model = build_train_step(cfg, mesh, agg)
                params = model.init(jax.random.PRNGKey(0))
                state = init_server_state('fedavg', params)
                p2, _, m = jax.jit(step)(params, state, batch)
                outs[comp] = jax.tree.map(np.asarray, p2)
        rel = max(
            float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9))
            for a, b in zip(jax.tree.leaves(outs['none']),
                            jax.tree.leaves(outs['int8'])))
        print('rel', rel)
        assert rel < 0.05, rel
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_mini_dryrun_cell():
    """A miniature of the production dry-run path: lower + compile +
    memory/cost/collective extraction on a (2,2,2) mesh."""
    out = run_forced("""
        import jax
        from functools import partial
        from repro.analysis.hlo_cost import parse_hlo_cost
        from repro.configs import ARCHS, ShapeConfig
        from repro.fl.round import (AggregationConfig, abstract_params,
            build_train_step, input_specs, train_shardings)
        from repro.fl.server import init_server_state
        from repro.launch.mesh import make_debug_mesh, dp_axes as mdp
        from repro.sharding import batch_specs, divisibility_fix, to_named

        mesh = make_debug_mesh((2,2,2), ('pod','data','model'))
        cfg = ARCHS['gemma3-4b'].reduced()
        shape = ShapeConfig('t', 64, 8, 'train')
        agg = AggregationConfig(num_microbatches=2)
        dp = mdp(mesh)
        with jax.set_mesh(mesh):
            step, model = build_train_step(cfg, mesh, agg)
            ap = abstract_params(model)
            ps, ss = train_shardings(model, mesh, agg)
            ast = jax.eval_shape(partial(init_server_state, 'fedavg'), ap)
            ab = input_specs(cfg, shape)
            bs = divisibility_fix(batch_specs(ab, dp), ab, mesh)
            fn = jax.jit(step,
                in_shardings=(to_named(ps, mesh), to_named(ss, mesh),
                              to_named(bs, mesh)),
                out_shardings=(to_named(ps, mesh), to_named(ss, mesh), None),
                donate_argnums=(0, 1))
            compiled = fn.lower(ap, ast, ab).compile()
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes > 0
        hc = parse_hlo_cost(compiled.as_text(), pod_size=4)
        assert hc.flops > 0 and hc.bytes_ > 0
        assert hc.coll_total > 0 and hc.coll_dcn > 0  # pod hop crosses DCN
        print('OK', hc.flops)
    """)
    assert "OK" in out
