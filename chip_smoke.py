"""Chip smoke test: the LIFL aggregation path on one TPU, end to end.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4 [--seed 0]

One process owns the chip and runs every phase; any failure exits
non-zero, and only a run that passed everything prints its verdict as
the last line of stdout:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

Phases on one chip:

1. device — ``jax.devices()`` must be TPUs; there is no CPU branch.
2. kernels at the ResNet-18 update size — ``fedavg_accumulate_k``
   (K=8, f32 and bf16 rows), ``eager_accumulate``, ``quantize`` and
   ``dequantize`` with ``impl="pallas"`` against a float64 numpy
   oracle; each compiled program must hold a ``tpu_custom_call``.
3. Session rounds at full ResNet-18 width — ``agg_engine="auto"`` must
   resolve to the compiled Pallas ``JaxEngine``; three client rounds
   (two ``submit_update`` calls in the first) plus one round fed only
   by ``submit_update``, checked against ``fedavg_oracle`` and against
   the same rounds re-run with the host fold (``agg_engine="blocked"``).
4. host runtimes beside the chip owner — one round on ``shmproc`` and
   one over two local ``netd`` daemons must equal the host-fold round
   bit for bit; no daemon may import jax, and no ``lifl`` shared-memory
   segment may remain.

``--chips 4`` runs only the fused hierarchical FedAvg step
(``fl/round.build_train_step``) on a (pod=2, data=2) mesh over four
chips with the ~100M-parameter LM of ``examples/train_fl_lm.py``, and
compares it with the flat step on the same mesh.

Every input is generated from ``--seed``.  Timings printed on the way
are informational, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "examples"))

# Every round folds its whole cohort (goal = clients, no over-
# provisioning): placement reacts to measured fold times, so a device-
# fold session and a host-fold session may group updates differently
# from round 1 on, but they always fold the same updates.
N_CLIENTS = 8
GOAL = N_CLIENTS
CAPACITY = 10.0          # per node; worstfit spreads a round over both
BATCH = 32               # every client batch has this shape: one compile
CLIENT_LR = 0.05
SAMPLES_PER_CLIENT = 96
NODES = ("nodeA", "nodeB")
F32_EPS = float(np.finfo(np.float32).eps)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"[device] devices={devs}")
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform "
                         f"{d0.platform!r}); this script runs on the chip only")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                         f"found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class CompileWatch:
    """Backend compile seconds and persistent-cache hits, from JAX's
    own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compile_s, self.cache_hits


# ---------------------------------------------------------------------------
# phase 2: the kernels at the real update size
# ---------------------------------------------------------------------------


def _assert_compiled_kernel(fn, *args, **static) -> None:
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text, (
        f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
        f"compiled program — the kernel was not compiled for the chip")


def _check_f32_fold(name: str, got: np.ndarray, ref: np.ndarray,
                    mag: np.ndarray, terms: int) -> None:
    """f32 accumulation bound: each of ``terms`` roundings costs at most
    one f32 epsilon of the running magnitude ``mag`` (Σ|terms|)."""
    err = np.abs(got.astype(np.float64) - ref)
    bound = terms * F32_EPS * mag + 1e-30
    ratio = float(np.max(err / bound))
    log(f"[kernels] {name}: max_abs_err={float(err.max()):.3e} "
        f"max_err/bound={ratio:.3f}")
    assert ratio <= 1.0, f"{name}: error beyond the f32 accumulation bound"


def phase_kernels(n: int, seed: int, impl: str = "pallas",
                  compiled_check: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.fedavg import eager_accumulate, fedavg_accumulate_k
    from repro.kernels.quantize import QBLOCK, dequantize, quantize

    K = 8
    rng = np.random.default_rng(seed)
    acc0 = rng.standard_normal(n, dtype=np.float32)
    U = rng.standard_normal((K, n), dtype=np.float32)
    W = rng.uniform(0.5, 4.0, size=K).astype(np.float32)
    log(f"[kernels] N={n} K={K} impl={impl}")

    for dtype in (jnp.float32, jnp.bfloat16):
        u_dev = jnp.asarray(U).astype(dtype)
        rows = np.asarray(u_dev.astype(jnp.float32))  # the rounded inputs
        ref = acc0.astype(np.float64)
        mag = np.abs(ref)
        for k in range(K):
            term = np.float64(W[k]) * rows[k]
            ref += term
            mag += np.abs(term)
        t0 = time.perf_counter()
        out = fedavg_accumulate_k(jnp.asarray(acc0), u_dev, jnp.asarray(W),
                                  impl=impl)
        out = np.asarray(jax.block_until_ready(out))
        log(f"[kernels] fedavg_accumulate_k {jnp.dtype(dtype).name}: "
            f"{time.perf_counter() - t0:.3f}s incl. compile")
        _check_f32_fold(f"fedavg_accumulate_k[{jnp.dtype(dtype).name}]",
                        out, ref, mag, K + 1)
        if compiled_check:
            _assert_compiled_kernel(
                fedavg_accumulate_k, jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((K, n), dtype),
                jax.ShapeDtypeStruct((K,), jnp.float32), impl=impl)
        del u_dev, rows, ref, mag, out

    ref = acc0.astype(np.float64) + np.float64(W[0]) * U[0]
    mag = np.abs(acc0.astype(np.float64)) + np.abs(np.float64(W[0]) * U[0])
    out = eager_accumulate(jnp.asarray(acc0), jnp.asarray(U[0]), W[0],
                           impl=impl)
    _check_f32_fold("eager_accumulate", np.asarray(out), ref, mag, 2)
    if compiled_check:
        _assert_compiled_kernel(
            eager_accumulate, jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32), impl=impl)

    # int8 block quantization: pallas against the jnp twin on the same
    # device, and both against the per-block error bound
    x = U[1] * np.float32(3.0)
    q, s = quantize(jnp.asarray(x), impl=impl)
    q_ref, s_ref = quantize(jnp.asarray(x), impl="jnp")
    q, s = np.asarray(q), np.asarray(s)
    np.testing.assert_allclose(s, np.asarray(s_ref), rtol=1e-6)
    q_diff = np.abs(q.astype(np.int32) - np.asarray(q_ref).astype(np.int32))
    log(f"[kernels] quantize: blocks={s.size} "
        f"q_mismatch={int(np.count_nonzero(q_diff))} "
        f"max_q_diff={int(q_diff.max())}")
    assert q_diff.max() <= 1, "quantize: pallas and jnp disagree by > 1 step"
    back = np.asarray(dequantize(jnp.asarray(q), jnp.asarray(s), n,
                                 impl=impl))
    np.testing.assert_array_equal(
        back, (q.astype(np.float32) * s[:, None]).reshape(-1)[:n])
    scales = np.repeat(s, QBLOCK)[:n]
    err = np.abs(back.astype(np.float64) - x)
    ratio = float(np.max(err / (scales * (0.5 + 1e-3))))
    log(f"[kernels] dequantize: max_err/(scale/2)={ratio:.4f}")
    assert ratio <= 1.0, "dequantize: error beyond half a quantization step"
    if compiled_check:
        nb = s.size
        _assert_compiled_kernel(quantize,
                                jax.ShapeDtypeStruct((n,), jnp.float32),
                                impl=impl)
        _assert_compiled_kernel(dequantize,
                                jax.ShapeDtypeStruct((nb, QBLOCK), jnp.int8),
                                jax.ShapeDtypeStruct((nb,), jnp.float32),
                                n=n, impl=impl)
    log("[kernels] ok")


# ---------------------------------------------------------------------------
# the workload: ResNet-18 clients over synthetic FEMNIST, from the seed
# ---------------------------------------------------------------------------


class JittedModel:
    """The user's model object with its loss jitted once, so each
    client step runs as one compiled program."""

    def __init__(self, model) -> None:
        import jax

        self.loss = jax.jit(model.loss)


def build_workload(cfg, seed: int):
    """-> (model, params, clients factory, flat update size)."""
    import jax

    from repro.core import ClientInfo
    from repro.data import (build_client_datasets, dirichlet_partition,
                            synthetic_femnist)
    from repro.data.partition import ClientShard
    from repro.models import build_resnet
    from repro.runtime import ClientRuntime

    model = build_resnet(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    n = int(sum(np.prod(np.shape(l)) for l in jax.tree.leaves(params)))
    imgs, labels = synthetic_femnist(N_CLIENTS * SAMPLES_PER_CLIENT,
                                     num_classes=cfg.num_classes,
                                     image_size=cfg.image_size, seed=seed)
    shards = dirichlet_partition(labels, N_CLIENTS, alpha=1.0, seed=seed,
                                 min_samples=BATCH)
    # one batch per client: a client's update does not depend on the
    # order the round trains its cohort in (that order follows the
    # placement, which follows measured fold times), and every client
    # step has one shape, so one compile
    assert all(s.num_samples >= BATCH for s in shards), shards
    shards = [ClientShard(s.client_id, s.indices[:BATCH]) for s in shards]
    datasets = build_client_datasets(imgs, labels, shards)

    def clients():
        return [ClientRuntime(ClientInfo(d.client_id, d.num_samples), d)
                for d in datasets]

    return JittedModel(model), params, clients, n


def external_updates(n: int, seed: int, count: int):
    """Seeded externally-computed updates: (client_id, flat, weight)."""
    rng = np.random.default_rng(seed + 1)
    return [(f"edge-{i}", rng.standard_normal(n, dtype=np.float32)
             * np.float32(1e-3), float(rng.integers(16, 128)))
            for i in range(count)]


def flat_params(params) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(l, np.float32).reshape(-1)
                           for l in jax.tree.leaves(params)])


def round_config():
    from repro.core import RoundConfig

    return RoundConfig(aggregation_goal=GOAL, over_provision=1.0,
                       placement_policy="worstfit", topology="controller")


def node_states():
    from repro.core import NodeState

    return {name: NodeState(node=name, max_capacity=CAPACITY)
            for name in NODES}


# ---------------------------------------------------------------------------
# phase 3: Session rounds
# ---------------------------------------------------------------------------


def run_session(workload, *, engine: str, seed: int, rounds: int,
                ext=(), ext_only_round: bool = False, runtime="inproc",
                nodes=None, watch=None, label: str = "", inspect=None):
    """Drive ``rounds`` client rounds (the first two externals ride the
    first one, whose placement no measurement has touched yet) and
    optionally one externals-only round.  Returns the
    flat params after each round, and the externals-only round's
    (params before, params after, TopFolded event)."""
    from repro.api import Session
    from repro.runtime.events import TopFolded

    model, params, clients, _n = workload
    out = []
    folded = []
    ext_round = None
    with Session.open(model, params, clients(), runtime=runtime,
                      nodes=nodes if nodes is not None else node_states(),
                      round_cfg=round_config(), agg_engine=engine,
                      seed=seed) as s:
        s.on(TopFolded, folded.append)
        for r in range(rounds):
            if r == 0:
                for cid, u, w in ext[:2]:
                    assert s.submit_update(cid, u, weight=w)
            c0 = watch.snapshot() if watch else (0.0, 0)
            rec = s.run_round(client_lr=CLIENT_LR, client_batch_size=BATCH)
            c1 = watch.snapshot() if watch else (0.0, 0)
            out.append(flat_params(s.params))
            log(f"[{label}] round {r}: updates={rec['updates']:.0f} "
                f"wall_s={rec['wall_s']:.3f} "
                f"compile_s={c1[0] - c0[0]:.3f} "
                f"cache_hits={c1[1] - c0[1]}")
            assert rec["updates"] == GOAL, rec
        if ext_only_round:
            before = out[-1]
            for cid, u, w in ext[2:2 + GOAL]:
                assert s.submit_update(cid, u, weight=w)
            rec = s.run_round(client_lr=CLIENT_LR, client_batch_size=BATCH)
            after = flat_params(s.params)
            log(f"[{label}] externals-only round: "
                f"updates={rec['updates']:.0f} wall_s={rec['wall_s']:.3f}")
            ext_round = (before, after, folded[-1])
        if inspect is not None:
            inspect(s)
    return out, ext_round


def check_externals_round(label: str, ext_round, ext) -> None:
    """The externals-only round folded exactly those updates:
    params_after == params_before + fedavg_oracle(updates)."""
    from repro.core.aggregation import fedavg_oracle

    before, after, top = ext_round
    ups = [u for _c, u, _w in ext[2:2 + GOAL]]
    ws = [w for _c, _u, w in ext[2:2 + GOAL]]
    assert top.count == GOAL and abs(top.weight - sum(ws)) < 1e-6, top
    expect = before + fedavg_oracle(ups, ws)
    err = float(np.max(np.abs(after - expect)))
    log(f"[{label}] externals-only round vs fedavg_oracle: "
        f"max_abs_err={err:.3e}")
    np.testing.assert_allclose(after, expect, rtol=1e-5, atol=1e-6)


def phase_session(workload, seed: int, *, engine: str = "auto",
                  expect_impl: str = "pallas", watch=None):
    """-> (device-fold round params, host-fold round params)."""
    from repro.core.engine import JaxEngine, make_engine

    n = workload[3]
    ext = external_updates(n, seed, 2 + GOAL)
    auto = make_engine(engine)
    assert isinstance(auto, JaxEngine) and auto.impl == expect_impl, (
        f"agg_engine={engine!r} resolved to {auto.name!r}, "
        f"not the {expect_impl} JaxEngine")
    log(f"[session] agg_engine={engine!r} -> JaxEngine(impl={auto.impl!r}); "
        f"update size {n} f32 ({4 * n / 2**20:.1f} MiB)")

    def inspect(s):
        engines = list(s.trainer._runtime._engines.values())
        assert engines and all(isinstance(e, JaxEngine)
                               and e.impl == expect_impl for e in engines)
        folds = sum(e.fold_calls for e in engines)
        elems = sum(e.elements_folded for e in engines)
        log(f"[session] {len(engines)} {expect_impl} engines: "
            f"fold_calls={folds} elements_folded={elems}")
        assert folds > 0 and elems > 0, "the device engines folded nothing"

    dev, dev_ext = run_session(workload, engine=engine, seed=seed, rounds=3,
                               ext=ext, ext_only_round=True, watch=watch,
                               label="session:device", inspect=inspect)
    host, host_ext = run_session(workload, engine="blocked", seed=seed,
                                 rounds=3, ext=ext, ext_only_round=True,
                                 watch=watch, label="session:host")
    # round 0 trains both cohorts from the same params, so the params
    # differ by fold rounding alone; later rounds train from params
    # that already differ by it, and training amplifies a little
    for r, (a, b) in enumerate(zip(dev, host)):
        err = float(np.max(np.abs(a - b)))
        log(f"[session] round {r}: device vs host fold max_abs_diff={err:.3e}")
        if r == 0:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    check_externals_round("session:device", dev_ext, ext)
    check_externals_round("session:host", host_ext, ext)
    log("[session] ok")
    return dev, host


# ---------------------------------------------------------------------------
# phase 4: host-side runtimes beside the chip owner
# ---------------------------------------------------------------------------


def _lifl_segments(prefixes=("lifl",)):
    """/dev/shm entries under ``prefixes`` (every runtime's segments
    start with ``lifl``)."""
    try:
        names = os.listdir("/dev/shm")
    except OSError:
        return []
    return sorted(f for f in names if f.startswith(tuple(prefixes)))


def phase_host_runtimes(workload, seed: int, host_round: np.ndarray,
                        device_round: np.ndarray) -> None:
    from repro.runtime.netrt import reap_local_daemon, spawn_local_daemon

    ext = external_updates(workload[3], seed, 2)   # round 0's externals
    prefixes = []
    shm, _ = run_session(
        workload, engine="blocked", seed=seed, rounds=1, ext=ext,
        runtime="shmproc", label="shmproc",
        inspect=lambda s: prefixes.append(s.trainer._runtime.store_prefix))
    np.testing.assert_array_equal(shm[0], host_round)
    log("[shmproc] round 0 == host-fold inproc round 0 (bit for bit)")

    daemons = []
    try:
        for name in NODES:
            daemons.append(spawn_local_daemon(name, runtime="shmproc",
                                              capacity=CAPACITY))
            prefixes.append(daemons[-1][0].lifl_store_prefix)
        stats = {}

        def inspect(s):
            stats.update(s.trainer._runtime.poll_stats())

        net, _ = run_session(workload, engine="blocked", seed=seed, rounds=1,
                             ext=ext, nodes=[addr for _p, addr in daemons],
                             label="netd", inspect=inspect)
    finally:
        for proc, _addr in daemons:
            proc.terminate()
        for proc, _addr in daemons:
            proc.wait(timeout=30)
            reap_local_daemon(proc)
    np.testing.assert_array_equal(net[0], host_round)
    log("[netd] round 0 == host-fold inproc round 0 (bit for bit)")
    assert set(stats) == set(NODES), stats
    for name, reply in sorted(stats.items()):
        loaded = reply["health"]["jax_imported"]
        log(f"[netd] {name}: jax_imported={loaded} "
            f"workers={reply['workers']}")
        assert loaded is False, f"netd {name} imported jax"
    err = float(np.max(np.abs(net[0] - device_round)))
    log(f"[host-runtimes] host rounds vs device-fold round 0: "
        f"max_abs_diff={err:.3e}")
    np.testing.assert_allclose(net[0], device_round, rtol=1e-5, atol=1e-6)
    left = _lifl_segments(prefixes)
    assert not left, f"/dev/shm still holds {left}"
    log(f"[host-runtimes] ok: no segment left under {prefixes}")


# ---------------------------------------------------------------------------
# --chips 4: the fused hierarchical FedAvg step across the mesh
# ---------------------------------------------------------------------------


def bytes_in_use():
    import jax

    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


def four_chip_step(cfg, seed: int, batch: int = 8, seq: int = 128) -> None:
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro.fl.round import (AggregationConfig, build_train_step,
                                input_specs, train_shardings)
    from repro.fl.server import init_server_state
    from repro.configs import ShapeConfig
    from repro.launch.mesh import dp_axes, make_debug_mesh
    from repro.models.transformer import ModelOptions
    from repro.sharding import batch_specs, divisibility_fix, to_named

    mesh = make_debug_mesh((2, 2, 1), ("pod", "data", "model"))
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(batch, seq))
    host_batch = {"tokens": toks.astype(np.int32),
                  "labels": np.roll(toks, -1, 1).astype(np.int32)}
    ab = input_specs(cfg, ShapeConfig("smoke", seq, batch, "train"))
    bs = divisibility_fix(batch_specs(ab, dp_axes(mesh)), ab, mesh)
    log(f"[4chip] mesh={dict(mesh.shape)} model={cfg.name} "
        f"params={cfg.param_count() / 1e6:.1f}M batch=({batch},{seq})")
    results = {}
    with jax.set_mesh(mesh), jax.default_matmul_precision("float32"):
        for hier in ("flat", "hierarchical"):
            agg = AggregationConfig(hierarchy=hier, num_microbatches=2)
            # the example's own execution options; tokens shard over
            # every data axis in the flat step and inside a pod in the
            # hierarchical one, as build_train_step's defaults do
            opts = ModelOptions(
                attn_impl="chunked", moe_impl="dense", loss_chunk=128,
                block_kv=128, remat=True,
                dp_axes=dp_axes(mesh) if hier == "flat" else ("data",))
            step, model = build_train_step(cfg, mesh, agg, opts=opts)
            ps, ss = train_shardings(model, mesh, agg)
            p_sh, s_sh = to_named(ps, mesh), to_named(ss, mesh)
            params = jax.jit(model.init, out_shardings=p_sh)(
                jax.random.PRNGKey(seed))
            state = jax.jit(partial(init_server_state, "fedavg"),
                            out_shardings=s_sh)(params)
            p0 = jax.tree.map(np.asarray, params)
            b = jax.device_put(host_batch, to_named(bs, mesh))
            fn = jax.jit(step, in_shardings=(p_sh, s_sh, to_named(bs, mesh)),
                         out_shardings=(p_sh, s_sh, None))
            t0 = time.perf_counter()
            p2, _s2, m = fn(params, state, b)
            jax.block_until_ready(p2)
            log(f"[4chip] {hier}: loss={float(m['loss']):.6f} "
                f"step_s={time.perf_counter() - t0:.3f} incl. compile")
            used = bytes_in_use()
            log(f"[4chip] {hier}: bytes_in_use per device "
                f"{[f'{u / 2**20:.1f}MiB' for u in used]}")
            assert all(u > 0 for u in used), "a device holds nothing"
            results[hier] = (p0, jax.tree.map(np.asarray, p2),
                             float(m["loss"]))
            del params, state, p2, b
    (p0, pf, lf), (_p0h, ph, lh) = results["flat"], results["hierarchical"]
    worst = 0.0
    for a0, a, h in zip(jax.tree.leaves(p0), jax.tree.leaves(pf),
                        jax.tree.leaves(ph)):
        step_size = float(np.max(np.abs(a - a0))) + 1e-12
        worst = max(worst, float(np.max(np.abs(a - h))) / step_size)
    log(f"[4chip] |loss_flat - loss_hier|={abs(lf - lh):.3e} "
        f"max |p_flat - p_hier| / |update| = {worst:.3e}")
    assert abs(lf - lh) <= 1e-4 * max(1.0, abs(lf)), (lf, lh)
    assert worst <= 1e-3, worst
    log("[4chip] ok: hierarchical == flat")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    device = phase_device(args.chips)

    import jax

    from repro.compile_cache import enable_compile_cache

    log(f"[cache] compilation cache: {enable_compile_cache()}")
    watch = CompileWatch()
    if args.chips == 4:
        from train_fl_lm import build_100m_config

        four_chip_step(build_100m_config(), args.seed)
    else:
        from repro.configs.resnet import RESNET18

        t0 = time.perf_counter()
        workload = build_workload(RESNET18, args.seed)
        n = workload[3]
        log(f"[workload] ResNet-18 full width: {n} params, "
            f"{N_CLIENTS} clients, set-up {time.perf_counter() - t0:.1f}s")
        phase_kernels(n, args.seed)
        # f32-accurate client steps: the device-fold and host-fold
        # sessions train from params that differ by fold rounding, and
        # single-pass bf16 products would amplify that into the checks
        with jax.default_matmul_precision("float32"):
            dev, host = phase_session(workload, args.seed, watch=watch)
            phase_host_runtimes(workload, args.seed, host[0], dev[0])
        left = _lifl_segments()
        assert not left, f"/dev/shm still holds {left}"
        log("[host-runtimes] /dev/shm holds no lifl segments")
    c_s, hits = watch.snapshot()
    log(f"[cache] backend compile {c_s:.1f}s, persistent-cache hits {hits}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
