"""Aggregation-engine correctness: every backend (naive / blocked / jnp /
pallas_interpret) against ``fedavg_oracle``, through both the raw
``FedAvgState`` fold API and the full ``Aggregator`` pipeline (eager vs
lazy timing, bursty arrival orders, K-way batched drain), plus the
warm-pool buffer-reuse contract (§5.3 at the fold level)."""
import numpy as np
import pytest

from repro.core import (
    Aggregator,
    AggregatorPool,
    FedAvgState,
    InProcObjectStore,
    Role,
    fedavg_oracle,
    make_engine,
)
from repro.core.engine import BlockedNumpyEngine
from repro.core.gateway import UpdateEnvelope
from repro.core.sidecar import EventSidecar, MetricsMap

ENGINES = ["naive", "blocked", "jnp", "pallas_interpret"]
RNG = np.random.default_rng(7)


def _updates(k=6, n=1000, dtype=np.float32):
    us = [RNG.normal(size=(n,)).astype(dtype) for _ in range(k)]
    ws = [float(w) for w in RNG.uniform(0.5, 8.0, size=k)]
    return us, ws


def _feed(agg, store, us, ws):
    for u, w in zip(us, ws):
        key = store.put(u)
        agg.recv(UpdateEnvelope(key, 0, "c", w))


# ---------------------------------------------------------------------------
# FedAvgState-level: fold / fold_many / merge per backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_sequential_fold_matches_oracle(engine):
    us, ws = _updates()
    st = FedAvgState(engine=make_engine(engine))
    for u, w in zip(us, ws):
        st.fold(u, w)
    got, weight = st.result()
    np.testing.assert_allclose(got, fedavg_oracle(us, ws), rtol=1e-5, atol=1e-5)
    assert weight == pytest.approx(sum(ws))
    assert st.count == len(us)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("n", [64, 999, 64 * 1024 + 17])  # block remainders
def test_batched_fold_matches_oracle(engine, n):
    us, ws = _updates(k=5, n=n)
    st = FedAvgState(engine=make_engine(engine))
    st.fold_many(us, ws)
    got, _ = st.result()
    np.testing.assert_allclose(got, fedavg_oracle(us, ws), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("engine", ENGINES)
def test_merge_partials_matches_oracle(engine):
    us, ws = _updates(k=7)
    a = FedAvgState(engine=make_engine(engine))
    b = FedAvgState(engine=make_engine(engine))
    for u, w in zip(us[:3], ws[:3]):
        a.fold(u, w)
    b.fold_many(us[3:], ws[3:])
    a.merge(b)
    got, _ = a.result()
    np.testing.assert_allclose(got, fedavg_oracle(us, ws), rtol=1e-5, atol=1e-5)


def test_blocked_reads_view_without_copy_or_alloc():
    """The blocked fold consumes read-only store views in place and does
    zero per-fold allocation after warm-up."""
    eng = BlockedNumpyEngine()
    us, ws = _updates(k=4, n=50_000)
    for u in us:
        u.flags.writeable = False            # store.get() contract
    acc = eng.begin(us[0].size)
    eng.fold(acc, us[0], ws[0])
    allocs = eng.buffer_allocs
    eng.fold_many(acc, us[1:], ws[1:])
    assert eng.buffer_allocs == allocs       # no new buffers post warm-up


# ---------------------------------------------------------------------------
# Aggregator-level: eager vs lazy, bursty arrivals, batched drain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("eager", [True, False])
def test_aggregator_end_to_end_matches_oracle(engine, eager):
    us, ws = _updates(k=9)
    store = InProcObjectStore()
    agg = Aggregator("a", store, goal=len(us), eager=eager,
                     engine=engine, batch_k=4)
    _feed(agg, store, us, ws)
    if not eager:
        agg.flush()
    assert agg.done
    got, weight = agg.result
    np.testing.assert_allclose(got, fedavg_oracle(us, ws), rtol=1e-5, atol=1e-5)
    assert weight == pytest.approx(sum(ws))


@pytest.mark.parametrize("engine", ENGINES)
def test_bursty_arrival_order_invariance(engine):
    """Permuted + bursty arrivals (lazy queue drained in K-way batches)
    agree with in-order eager arrival bit-for-bit within tolerance."""
    us, ws = _updates(k=11)
    perm = RNG.permutation(len(us))
    results = []
    for order, eager, batch_k in (
        (range(len(us)), True, 1),       # in-order, fold-on-arrival
        (perm, False, 8),                # permuted burst, batched drain
        (perm[::-1], False, 3),          # reversed burst, ragged batches
    ):
        store = InProcObjectStore()
        agg = Aggregator("a", store, goal=len(us), eager=eager,
                         engine=engine, batch_k=batch_k)
        _feed(agg, store, [us[i] for i in order], [ws[i] for i in order])
        if not eager:
            agg.flush()
        assert agg.done
        results.append(agg.result[0])
    oracle = fedavg_oracle(us, ws)
    for got in results:
        np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5)


def test_drain_batches_reported_to_sidecar():
    """Lazy drain folds in K-way batches — the sidecar sees fewer, larger
    aggregate events; the updates total is conserved."""
    us, ws = _updates(k=10)
    store = InProcObjectStore()
    mm = MetricsMap()
    agg = Aggregator("a", store, goal=len(us), eager=False, engine="blocked",
                     batch_k=4, sidecar=EventSidecar("a", mm))
    _feed(agg, store, us, ws)
    agg.flush()
    total, events = mm.peek("a", "agg_updates")
    assert total == len(us)
    assert events == 3                       # 4 + 4 + 2

    # satellite: InProcObjectStore.meta() feeds real rx_bytes now
    rx, _ = mm.peek("a", "rx_bytes")
    assert rx == sum(u.nbytes for u in us)


def test_goal_overshoot_leaves_extra_updates_queued():
    us, ws = _updates(k=6)
    store = InProcObjectStore()
    agg = Aggregator("a", store, goal=4, eager=False, engine="blocked",
                     batch_k=8)
    _feed(agg, store, us, ws)
    agg.flush()
    assert agg.done and agg.state.count == 4  # batch clamped to the goal
    assert len(agg.fifo) == 2                 # stragglers left queued
    np.testing.assert_allclose(
        agg.result[0], fedavg_oracle(us[:4], ws[:4]), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# warm pool keeps engine buffers across release/acquire (§5.3)
# ---------------------------------------------------------------------------

def test_pool_reuse_keeps_warm_engine_buffers():
    pool = AggregatorPool(cold_start_s=1.0, engine="blocked")
    inst, _ = pool.acquire("node0", Role.LEAF)
    assert inst.engine is None               # lazy: sims never pay
    eng = pool.engine_for(inst)
    assert isinstance(eng, BlockedNumpyEngine)

    us, ws = _updates(k=3, n=20_000)
    acc = eng.begin(us[0].size)
    eng.fold_many(acc, us, ws)
    allocs = eng.buffer_allocs
    pool.release(inst.agg_id)

    inst2, delay = pool.acquire("node0", Role.MIDDLE)
    assert inst2.engine is eng and delay == 0.0   # same warm runtime
    acc2 = eng.begin(us[0].size)                  # buffer reused, re-zeroed
    assert eng.buffer_allocs == allocs
    eng.fold(acc2, us[0], ws[0])
    np.testing.assert_allclose(
        acc2, np.float32(ws[0]) * us[0], rtol=1e-5, atol=1e-5)


def test_blocked_begin_while_busy_is_safe():
    """A second begin() while the warm accumulator is handed out must
    not corrupt or untrack it; after recycle the warm buffer is reused
    with no fresh allocation."""
    eng = BlockedNumpyEngine()
    a = eng.begin(64)
    eng.fold(a, np.ones(64, np.float32), 2.0)
    b = eng.begin(64)                   # one-off: warm buffer is busy
    assert b is not a
    np.testing.assert_allclose(a, 2.0)  # first handle untouched
    allocs = eng.buffer_allocs
    eng.recycle(b)                      # not the warm buffer: no-op
    c = eng.begin(64)
    assert c is not a and eng.buffer_allocs == allocs + 1
    eng.recycle(a)
    d = eng.begin(64)                   # warm buffer back in rotation
    assert d is a and eng.buffer_allocs == allocs + 1


def test_jax_engine_recycle_reuses_device_buffer():
    """recycle() + begin() rewinds the donated device buffer to zeros
    instead of allocating — buffer_allocs stays flat across rounds."""
    from repro.core.engine import JaxEngine

    eng = JaxEngine(impl="jnp")
    us, ws = _updates(k=3, n=512)
    acc = eng.begin(512)
    for u, w in zip(us, ws):
        acc = eng.fold(acc, u, w)
    allocs = eng.buffer_allocs
    eng.recycle(acc)
    acc2 = eng.begin(512)                     # warm: donated zeroing
    assert eng.buffer_allocs == allocs
    np.testing.assert_allclose(np.asarray(acc2), 0.0)
    acc2 = eng.fold(acc2, us[0], ws[0])
    np.testing.assert_allclose(np.asarray(acc2), np.float32(ws[0]) * us[0],
                               rtol=1e-6, atol=1e-6)


def test_simulation_engine_speedup_strict():
    from repro.core.simulation import DataPlaneCosts

    c = DataPlaneCosts()
    assert c.t_agg_for("naive") == c.t_agg
    assert c.t_agg_for("blocked") < c.t_agg
    assert c.t_agg_for("auto") < c.t_agg      # resolves like make_engine
    with pytest.raises(ValueError):
        c.t_agg_for("warpdrive")


# ---------------------------------------------------------------------------
# "auto" resolves by platform and never falls back in silence
# ---------------------------------------------------------------------------

def test_auto_engine_is_blocked_numpy_on_cpu():
    jax = pytest.importorskip("jax")
    assert jax.default_backend() == "cpu"
    assert isinstance(make_engine("auto"), BlockedNumpyEngine)


@pytest.mark.parametrize("platform,expected", [("tpu", "pallas"),
                                               ("cpu", "blocked")])
def test_auto_engine_name_follows_platform(monkeypatch, platform, expected):
    jax = pytest.importorskip("jax")
    from repro.core.engine import _auto_name

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert _auto_name() == expected


def test_auto_engine_unknown_platform_raises(monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="gpu"):
        make_engine("auto")


def test_auto_engine_backend_failure_propagates(monkeypatch):
    jax = pytest.importorskip("jax")

    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="backend init failed"):
        make_engine("auto")


def test_object_store_meta():
    store = InProcObjectStore()
    x = RNG.normal(size=(17, 3)).astype(np.float32)
    key = store.put(x)
    m = store.meta(key)
    assert m.nbytes == x.nbytes and m.shape == (17, 3)
    assert m.dtype == "float32" and m.sealed


# ---------------------------------------------------------------------------
# dtype-preserving folds: reduced-precision wire updates, f32 accumulation
# ---------------------------------------------------------------------------

_WIRE_DTYPES = ["float16", "bfloat16"]


def _wire_updates(dtype_name, k=6, n=1000):
    """f32 ground-truth updates + their wire-dtype (rounded) twins."""
    dt = np.dtype(dtype_name) if dtype_name != "bfloat16" else np.dtype(
        pytest.importorskip("ml_dtypes").bfloat16)
    us32, ws = _updates(k=k, n=n)
    wire = [u.astype(dt) for u in us32]
    return us32, wire, ws, dt


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("wire_dtype", _WIRE_DTYPES)
def test_reduced_dtype_fold_accumulates_in_f32(engine, wire_dtype):
    """bf16/f16 wire updates fold without materializing f32 copies of
    the inputs; the running sum is f32, so the only error vs the f32
    oracle is the *per-update* wire rounding — exact against an oracle
    fed the same rounded values, loosely bounded against the f32 one."""
    us32, wire, ws, _ = _wire_updates(wire_dtype)
    st = FedAvgState(engine=make_engine(engine))
    for u, w in zip(wire, ws):
        st.fold(u, w)
    got, _ = st.result()
    assert np.asarray(st.acc).dtype == np.float32  # accumulate-in-f32
    # tight: same rounded inputs, f32 accumulation on both sides
    rounded_oracle = fedavg_oracle([u.astype(np.float32) for u in wire], ws)
    np.testing.assert_allclose(got, rounded_oracle, rtol=1e-5, atol=1e-5)
    # loose: wire precision loss is bounded (bf16 ≈ 8 mantissa bits)
    np.testing.assert_allclose(got, fedavg_oracle(us32, ws),
                               rtol=0.05, atol=0.05)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("wire_dtype", _WIRE_DTYPES)
def test_reduced_dtype_burst_fold_matches_rounded_oracle(engine, wire_dtype):
    """The K-way burst path (fedavg_accumulate_k for jax engines,
    blocked scratch staging for numpy) handles reduced wire dtypes."""
    _, wire, ws, _ = _wire_updates(wire_dtype, k=5, n=64 * 1024 + 17)
    st = FedAvgState(engine=make_engine(engine))
    st.fold_many(wire, ws)
    got, _ = st.result()
    rounded_oracle = fedavg_oracle([u.astype(np.float32) for u in wire], ws)
    np.testing.assert_allclose(got, rounded_oracle, rtol=1e-5, atol=1e-5)


def test_jax_engine_slab_preserves_wire_dtype():
    """A homogeneous bf16 burst must stage through a bf16 slab (half
    the host-side staging bytes), not silently upcast to f32."""
    jnp = pytest.importorskip("jax.numpy")  # noqa: F841
    ml = pytest.importorskip("ml_dtypes")
    from repro.core.engine import JaxEngine

    eng = JaxEngine(impl="jnp")
    _, wire, ws, dt = _wire_updates("bfloat16", k=4, n=512)
    acc = eng.begin(512)
    acc = eng.fold_many(acc, wire, ws)
    assert eng._slabs[np.dtype(ml.bfloat16).str].dtype == dt
    # mixed-dtype bursts fall back to the f32 slab
    mixed = [wire[0], wire[1].astype(np.float32), wire[2], wire[3]]
    acc = eng.fold_many(acc, mixed, ws)
    assert np.dtype(np.float32).str in eng._slabs


def test_accumulate_k_ref_path_bf16_wire():
    """fedavg_accumulate_k's jnp ref path: (K,N) bf16 slab folded into
    the aliased f32 accumulator matches the f32 oracle to wire
    tolerance."""
    jnp = pytest.importorskip("jax.numpy")
    pytest.importorskip("ml_dtypes")
    from repro.kernels.fedavg import fedavg_accumulate_k

    us32, wire, ws, _ = _wire_updates("bfloat16", k=4, n=4096)
    acc = jnp.zeros((4096,), jnp.float32)
    out = fedavg_accumulate_k(
        acc, jnp.asarray(np.stack(wire)),
        jnp.asarray(np.asarray(ws, np.float32)), impl="jnp")
    assert out.dtype == jnp.float32
    expect = sum(np.float32(w) * u.astype(np.float32)
                 for u, w in zip(wire, ws))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# block-size autotune (EngineConfig(block="auto"))
# ---------------------------------------------------------------------------

def test_autotune_block_picks_candidate_and_caches():
    from repro.core import engine as engine_mod
    from repro.core.engine import (EngineConfig, autotune_block_elems,
                                   make_engine)

    # tiny probe: the result must come from the candidate set and be
    # cached for the rest of the process (keyed by the probe arguments:
    # a caller constraining the candidates gets its own answer, never a
    # tile outside its requested set)
    engine_mod._AUTOTUNE_CACHE.clear()
    try:
        blk = autotune_block_elems(candidates=(8 * 1024, 32 * 1024),
                                   n_elems=1 << 17, repeats=1)
        assert blk in (8 * 1024, 32 * 1024)
        # same arguments: answered from the cache, no re-probe
        assert len(engine_mod._AUTOTUNE_CACHE) == 1
        assert autotune_block_elems(candidates=(8 * 1024, 32 * 1024),
                                    n_elems=1 << 17, repeats=1) == blk
        assert len(engine_mod._AUTOTUNE_CACHE) == 1
        # different candidate set: a fresh probe honoring it
        assert autotune_block_elems(candidates=(123,), n_elems=1 << 14,
                                    repeats=1) == 123
        eng = make_engine(EngineConfig(name="blocked", block="auto"))
        assert eng.name == "blocked"
        assert eng.block_elems in engine_mod._AUTOTUNE_CANDIDATES
        eng2 = make_engine("blocked", block_elems="auto")
        assert eng2.block_elems == eng.block_elems  # default-key cache
    finally:
        engine_mod._AUTOTUNE_CACHE.clear()


def test_engine_config_explicit_block_and_autotuned_bits_match():
    from repro.core.engine import EngineConfig, make_engine

    rng = np.random.default_rng(3)
    ups = [rng.normal(size=5000).astype(np.float32) for _ in range(4)]
    ws = [1.0, 2.0, 0.5, 3.0]

    def run(engine):
        acc = engine.begin(5000)
        acc = engine.fold_many(acc, ups, ws)
        return np.asarray(acc)

    base = run(make_engine("blocked"))
    cfgd = run(make_engine(EngineConfig(name="blocked", block=16 * 1024)))
    # tile size changes the blocking, never the bits (per-element fold
    # order within a block is element-independent)
    np.testing.assert_array_equal(base, cfgd)
