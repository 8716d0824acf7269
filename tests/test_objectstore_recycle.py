"""InProcObjectStore recycling: a deleted object's array is parked on a
free list keyed by (nbytes, dtype) and the next put of that size copies
into it — only when nothing outside the store still holds the array,
and never beyond the store's own high-water mark."""
import sys
import threading
import types

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core.aggregation import fedavg_oracle
from repro.runtime import driver as driver_mod
from repro.runtime.driver import InProcRuntime, RoundDriver


def _ptr(a) -> int:
    return a.__array_interface__["data"][0]


@pytest.mark.parametrize("first, second, reused", [
    ((np.float32, (64,)), (np.float32, (64,)), True),      # same size
    ((np.float32, (64,)), (np.float32, (8, 8)), True),     # same bytes
    ((np.float32, (64,)), (np.float32, (65,)), False),     # other size
    ((np.float32, (64,)), (np.int32, (64,)), False),       # other dtype
    ((np.float32, (64,)), (np.float64, (32,)), False),     # same bytes
])
def test_delete_then_put_reuses_only_a_matching_buffer(first, second,
                                                       reused):
    store = core.InProcObjectStore()
    rng = np.random.default_rng(0)
    (dt0, shape0), (dt1, shape1) = first, second
    k0 = store.put(rng.normal(size=shape0).astype(dt0))
    ptr = _ptr(store.get(k0))
    store.delete(k0)
    assert store.parked_bytes == 64 * 4 and store.bytes_in_use == 0
    x = rng.normal(size=shape1).astype(dt1)
    k1 = store.put(x)
    got = store.get(k1)
    assert k1 != k0 and not store.contains(k0)   # keys are never reissued
    assert store.stats["recycled"] == int(reused)
    if reused:
        assert _ptr(got) == ptr
    # a fresh put of another size drops the parked array: parked plus
    # in-use bytes may not pass the high-water mark
    assert store.parked_bytes == 0
    assert store.stats["recycle_skipped"] == 0
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(got, x)
    assert store.meta(k1).nbytes == x.nbytes
    assert store.bytes_in_use == x.nbytes


@pytest.mark.parametrize("recycled", [False, True])
def test_objects_stay_read_only_and_copied_on_put(recycled):
    store = core.InProcObjectStore()
    if recycled:
        store.delete(store.put(np.zeros(32, np.float32)))
    x = np.arange(32, dtype=np.float32)
    key = store.put(x)
    assert store.stats["recycled"] == int(recycled)
    x[:] = -1.0                       # the source changes after the put
    got = store.get(key)
    np.testing.assert_array_equal(got, np.arange(32, dtype=np.float32))
    assert not got.flags.writeable
    with pytest.raises(ValueError):
        got[0] = 1.0


@pytest.mark.parametrize("hold", [
    lambda a: a,                      # the get() result itself
    lambda a: a[::2],                 # a view
    lambda a: a.reshape(4, -1),       # a reshaped view
    lambda a: np.asarray(a),          # the same array again
    memoryview,                       # a buffer export
], ids=["get", "slice", "reshape", "asarray", "memoryview"])
def test_a_reader_holding_the_object_keeps_its_bytes(hold):
    store = core.InProcObjectStore()
    x = np.arange(64, dtype=np.float32)
    key = store.put(x)
    held = hold(store.get(key))
    before = np.array(held, copy=True)
    store.delete(key)
    assert store.stats["recycle_skipped"] == 1 and store.parked_bytes == 0
    store.put(np.full(64, 7.0, np.float32))
    assert store.stats["recycled"] == 0
    np.testing.assert_array_equal(np.asarray(held), before)
    del held
    # once the reader lets go, the next delete parks again
    k2 = store.put(np.ones(64, np.float32))
    store.delete(k2)
    assert store.parked_bytes == 64 * 4


def test_a_cpu_jax_array_of_a_stored_object_keeps_its_values():
    store = core.InProcObjectStore()
    n = 1 << 14
    # prefer an object JAX aliases on the CPU (64-byte aligned), the
    # case in which a recycled buffer would rewrite the jax array
    keys = [store.put(np.full(n, float(i), np.float32)) for i in range(16)]
    key = next((k for k in keys if _ptr(store.get(k)) % 64 == 0), keys[0])
    value = float(store.get(key)[0])
    for k in keys:
        if k != key:
            store.delete(k)
    dev = jnp.asarray(store.get(key))
    aliased = dev.unsafe_buffer_pointer() == _ptr(store.get(key))
    skipped0 = store.stats["recycle_skipped"]
    store.delete(key)
    for _ in range(16):               # take every parked buffer and more
        store.put(np.full(n, -1.0, np.float32))
    np.testing.assert_array_equal(np.asarray(dev),
                                  np.full(n, value, np.float32))
    if aliased:
        assert store.stats["recycle_skipped"] == skipped0 + 1


def test_parked_plus_in_use_never_exceed_the_high_water_mark():
    store = core.InProcObjectStore()
    rng = np.random.default_rng(7)
    live, held = [], []
    high = 0
    for _ in range(600):
        if live and rng.random() < 0.45:
            key = live.pop(int(rng.integers(len(live))))
            if rng.random() < 0.1:
                held.append(store.get(key))     # a reader keeps it
            store.delete(key)
        else:
            n = int(rng.choice([16, 32, 48]))
            dt = [np.float32, np.float64, np.int8][int(rng.integers(3))]
            live.append(store.put(np.ones(n, dt)))
        if len(held) > 4:
            held.pop(0)
        high = max(high, store.bytes_in_use)
        parked = sum(a.nbytes for bucket in store._free.values()
                     for a in bucket)
        assert store.parked_bytes == parked
        assert store.peak_bytes == high
        assert store.parked_bytes + store.bytes_in_use <= high
    assert store.stats["recycled"] > 0 and store.stats["recycle_skipped"] > 0


def test_concurrent_puts_and_deletes_never_share_a_buffer():
    """More threads than cores put, read back and delete objects of one
    size under a short switch interval: each reads its own values, and
    the books balance at the end."""
    store = core.InProcObjectStore()
    errors = []

    def work(tid):
        try:
            for i in range(150):
                value = float(tid * 1000 + i)
                key = store.put(np.full(256, value, np.float32))
                got = store.get(key)
                if not (got == value).all():
                    errors.append((tid, i))
                del got
                store.delete(key)
        except Exception as e:  # reported by the assertion below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert store.bytes_in_use == 0 and store.stats["puts"] == 16 * 150
    assert 0 < store.parked_bytes <= store.peak_bytes
    assert store.stats["recycled"] > 0


def test_close_empties_the_free_list():
    store = core.InProcObjectStore()
    keys = [store.put(np.ones(16, np.float32)) for _ in range(3)]
    for k in keys:
        store.delete(k)
    assert store.parked_bytes == 3 * 64
    store.close()
    assert store.parked_bytes == 0 and store._free == {}
    store.put(np.ones(16, np.float32))
    assert store.stats["recycled"] == 0


def test_inproc_rounds_count_their_store_puts_and_recycled_puts():
    n, goal = 256, 4
    rng = np.random.default_rng(3)
    ups = [rng.normal(size=n).astype(np.float32) for _ in range(goal)]
    ws = [1.0, 2.0, 3.0, 4.0]
    rt = InProcRuntime()
    drv = RoundDriver(rt)
    outs = []
    for r in range(3):
        feed = ((("n0", "n1")[i % 2], f"c{i}", u, w)
                for i, (u, w) in enumerate(zip(ups, ws)))
        outs.append(drv.run_round(
            round_id=r, assignment={"n0": [0, 2], "n1": [1, 3]},
            updates=feed, goal=goal, n_elems=n))
    rt.close()
    # four updates and two partials a round; the first round's
    # close-out parks all six for the next
    assert [o.store_puts for o in outs] == [6, 6, 6]
    assert [o.store_recycled for o in outs] == [0, 6, 6]
    for o in outs:
        np.testing.assert_allclose(o.delta, fedavg_oracle(ups, ws),
                                   rtol=1e-6, atol=1e-7)


def test_runtimes_without_an_in_proc_store_count_nothing():
    shm = types.SimpleNamespace(store=core.SharedMemoryObjectStore.__new__(
        core.SharedMemoryObjectStore))
    for rt in (types.SimpleNamespace(), shm):
        assert driver_mod._store_counts(rt) == {"store_puts": 0,
                                                "store_recycled": 0}
