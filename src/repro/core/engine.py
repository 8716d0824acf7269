"""Pluggable aggregation engines — the §4.1 fold hot loop, made swappable.

LIFL's aggregation throughput is bounded by memory movement, not compute:
the shared-memory object store exists so each update element is touched
once (§4.1, App-G).  The engine layer is where that promise is kept or
broken.  All backends implement one interface (fold one update, fold a
K-way burst, merge a partial aggregate) and are exercised by the same
``Aggregator`` pipeline:

  * ``naive``   — the seed's scalar path: materialize a full-size
    ``update.astype(f32) * w`` temporary, then ``acc += tmp`` (three
    passes + a GB-scale allocation per fold).  Kept as the measurable
    baseline.
  * ``blocked`` — cache-tiled numpy: ``np.multiply(..., out=scratch)`` /
    ``np.add(..., out=acc)`` over L2-sized blocks with preallocated
    scratch.  Zero per-fold allocation, one read pass over the
    shared-memory view — the zero-copy ``store.get()`` view is actually
    consumed zero-copy.  A K-way burst keeps the accumulator block
    cache-resident while folding all K rows, so a burst of arrivals
    costs ~one read of the accumulator rather than K.
  * ``jnp`` / ``pallas`` / ``pallas_interpret`` — route through the
    ``kernels/fedavg`` twins: ``eager_accumulate`` (donated accumulator)
    for single folds and ``fedavg_accumulate_k`` ((K, N) slab folded
    into the aliased accumulator in a single grid sweep) for bursts.

Engines own their buffers (accumulator + scratch + staging slab) and are
*warm-reusable*: ``AggregatorPool`` (reuse.py) keeps the engine attached
to an aggregator instance across release/acquire, so a warm aggregator
re-enters a round with its buffers already resident — LIFL's reuse
benefit (§5.3) becomes measurable at the fold level (``buffer_allocs``
stays flat).  One engine serves one aggregator at a time: ``begin()``
hands out the single cached accumulator.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.trace import NULL_TRACER

# Cache-sized tile: 64 Ki f32 = 256 KiB — acc block + update block +
# scratch fit in L2 so the scratch round-trip never touches DRAM.
BLOCK_ELEMS = 64 * 1024

ENGINE_NAMES = ("naive", "blocked", "jnp", "pallas", "pallas_interpret")

# one-shot block-size autotune, cached per process AND per probe
# arguments (the cache hierarchy doesn't change under us; re-probing
# every engine build would put a measurement in every cold start — but
# a caller constraining the candidate set must not get another probe's
# answer)
_AUTOTUNE_CANDIDATES = (16 * 1024, 32 * 1024, 64 * 1024, 128 * 1024,
                        256 * 1024)
_AUTOTUNE_CACHE: Dict[Tuple, int] = {}


def autotune_block_elems(
    candidates: Sequence[int] = _AUTOTUNE_CANDIDATES,
    n_elems: int = 1 << 21,
    repeats: int = 3,
) -> int:
    """Pick the blocked-engine tile size from measured fold throughput.

    One-shot probe at engine init (``EngineConfig(block="auto")`` /
    ``BlockedNumpyEngine(block_elems="auto")``): folds an 8 MB synthetic
    update through each candidate tile and keeps the fastest — the
    empirical answer to where this machine's cache/NUMA sweet spot is,
    instead of the hardcoded 64 Ki guess.  Cached per process, keyed by
    the probe arguments."""
    cache_key = (tuple(int(c) for c in candidates), int(n_elems),
                 int(repeats))
    cached = _AUTOTUNE_CACHE.get(cache_key)
    if cached is not None:
        return cached
    rng = np.random.default_rng(0)
    update = rng.standard_normal(n_elems).astype(np.float32)
    best: Tuple[float, int] = (-1.0, int(candidates[0]))
    for blk in candidates:
        eng = BlockedNumpyEngine(block_elems=int(blk))
        acc = eng.begin(n_elems)
        eng.fold(acc, update, 1.0)          # fault + warm the buffers
        t0 = time.perf_counter()
        for _ in range(repeats):
            eng.fold(acc, update, 1.0)
        dt = (time.perf_counter() - t0) / repeats
        gbs = update.nbytes / max(dt, 1e-9)
        if gbs > best[0]:
            best = (gbs, int(blk))
    _AUTOTUNE_CACHE[cache_key] = best[1]
    return best[1]


@dataclass(frozen=True)
class EngineConfig:
    """Declarative engine spec accepted by :func:`make_engine`.

    ``block`` applies to the blocked (and shm) engines: an explicit
    element count, or ``"auto"`` to run the one-shot throughput probe
    (:func:`autotune_block_elems`, cached per process)."""

    name: str = "blocked"
    block: Any = None        # None | int | "auto"

    def resolve_block(self) -> Optional[int]:
        if self.block is None:
            return None
        if self.block == "auto":
            return autotune_block_elems()
        return int(self.block)


class AggregationEngine:
    """Folds weighted updates into an fp32 accumulator it owns.

    Stateless w.r.t. the running (Σ c·w, Σ c) pair — ``FedAvgState``
    owns that — stateful w.r.t. preallocated buffers, which survive
    across folds and (via the warm pool) across aggregator lifetimes.
    """

    name = "base"

    def __init__(self) -> None:
        self.fold_calls = 0
        self.elements_folded = 0
        self.buffer_allocs = 0
        self.tracer = NULL_TRACER
        self.span_tags: Dict[str, Any] = {}

    def bind_trace(self, tracer, span_tags: Dict[str, Any]) -> None:
        """Attribute the engine's host↔device copy spans (``owner``,
        ``round_id``) to whoever folds through it next.  A warm engine
        serves the same tree position in every round, two rolling
        rounds included, so each user binds before it folds."""
        self.tracer = tracer
        self.span_tags = span_tags

    # -- accumulator lifecycle -----------------------------------------
    def begin(self, n: int) -> Any:
        """Zeroed length-``n`` accumulator (reuses the warm buffer)."""
        raise NotImplementedError

    def fold(self, acc: Any, update: np.ndarray, w: float) -> Any:
        """acc += w·u for one update; returns the (possibly new) handle."""
        raise NotImplementedError

    def fold_many(self, acc: Any, updates: Sequence[np.ndarray],
                  weights: Sequence[float]) -> Any:
        """K-way burst fold — one logical read of the accumulator."""
        for u, w in zip(updates, weights):
            acc = self.fold(acc, u, w)
        return acc

    def add_partial(self, acc: Any, partial: np.ndarray) -> Any:
        """acc += partial (hierarchy merge of two running sums)."""
        raise NotImplementedError

    def recycle(self, acc: Any = None) -> None:
        """Return the accumulator to the warm buffer pool (no-op for
        engines that allocate per round)."""

    def sync(self, acc: Any) -> None:
        """Block until pending folds on ``acc`` have executed — numpy
        engines are synchronous (no-op); jax engines dispatch
        asynchronously, so timing a fold without this measures only
        host dispatch."""

    def to_numpy(self, acc: Any) -> np.ndarray:
        return np.asarray(acc)

    def _count(self, k: int, n: int) -> None:
        self.fold_calls += 1
        self.elements_folded += k * n


class NaiveEngine(AggregationEngine):
    """The seed's scalar path, verbatim — the measurable baseline."""

    name = "naive"

    def begin(self, n: int) -> np.ndarray:
        self.buffer_allocs += 1
        return np.zeros((n,), np.float32)

    def fold(self, acc: np.ndarray, update: np.ndarray, w: float) -> np.ndarray:
        self._count(1, update.size)
        contrib = update.astype(np.float32) * np.float32(w)
        acc += contrib
        return acc

    def add_partial(self, acc: np.ndarray, partial: np.ndarray) -> np.ndarray:
        acc += np.asarray(partial, np.float32)
        return acc


class BlockedNumpyEngine(AggregationEngine):
    """Cache-tiled in-place fold: zero per-fold allocation, one pass."""

    name = "blocked"

    def __init__(self, block_elems: Any = BLOCK_ELEMS) -> None:
        super().__init__()
        if block_elems == "auto":
            block_elems = autotune_block_elems()
        self.block_elems = int(block_elems)
        self._acc_buf: Optional[np.ndarray] = None
        self._scratch: Optional[np.ndarray] = None
        self._acc_out = False  # the single cached acc is handed out

    # -- buffers --------------------------------------------------------
    def begin(self, n: int) -> np.ndarray:
        if self._acc_buf is not None and not self._acc_out:
            if self._acc_buf.size == n:
                self._acc_buf.fill(0.0)   # warm: reuse, no allocation
                self._acc_out = True
                return self._acc_buf
            self._acc_buf = None          # idle but wrong size: replace
        acc = np.zeros((n,), np.float32)
        self.buffer_allocs += 1
        if self._acc_buf is None:
            # adopt as the cached warm buffer; if the cached one is
            # still handed out, this is a one-off allocation instead —
            # the warm buffer stays tracked for its eventual recycle
            self._acc_buf = acc
            self._acc_out = True
        return acc

    def recycle(self, acc: Optional[np.ndarray] = None) -> None:
        """Return the accumulator to the warm pool.  Only call once the
        round is over — result() has copied out and no FedAvgState still
        folds into this handle (the next begin() re-zeros it)."""
        if acc is None or acc is self._acc_buf:
            self._acc_out = False

    def _scratch_for(self, n: int) -> np.ndarray:
        m = min(n, self.block_elems)
        if self._scratch is None or self._scratch.size < m:
            self._scratch = np.empty((m,), np.float32)
            self.buffer_allocs += 1
        return self._scratch

    # -- folds ----------------------------------------------------------
    def fold(self, acc: np.ndarray, update: np.ndarray, w: float) -> np.ndarray:
        return self.fold_many(acc, (update,), (w,))

    def fold_many(self, acc: np.ndarray, updates: Sequence[np.ndarray],
                  weights: Sequence[float]) -> np.ndarray:
        n = acc.size
        ws = [np.float32(w) for w in weights]
        scratch = self._scratch_for(n)
        blk = scratch.size
        for off in range(0, n, blk):
            end = min(off + blk, n)
            a = acc[off:end]
            s = scratch[: end - off]
            # acc block stays cache-resident across all K rows: the
            # burst costs one DRAM read of the accumulator, not K
            for u, w in zip(updates, ws):
                ub = u[off:end]
                if ub.dtype == np.float32:
                    np.multiply(ub, w, out=s, casting="unsafe")
                else:
                    # dtype-preserving fold: the wire update stays in
                    # its reduced dtype (bf16/f16 — half the DRAM read);
                    # upcast happens block-wise into the f32 scratch, so
                    # accumulation precision is still full f32
                    np.copyto(s, ub, casting="unsafe")
                    np.multiply(s, w, out=s)
                np.add(a, s, out=a, casting="unsafe")
        self._count(len(ws), n)
        return acc

    def add_partial(self, acc: np.ndarray, partial: np.ndarray) -> np.ndarray:
        np.add(acc, partial, out=acc, casting="unsafe")
        return acc


class JaxEngine(AggregationEngine):
    """Kernel-backed engine: eager_accumulate (donated accumulator) for
    single folds, fedavg_accumulate_k (aliased (N,) accumulator, one
    grid sweep over the (K, N) slab) for bursts.  The staging slab is a
    preallocated pinned-host numpy buffer filled row-wise in place."""

    def __init__(self, impl: str = "jnp", max_k: int = 16) -> None:
        super().__init__()
        # function-level import: repro.core stays importable without jax
        import jax
        import jax.numpy as jnp
        from repro.kernels.fedavg import eager_accumulate, fedavg_accumulate_k

        self.name = impl
        self.impl = impl
        self.max_k = int(max_k)
        self._jax = jax
        self._jnp = jnp
        self._accumulate = eager_accumulate
        self._accumulate_k = fedavg_accumulate_k
        # staging slabs keyed by wire dtype: a bf16 burst ships a (K,N)
        # bf16 slab to the device (half the host/PCIe bytes) and the
        # kernel accumulates in f32 VREGs — dtype-preserving folds
        self._slabs: Dict[str, np.ndarray] = {}
        # donated in-place zeroing: a recycled accumulator's device
        # buffer is rewound to zeros without a fresh allocation
        self._zero = jax.jit(lambda a: a * 0.0, donate_argnums=(0,))
        self._acc_cache = None  # recycled accumulator awaiting reuse
        self._last = None       # latest handle returned by a fold

    def begin(self, n: int):
        cached, self._acc_cache = self._acc_cache, None
        if cached is not None and cached.shape == (n,):
            return self._zero(cached)   # warm: reuse the device buffer
        self.buffer_allocs += 1
        return self._jnp.zeros((n,), self._jnp.float32)

    def recycle(self, acc=None) -> None:
        """Cache the finished accumulator's device buffer for the next
        begin().  Called without a handle (the pool's release path) it
        adopts the last fold result — safe once result() has copied out,
        because the donated zeroing invalidates that old handle."""
        self._acc_cache = acc if acc is not None else self._last
        self._last = None

    def _slab_for(self, k: int, n: int, dtype: np.dtype) -> np.ndarray:
        slab = self._slabs.get(dtype.str)
        if slab is None or slab.shape[0] < k or slab.shape[1] != n:
            slab = np.empty((max(k, min(self.max_k, 8)), n), dtype)
            self._slabs[dtype.str] = slab
            self.buffer_allocs += 1
        return slab

    def fold(self, acc, update: np.ndarray, w: float):
        self._count(1, update.size)
        # wire dtype rides to the device untouched; the kernel upcasts
        # to f32 in-register (accumulate-in-f32, any float wire dtype)
        # the upload span lasts until the copy has landed; the kernel is
        # queued behind the copy first, so waiting for it here costs the
        # fold nothing
        with self.tracer.span("fold.upload", n=float(update.nbytes),
                              **self.span_tags):
            u = self._jnp.asarray(np.ascontiguousarray(update))
            out = self._accumulate(acc, u, np.float32(w), impl=self.impl)
            u.block_until_ready()
        self._last = out
        return out

    def fold_many(self, acc, updates: Sequence[np.ndarray],
                  weights: Sequence[float]):
        k = len(updates)
        if k == 1:
            return self.fold(acc, updates[0], weights[0])
        n = int(acc.shape[0])
        # a homogeneous burst keeps its wire dtype end-to-end; a mixed
        # one stages through f32 (the common denominator)
        dtypes = {u.dtype.str for u in updates}
        dtype = updates[0].dtype if len(dtypes) == 1 else np.dtype(np.float32)
        slab = self._slab_for(k, n, np.dtype(dtype))
        for i, u in enumerate(updates):          # row fill, no concat/stack
            np.copyto(slab[i], u, casting="unsafe")
        self._count(k, n)
        ws = np.asarray(weights, np.float32)
        with self.tracer.span("fold.upload",
                              n=float(slab[:k].nbytes + ws.nbytes),
                              **self.span_tags):
            rows = self._jnp.asarray(slab[:k])
            dev_ws = self._jnp.asarray(ws)
            out = self._accumulate_k(acc, rows, dev_ws, impl=self.impl)
            self._jax.block_until_ready((rows, dev_ws))
        self._last = out
        return out

    def add_partial(self, acc, partial: np.ndarray):
        host = np.asarray(partial, np.float32)
        with self.tracer.span("fold.upload", n=float(host.nbytes),
                              **self.span_tags):
            dev = self._jnp.asarray(host)
            out = acc + dev
            dev.block_until_ready()
        return out

    def sync(self, acc) -> None:
        self._jax.block_until_ready(acc)

    def to_numpy(self, acc) -> np.ndarray:
        with self.tracer.span("readback", n=float(acc.nbytes),
                              **self.span_tags):
            return np.asarray(acc)


def _auto_name() -> str:
    """``auto`` by platform: the compiled Pallas fold on TPU, blocked
    numpy on CPU hosts.  Any other platform raises rather than guess."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "blocked"
    raise ValueError(f"agg_engine='auto' has no choice for platform "
                     f"{platform!r}; pass one of {ENGINE_NAMES}")


def make_engine(spec: Any = "auto", **kwargs) -> AggregationEngine:
    """Resolve an engine spec: an instance passes through (how the warm
    pool hands a resident engine to a fresh Aggregator), a name builds
    one, an :class:`EngineConfig` carries options (``block="auto"``
    runs the one-shot tile autotune).  ``auto`` → pallas on TPU,
    blocked numpy on CPU (:func:`_auto_name`)."""
    if isinstance(spec, AggregationEngine):
        return spec
    if isinstance(spec, EngineConfig):
        name = spec.name or "auto"
        if name == "auto":
            name = _auto_name()
        if name == "blocked":
            blk = spec.resolve_block()
            if blk is not None:
                kwargs.setdefault("block_elems", blk)
        return make_engine(name, **kwargs)
    name = spec or "auto"
    if name == "auto":
        name = _auto_name()
    if name == "naive":
        return NaiveEngine()
    if name == "blocked":
        return BlockedNumpyEngine(**kwargs)
    if name in ("jnp", "pallas", "pallas_interpret"):
        return JaxEngine(impl=name, **kwargs)
    raise ValueError(f"unknown aggregation engine {spec!r} "
                     f"(expected one of {ENGINE_NAMES} or 'auto')")
