"""Jitted public wrappers for the fedavg kernels.

``impl='auto'`` picks the compiled Pallas kernel on TPU and the jnp
twin on CPU (``kernels/impl.py``); ``impl='pallas_interpret'`` runs the
kernel body in Python for correctness tests.  Pytree helpers flatten an update
pytree into the (K, N) layout the kernel streams.
"""
from __future__ import annotations

from functools import partial
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fedavg.fedavg import (
    eager_accumulate_pallas,
    fedavg_accumulate_k_pallas,
    fedavg_reduce_pallas,
)
from repro.kernels.fedavg.ref import (
    eager_accumulate_ref,
    fedavg_accumulate_k_ref,
    fedavg_reduce_ref,
)
from repro.kernels.impl import use_pallas


@partial(jax.jit, static_argnames=("impl",))
def fedavg_reduce(updates: jnp.ndarray, weights: jnp.ndarray,
                  *, impl: str = "auto") -> jnp.ndarray:
    """Weighted mean of K stacked flat updates: (K,N) × (K,) -> (N,)."""
    wn = weights.astype(jnp.float32)
    wn = wn / jnp.maximum(jnp.sum(wn), 1e-30)
    pallas, interp = use_pallas(impl)
    if pallas:
        return fedavg_reduce_pallas(updates, wn, interpret=interp)
    return fedavg_reduce_ref(updates, wn)


@partial(jax.jit, static_argnames=("impl",), donate_argnums=(0,))
def eager_accumulate(acc: jnp.ndarray, update: jnp.ndarray, weight,
                     *, impl: str = "auto") -> jnp.ndarray:
    """acc += w·u, donated/aliased accumulator (zero-copy fold)."""
    pallas, interp = use_pallas(impl)
    if pallas:
        return eager_accumulate_pallas(acc, update, weight, interpret=interp)
    return eager_accumulate_ref(acc, update, weight)


@partial(jax.jit, static_argnames=("impl",), donate_argnums=(0,))
def fedavg_accumulate_k(acc: jnp.ndarray, updates: jnp.ndarray, weights,
                        *, impl: str = "auto") -> jnp.ndarray:
    """K-way burst fold acc += Σ_k w[k]·u[k], donated accumulator.

    Weights are raw (not normalized): this extends the running weighted
    *sum*; the caller divides by Σ w at the end (cumulative averaging,
    §2.1), so eager bursts and lazy batches stay numerically aligned.
    """
    pallas, interp = use_pallas(impl)
    if pallas:
        return fedavg_accumulate_k_pallas(acc, updates, weights,
                                          interpret=interp)
    return fedavg_accumulate_k_ref(acc, updates, weights)


# ---------------------------------------------------------------------------
# pytree adapters (model updates are parameter pytrees)
# ---------------------------------------------------------------------------


def _tree_meta(tree: Any) -> Tuple[Any, List, int]:
    leaves, treedef = jax.tree.flatten(tree)
    meta = [(l.shape, l.dtype) for l in leaves]
    n = sum(int(np.prod(s)) if s else 1 for s, _ in meta)
    return treedef, meta, n


def _host_staging() -> bool:
    """Stage through a preallocated host slab only on CPU backends —
    on TPU/GPU the leaves are device-resident and a host round trip
    would cost K full-model transfers; keep the all-device path there."""
    return jax.default_backend() == "cpu"


def _fill_row(row: np.ndarray, tree: Any) -> None:
    """Copy a pytree's leaves into a flat fp32 row — one write pass, no
    per-leaf temporaries and no concatenate."""
    off = 0
    for l in jax.tree.leaves(tree):
        a = np.asarray(l)
        k = a.size
        row[off : off + k] = a.reshape(-1)   # dtype-converting copy in place
        off += k


def flatten_update(tree: Any) -> Tuple[jnp.ndarray, Any, List]:
    treedef, meta, n = _tree_meta(tree)
    if not _host_staging():
        leaves = jax.tree.leaves(tree)
        flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                for l in leaves])
        return flat, treedef, meta
    flat = np.empty((n,), np.float32)        # single staging buffer
    _fill_row(flat, tree)
    return jnp.asarray(flat), treedef, meta


def unflatten_update(flat: jnp.ndarray, treedef, meta) -> Any:
    out = []
    off = 0
    for shape, dtype in meta:
        n = 1
        for d in shape:
            n *= d
        out.append(flat[off : off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def fedavg_reduce_tree(updates: Sequence[Any], weights: Sequence[float],
                       *, impl: str = "auto") -> Any:
    """Weighted mean of update pytrees via the flat kernel.

    On hosts the (K, N) slab is preallocated once and each pytree's
    leaves are written straight into its row — no per-update concatenate
    and no stack (the seed's double copy).  On accelerator backends the
    leaves stay on device (a host slab would add K model transfers)."""
    treedef, meta, n = _tree_meta(updates[0])
    if not _host_staging():
        stacked = jnp.stack([flatten_update(u)[0] for u in updates])
    else:
        stacked = np.empty((len(updates), n), np.float32)
        for k, u in enumerate(updates):
            _fill_row(stacked[k], u)
        stacked = jnp.asarray(stacked)
    flat = fedavg_reduce(stacked, jnp.asarray(weights, jnp.float32),
                         impl=impl)
    return unflatten_update(flat, treedef, meta)
