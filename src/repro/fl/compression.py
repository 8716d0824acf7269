"""Gradient/update compression for the slow (DCN / inter-pod) tier.

LIFL's insight is to keep heavy update traffic on the fast tier and
minimize what crosses the slow tier; we additionally *compress* what
must cross it (beyond-paper, DESIGN.md §5): per-block int8 quantization
with fp32 scales.  The pallas twin lives in kernels/quantize.

The DCN collective then moves 1 byte + 4/block instead of 4 bytes per
element (4× for fp32 updates, 2× for bf16).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


BLOCK = 256


def quantize_leaf(x: jnp.ndarray, block: int = BLOCK) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """-> (q int8 (n_blocks, block), scales fp32 (n_blocks,), orig_size)."""
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(nb, block)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(blocks / safe[:, None]), -127, 127).astype(jnp.int8)
    return q, scale, n


def dequantize_leaf(q: jnp.ndarray, scale: jnp.ndarray, n: int, shape, dtype) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n]
    return flat.reshape(shape).astype(dtype)


def quantize_tree(tree: Any, block: int = BLOCK):
    leaves, treedef = jax.tree.flatten(tree)
    qs = [quantize_leaf(l, block) for l in leaves]
    meta = [(l.shape, l.dtype) for l in leaves]
    return [(q, s) for q, s, _ in qs], [(n, m) for (_, _, n), m in zip(qs, meta)], treedef


def dequantize_tree(qs, meta, treedef, block: int = BLOCK):
    leaves = [
        dequantize_leaf(q, s, n, shape, dtype)
        for (q, s), (n, (shape, dtype)) in zip(qs, meta)
    ]
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# compressed cross-pod mean (the LIFL "top aggregator" hop over DCN)
# ---------------------------------------------------------------------------


def _quantize_blocks_last_axis(x: jnp.ndarray, block: int):
    """Shape-preserving int8 block quantization along the last axis —
    the wire format of the manual-pod ring exchange.  Returns (q int8, safe fp32
    scales, original last-axis length); dequantize with
    ``(q.astype(f32) * safe[..., None]).reshape(..)[..., :last]``."""
    xf = x.astype(jnp.float32)
    if xf.ndim == 0:
        xf = xf[None]
    last = xf.shape[-1]
    b = min(block, last)
    nb = -(-last // b)
    pad = nb * b - last
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, pad)])
    blocks = xf.reshape(*xf.shape[:-1], nb, b)
    scale = jnp.max(jnp.abs(blocks), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(blocks / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, safe, last


def pod_mean_compressed(delta: Any, pod_axis: str, block: int = BLOCK) -> Any:
    """Weighted-mean over the pod axis moving int8 on the wire.

    all_gather(int8 q, fp32 scales) over `pod`, dequantize locally, mean.
    Executed inside a manual-`pod` shard_map region.

    Quantization blocks run along the LAST axis only — flattening a
    (data, model)-sharded leaf forces GSPMD to replicate it per device
    (§Perf K3 first attempt: DCN term 2.7 s → 334 s); keeping the leaf's
    shape keeps its intra-pod sharding intact, so the pod gather moves
    ~1 byte/element of the device's shard, as intended."""

    def leaf(x):
        q, safe, last = _quantize_blocks_last_axis(x, block)
        padded_shape = q.shape[:-2] + (q.shape[-2] * q.shape[-1],)

        # ring exchange: P-1 point-to-point hops of the LOCAL int8 shard
        # (all_gather's concatenated output loses the intra-pod sharding
        # under GSPMD and replicates — measured 334 s of DCN on kimi;
        # ppermute moves exactly shard_bytes × (P−1) per device)
        n_pods = jax.lax.axis_size(pod_axis)
        perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]
        acc = q.astype(jnp.float32) * safe[..., None]
        qc, sc = q, safe
        for _ in range(n_pods - 1):
            qc = jax.lax.ppermute(qc, pod_axis, perm)
            sc = jax.lax.ppermute(sc, pod_axis, perm)
            acc = acc + qc.astype(jnp.float32) * sc[..., None]
        deq = acc / n_pods
        out = deq.reshape(*padded_shape)[..., :last]
        return out.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(leaf, delta)


def pod_mean(delta: Any, pod_axis: str) -> Any:
    """Uncompressed cross-pod mean (paper-faithful baseline)."""
    return jax.tree.map(lambda x: jax.lax.pmean(x, pod_axis), delta)
