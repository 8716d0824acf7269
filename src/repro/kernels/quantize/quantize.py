"""Pallas TPU kernels: per-block int8 quantization (DCN compression).

The cross-pod hop moves the aggregated update over slow DCN links;
quantizing to int8 with one fp32 scale per 256-lane block cuts wire
bytes ~4× (fp32) / ~2× (bf16).  Layout: flat N padded to blocks of
``QBLOCK``; kernel tiles ``ROWS_PER_CALL`` blocks per grid step so each
VMEM slab is (rows, 256) — lane-aligned for the VPU — and each scales
block is (rows,), aligned to XLA's 1-D tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 256          # elements per scale
# quant blocks per grid step -> (1024, 256) VMEM slab.  The (rows,)
# scales block must be a multiple of 1024: XLA tiles a 1-D f32 array
# T(1024) in HBM, and Mosaic refuses a block whose tiling disagrees
ROWS_PER_CALL = 1024


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # (rows, QBLOCK)
    amax = jnp.max(jnp.abs(x), axis=1)               # (rows,)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...]
    o_ref[...] = (q * s[:, None]).astype(o_ref.dtype)


def quantize_pallas(x_blocks: jnp.ndarray, *, interpret: bool = False):
    """x_blocks: (n_blocks, QBLOCK) fp32 -> (int8 same shape, fp32 scales)."""
    nb, qb = x_blocks.shape
    rows = min(ROWS_PER_CALL, nb)
    grid = (pl.cdiv(nb, rows),)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, qb), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, qb), lambda i: (i, 0)),
            pl.BlockSpec((rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, qb), jnp.int8),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
        ],
        interpret=interpret,
    )(x_blocks)


def dequantize_pallas(q: jnp.ndarray, scales: jnp.ndarray,
                      *, out_dtype=jnp.float32, interpret: bool = False):
    nb, qb = q.shape
    rows = min(ROWS_PER_CALL, nb)
    grid = (pl.cdiv(nb, rows),)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, qb), lambda i: (i, 0)),
            pl.BlockSpec((rows,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((rows, qb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, qb), out_dtype),
        interpret=interpret,
    )(q, scales)
