"""Jitted wrappers: flat-array int8 compress/decompress."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.impl import use_pallas
from repro.kernels.quantize.quantize import (
    QBLOCK,
    dequantize_pallas,
    quantize_pallas,
)
from repro.kernels.quantize.ref import dequantize_ref, quantize_ref


@partial(jax.jit, static_argnames=("impl",))
def quantize(x: jnp.ndarray, *, impl: str = "auto"):
    """flat (N,) -> (q (nb, QBLOCK) int8, scales (nb,) fp32, N)."""
    n = x.shape[0]
    nb = -(-n // QBLOCK)
    xp = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, nb * QBLOCK - n))
    blocks = xp.reshape(nb, QBLOCK)
    pallas, interp = use_pallas(impl)
    if pallas:
        q, s = quantize_pallas(blocks, interpret=interp)
    else:
        q, s = quantize_ref(blocks)
    return q, s


@partial(jax.jit, static_argnames=("n", "impl"))
def dequantize(q: jnp.ndarray, scales: jnp.ndarray, n: int,
               *, impl: str = "auto") -> jnp.ndarray:
    pallas, interp = use_pallas(impl)
    if pallas:
        out = dequantize_pallas(q, scales, interpret=interp)
    else:
        out = dequantize_ref(q, scales)
    return out.reshape(-1)[:n]
