"""Jitted wrapper exposing the Pallas flash kernel through the model
attention interface ((B, S, K, G, D) layout used by models/attention)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    GLOBAL,
    flash_attention_fwd_pallas,
)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.impl import use_pallas


@partial(
    jax.jit,
    static_argnames=("window", "causal", "scale", "impl", "bq", "bk"),
)
def flash_attention(
    q: jnp.ndarray,      # (B, S, K, G, D)
    k: jnp.ndarray,      # (B, S, K, D)
    v: jnp.ndarray,      # (B, S, K, Dv)
    qpos=None,
    kpos=None,
    *,
    window: int = GLOBAL,
    causal: bool = True,
    scale: float = 1.0,
    impl: str = "auto",
    bq: int = 128,
    bk: int = 512,
) -> jnp.ndarray:
    """-> (B, S, K, G, Dv).  qpos/kpos accepted for interface parity with
    the chunked impl; the kernel assumes self-attention (arange)."""
    B, S, K, G, D = q.shape
    Dv = v.shape[-1]
    qh = q.reshape(B, S, K * G, D).transpose(0, 2, 1, 3)   # (B,H,S,D)
    kh = k.transpose(0, 2, 1, 3)                            # (B,K,S,D)
    vh = v.transpose(0, 2, 1, 3)
    pallas, interp = use_pallas(impl)
    if pallas:
        out = flash_attention_fwd_pallas(
            qh, kh, vh, scale=scale, window=window, causal=causal,
            bq=bq, bk=bk, interpret=interp,
        )
    else:
        out = attention_ref(qh, kh, vh, scale=scale, window=window, causal=causal)
    return out.transpose(0, 2, 1, 3).reshape(B, S, K, G, Dv)
