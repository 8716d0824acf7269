"""Blockwise self-attention with a flash-style custom VJP.

Differentiating through the online-softmax ``lax.scan`` saves the
(acc, m, l) carry per KV block — O(S·n_blocks) residuals (~25 GB/device
measured on llama3.2-3b train_4k).  Flash attention's defining trick is
the backward pass: save only (out, lse) per query and *recompute* the
probability block inside the gradient loop.  This module is that
backward, in pure JAX (the Pallas kernel in kernels/flash_attention is
its TPU twin; this one also lowers on CPU for the dry-run).

Positions are explicit: ``sq0`` (scalar offset of the q rows — the
shard's slice start under context parallelism) and ``kpos`` (int32
(Skv,) absolute positions of the kv rows, enabling window-limited KV
exchange where a shard holds a non-contiguous kv working set).  Both are
integer operands of the custom_vjp (float0 cotangents).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


from repro.configs.base import GLOBAL

_NEG_INF = -1e30


def _mask(sq0, Sq: int, kposb, window: int, causal: bool):
    """(Sq, bk) mask for q rows [sq0, sq0+Sq) vs kv rows at ``kposb``."""
    qpos = sq0 + jnp.arange(Sq)
    diff = qpos[:, None] - kposb[None, :]
    m = (diff >= 0) if causal else jnp.ones((Sq, kposb.shape[0]), bool)
    if window != GLOBAL:
        m = m & (diff < window)
    return m


def _fwd_scan(q, k, v, sq0, kpos, window, causal, scale, bk):
    """q (B,Sq,K,G,D), k/v (B,Skv,K,Dk/Dv) -> out (B,K,G,Sq,Dv), lse."""
    B, Sq, K, G, D = q.shape
    Skv = k.shape[1]
    Dk = k.shape[-1]
    Dv = v.shape[-1]
    nkv = -(-Skv // bk)
    pad = nkv * bk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded rows land in the future -> masked by causality/window
        kpos = jnp.concatenate(
            [kpos, jnp.full((pad,), 2 ** 30, kpos.dtype)]
        )
    kb = k.reshape(B, nkv, bk, K, Dk).swapaxes(0, 1)
    vb = v.reshape(B, nkv, bk, K, Dv).swapaxes(0, 1)
    pb = kpos.reshape(nkv, bk)
    qf = q.astype(jnp.float32)

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, pc = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32)) * scale
        msk = _mask(sq0, Sq, pc, window, causal)
        s = jnp.where(msk[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, K, G, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, K, G, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, pb))
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse  # out (B,K,G,Sq,Dv) fp32


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q, k, v, sq0, kpos, window: int, causal: bool, scale: float,
                bk: int):
    out, _ = _fwd_scan(q, k, v, sq0, kpos, window, causal, scale, bk)
    return out.transpose(0, 3, 1, 2, 4).astype(v.dtype)  # (B,Sq,K,G,Dv)


def _flash_fwd(q, k, v, sq0, kpos, window, causal, scale, bk):
    out, lse = _fwd_scan(q, k, v, sq0, kpos, window, causal, scale, bk)
    out_t = out.transpose(0, 3, 1, 2, 4).astype(v.dtype)
    return out_t, (q, k, v, sq0, kpos, out, lse)


def _flash_bwd(window, causal, scale, bk, res, do):
    q, k, v, sq0, kpos, out, lse = res  # out (B,K,G,Sq,Dv) fp32
    B, Sq, K, G, D = q.shape
    Skv = k.shape[1]
    Dk, Dv = k.shape[-1], v.shape[-1]
    nkv = -(-Skv // bk)
    pad = nkv * bk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.concatenate(
            [kpos, jnp.full((pad,), 2 ** 30, kpos.dtype)]
        )
    kb = k.reshape(B, nkv, bk, K, Dk).swapaxes(0, 1)
    vb = v.reshape(B, nkv, bk, K, Dv).swapaxes(0, 1)
    pb = kpos.reshape(nkv, bk)

    qf = q.astype(jnp.float32)
    dof = do.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # (B,K,G,Sq,Dv)
    delta = jnp.sum(dof * out, axis=-1)  # (B,K,G,Sq)

    def body(dq, xs):
        kc, vc, pc = xs
        kcf = kc.astype(jnp.float32)
        vcf = vc.astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kcf) * scale
        msk = _mask(sq0, Sq, pc, window, causal)
        s = jnp.where(msk[None, None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,K,G,Sq,bk)
        dv_j = jnp.einsum("bkgqs,bkgqd->bskd", p, dof)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", dof, vcf)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds, kcf)
        dk_j = jnp.einsum("bkgqs,bqkgd->bskd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Sq, K, G, D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(body, dq0, (kb, vb, pb))
    dk = dk.swapaxes(0, 1).reshape(B, nkv * bk, K, Dk)[:, :Skv]
    dv = dv.swapaxes(0, 1).reshape(B, nkv * bk, K, Dv)[:, :Skv]
    f0 = lambda x: jnp.zeros(jnp.shape(x), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            f0(sq0), f0(kpos))


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_self_attention(q, k, v, window: int, causal: bool, scale: float,
                         bk: int):
    """Single-region flash attention (q rows start at position 0)."""
    Skv = k.shape[1]
    return _flash_core(
        q, k, v, jnp.int32(0), jnp.arange(Skv, dtype=jnp.int32),
        window, causal, scale, bk,
    )


def flash_self_attention_sp(
    q, k, v, window: int, causal: bool, scale: float, bk: int,
    dp_axes, model_axis: str,
    window_limited: bool = True,
):
    """Context-parallel flash: q sequence-sharded over ``model_axis``.

    Global layers all-gather K/V over `model` (the textbook context-
    parallelism cost).  Sliding-window layers (§Perf G3) instead fetch
    only ceil(window/shard_len) neighbor shards via a collective-permute
    ring — wire bytes drop from S to (window + shard_len) per layer
    (4096→1280 on gemma's 1024-window layers at S=4k/16 shards).

    This also sidesteps head-count divisibility entirely (24 q-heads on
    a 16-way model axis cannot head-shard; GSPMD otherwise inserts
    per-step resharding collectives — measured 5k+ all-reduces per llama
    step).
    """
    from jax.sharding import PartitionSpec as P

    B, S = q.shape[:2]

    def body(qc, kc, vc):
        shards = jax.lax.axis_size(model_axis)
        L = S // shards
        idx = jax.lax.axis_index(model_axis)
        sq0 = idx * L

        hops = -(-window // L) if (window != GLOBAL and causal) else None
        if window_limited and hops is not None and hops < shards - 1:
            # ring fetch: shards i-hops .. i  (older kv first)
            blocks_k, blocks_v, blocks_p = [], [], []
            perm1 = [(s, (s + 1) % shards) for s in range(shards)]
            kh, vh = kc, vc
            fetched = []
            for h in range(1, hops + 1):
                kh = jax.lax.ppermute(kh, model_axis, perm1)
                vh = jax.lax.ppermute(vh, model_axis, perm1)
                src = idx - h
                pos = jnp.where(
                    src >= 0, src * L + jnp.arange(L), 2 ** 30
                ).astype(jnp.int32)
                fetched.append((kh, vh, pos))
            for kh, vh, pos in reversed(fetched):
                blocks_k.append(kh)
                blocks_v.append(vh)
                blocks_p.append(pos)
            blocks_k.append(kc)
            blocks_v.append(vc)
            blocks_p.append((sq0 + jnp.arange(L)).astype(jnp.int32))
            kf = jnp.concatenate(blocks_k, axis=1)
            vf = jnp.concatenate(blocks_v, axis=1)
            kpos = jnp.concatenate(blocks_p)
        else:
            kf = jax.lax.all_gather(kc, model_axis, axis=1, tiled=True)
            vf = jax.lax.all_gather(vc, model_axis, axis=1, tiled=True)
            kpos = jnp.arange(S, dtype=jnp.int32)
        return _flash_core(
            qc, kf, vf, sq0, kpos, window, causal, scale, min(bk, kf.shape[1])
        )

    spec_q = P(dp_axes, model_axis, None, None, None)
    spec_kv = P(dp_axes, model_axis, None, None)
    return jax.shard_map(
        body,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q,
        check_vma=False,
        axis_names=set(dp_axes) | {model_axis},
    )(q, k, v)
