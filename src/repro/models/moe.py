"""Mixture-of-Experts: router + two dispatch implementations.

* ``dense``  — every expert computes every token, outputs weighted by the
  top-k gates.  O(E/k) FLOP waste; numerically exact (no token dropping).
  The oracle for tests and the impl for tiny smoke configs.
* ``ep``     — production expert-parallel dispatch as a ``shard_map`` over
  the mesh: experts sharded over the ``model`` axis, tokens sharded over
  the data axes and replicated across ``model``.  Each model shard
  gathers (via per-expert top-capacity selection) the tokens routed to
  its local experts, runs the expert FFNs as one batched matmul, and
  scatter-adds weighted outputs; a single ``psum`` over ``model``
  combines expert contributions — the same collective shape as a TP FFN,
  so no all-to-all is needed while activations are model-replicated.
  Capacity-overflow tokens are dropped (standard capacity-factor MoE).

Shared experts (DeepSeek/Kimi) are dense FFNs applied to every token.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.layers import dense_init, ffn, init_ffn


def init_moe(key, cfg: ArchConfig, dtype) -> dict:
    moe = cfg.moe
    d = cfg.d_model
    kr, ke, ks = jax.random.split(key, 3)
    ek = jax.random.split(ke, 3)
    p = {
        "router": dense_init(kr, (d, moe.num_experts), jnp.float32),
        # experts stacked on a leading E axis (sharded over `model`)
        "experts": {
            "gate": dense_init(ek[0], (moe.num_experts, d, moe.expert_d_ff), dtype),
            "up": dense_init(ek[1], (moe.num_experts, d, moe.expert_d_ff), dtype),
            "down": dense_init(ek[2], (moe.num_experts, moe.expert_d_ff, d), dtype),
        },
    }
    if moe.num_shared_experts:
        p["shared"] = init_ffn(
            ks, d, moe.num_shared_experts * moe.shared_d_ff, dtype
        )
    return p


def router_probs(router_w: jnp.ndarray, x: jnp.ndarray, top_k: int):
    """-> (gates (T,k) fp32 normalized, idx (T,k) int32, probs (T,E))."""
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balance_loss(probs: jnp.ndarray, idx: jnp.ndarray, num_experts: int):
    """Switch-style aux loss: E * Σ_e f_e · p_e."""
    T = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(T * idx.shape[-1], 1)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


# ---------------------------------------------------------------------------
# dense dispatch (oracle)
# ---------------------------------------------------------------------------


def _moe_dense(moe: MoEConfig, experts: dict, x2: jnp.ndarray, gates, idx):
    # x2: (T, D)
    h = jnp.einsum("td,edf->tef", x2, experts["gate"])
    u = jnp.einsum("td,edf->tef", x2, experts["up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, experts["down"])  # (T,E,D)
    onehot = jax.nn.one_hot(idx, moe.num_experts, dtype=gates.dtype)  # (T,k,E)
    w = jnp.einsum("tk,tke->te", gates, onehot)  # (T,E)
    return jnp.einsum("te,ted->td", w.astype(y.dtype), y)


# ---------------------------------------------------------------------------
# expert-parallel dispatch (shard_map)
# ---------------------------------------------------------------------------


def _ep_local(moe: MoEConfig, gate_w, up_w, down_w, x2, gates, idx, *, model_axis: str):
    """Body executed per model shard.  x2 (T,D) is replicated across the
    model axis; gate/up/down are the LOCAL (E_loc, ...) expert shards."""
    E_loc = gate_w.shape[0]
    T = x2.shape[0]
    shard = jax.lax.axis_index(model_axis)
    e_lo = shard * E_loc

    # gate matrix restricted to local experts: (T, E_loc) fp32
    local = (idx[..., None] == (e_lo + jnp.arange(E_loc))[None, None, :])
    g_local = jnp.sum(jnp.where(local, gates[..., None], 0.0), axis=1)  # (T,E_loc)

    cap = int(min(T, max(1, -(-T * moe.top_k * moe.capacity_factor // moe.num_experts))))
    # per-expert top-C token selection (capacity-based dispatch)
    chosen = (g_local > 0).astype(jnp.float32)
    sel_score = chosen.T  # (E_loc, T)
    _, sel_idx = jax.lax.top_k(sel_score, cap)  # (E_loc, C) token ids
    sel_gate = jnp.take_along_axis(g_local.T, sel_idx, axis=1)  # (E_loc, C)
    sel_valid = sel_gate > 0

    xe = jnp.take(x2, sel_idx.reshape(-1), axis=0).reshape(E_loc, cap, -1)
    h = jnp.einsum("ecd,edf->ecf", xe, gate_w)
    u = jnp.einsum("ecd,edf->ecf", xe, up_w)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, down_w)
    y = y * (sel_gate * sel_valid)[..., None].astype(y.dtype)

    out = jnp.zeros_like(x2).at[sel_idx.reshape(-1)].add(y.reshape(E_loc * cap, -1))
    return jax.lax.psum(out, model_axis)


def _moe_ep(
    moe: MoEConfig,
    experts: dict,
    x2: jnp.ndarray,
    gates,
    idx,
    *,
    dp_axes: Tuple[str, ...],
    model_axis: str,
):
    # Ambient-mesh shard_map: composes with an enclosing manual-`pod`
    # shard_map (hierarchical aggregation) and with plain GSPMD (flat).
    body = partial(_ep_local, moe, model_axis=model_axis)
    tok_spec = P(dp_axes)  # (T, D): T sharded over data axes, D replicated
    w_spec = P(model_axis)  # (E, ...) sharded over model axis
    return jax.shard_map(
        lambda g, u, d, x, gg, ii: body(g, u, d, x, gg, ii),
        in_specs=(w_spec, w_spec, w_spec, tok_spec, tok_spec, tok_spec),
        out_specs=tok_spec,
        check_vma=False,
        axis_names=set(dp_axes) | {model_axis},
    )(experts["gate"], experts["up"], experts["down"], x2, gates, idx)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def moe_block(
    cfg: ArchConfig,
    params: dict,
    x: jnp.ndarray,  # (B, S, D)
    *,
    impl: str = "dense",
    mesh=None,
    dp_axes: Tuple[str, ...] = (),
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (output (B,S,D), aux load-balance loss scalar)."""
    moe = cfg.moe
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    gates, idx, probs = router_probs(params["router"], x2, moe.top_k)
    aux = load_balance_loss(probs, idx, moe.num_experts)

    if impl == "dense":
        y = _moe_dense(moe, params["experts"], x2, gates, idx)
    elif impl == "ep":
        y = _moe_ep(
            moe, params["experts"], x2, gates, idx,
            dp_axes=dp_axes, model_axis=model_axis,
        )
    else:
        raise ValueError(f"unknown moe impl {impl!r}")

    if "shared" in params:
        y = y + ffn(params["shared"], x2)
    return y.reshape(B, S, D).astype(x.dtype), aux
