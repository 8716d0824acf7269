"""Fused FL round steps: the paper's aggregation pipeline as one XLA
program per round (train shapes), plus serving steps (prefill/decode).

One *fused* FL round (DESIGN.md §4):

  1. cohort updates — microbatches of client data produce model updates
     u_i = ∇loss (local_steps=1); cohorts are mapped onto the data mesh
     axes.  Intra-pod reduction of each u_i rides ICI — LIFL's *leaf
     aggregator* tier on the shared-memory-analogue fast tier.
  2. timing — "eager": u_i folded into a running (Σ wᵢuᵢ, Σ wᵢ)
     accumulator the moment it exists (Recv ∥ Agg overlap; O(1) update
     memory); "lazy": all u_i stacked, reduced once at the aggregation
     goal (O(n) queue memory — the broker-queue cost, visible in
     memory_analysis()).
  3. hierarchy — "hierarchical": grads computed inside a manual-`pod`
     shard_map; exactly one intermediate update per pod crosses DCN
     through an explicit, compressible collective (LIFL's *top
     aggregator*).  "flat": plain GSPMD grad; XLA emits one all-reduce
     over (pod, data) — the no-hierarchy baseline (paper §4.1 "NH").
  4. server optimizer applies the aggregated Δ (params donated —
     consume-in-place, the buffer-donation analogue of LIFL's
     zero-copy shared-memory object store).

In-graph sidecar metrics (update norm, aggregate weight, microbatches
seen) are fused into the step — metrics collection costs nothing when
no aggregation event runs (the eBPF property, DESIGN.md C4).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.fl.compression import pod_mean, pod_mean_compressed
from repro.fl.server import apply_server_opt, init_server_state
from repro.launch.mesh import dp_axes as mesh_dp_axes
from repro.launch.mesh import pod_axis as mesh_pod_axis
from repro.models import build_model
from repro.models.transformer import ModelOptions
from repro.sharding import batch_specs, cache_specs, divisibility_fix, param_specs


@dataclass(frozen=True)
class AggregationConfig:
    """LIFL aggregation knobs (the paper's C1/C9 + beyond-paper compress)."""

    hierarchy: str = "hierarchical"  # 'hierarchical' | 'flat'
    timing: str = "eager"            # 'eager' | 'lazy'
    compress: str = "none"           # 'none' | 'int8'
    num_microbatches: int = 4        # model updates arriving per pod per round
    server_opt: str = "fedavg"
    server_lr: float = 1.0
    acc_dtype: str = "float32"       # eager-accumulator dtype (bf16 for 1T-scale)


# ---------------------------------------------------------------------------
# microbatch update accumulation (eager vs lazy)
# ---------------------------------------------------------------------------


def _split_micro(batch: Dict[str, jnp.ndarray], n: int) -> Dict[str, jnp.ndarray]:
    def f(x):
        b = x.shape[0]
        assert b % n == 0, f"global batch {b} not divisible by {n} microbatches"
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(f, batch)


def _cohort_update(model, params, mb):
    """One arriving model update: (grads, weight, metrics)."""

    def loss_fn(p):
        loss, aux = model.loss(p, mb)
        return loss, aux

    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    weight = jnp.sum((mb["labels"] >= 0).astype(jnp.float32))
    return grads, weight, loss


def accumulate_updates(model, params, batch, agg: AggregationConfig):
    """-> (delta = weighted-mean update, total_weight, metrics)."""
    micro = _split_micro(batch, agg.num_microbatches)

    if agg.timing == "eager":
        # Fold each arriving update into the running accumulator (paper
        # §5.4, App-G: Recv ∥ Agg; FedAvg cumulative averaging).  O(1)
        # extra memory; the scan carry is donated/aliased by XLA.
        def body(carry, mb):
            acc, wsum, loss_sum = carry
            g, w, loss = _cohort_update(model, params, mb)
            acc = jax.tree.map(
                lambda a, gg: a + w * gg.astype(a.dtype), acc, g
            )
            return (acc, wsum + w, loss_sum + loss), None

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (acc, wsum, loss_sum), _ = jax.lax.scan(
            body, (acc0, jnp.float32(0), jnp.float32(0)), micro
        )
    else:
        # Lazy: queue all updates (the message-broker pattern), reduce at
        # the aggregation goal.  O(n_updates) live memory — the cost LIFL
        # §4.2 eliminates; left as the measurable baseline.
        def one(mb):
            g, w, loss = _cohort_update(model, params, mb)
            return jax.tree.map(lambda x: x.astype(jnp.float32), g), w, loss

        gs, ws, losses = jax.lax.map(one, micro)  # stacked: (n, ...) queue
        acc = jax.tree.map(lambda g: jnp.tensordot(ws, g, axes=1), gs)
        wsum, loss_sum = jnp.sum(ws), jnp.sum(losses)

    delta = jax.tree.map(lambda a: a / jnp.maximum(wsum, 1.0), acc)
    return delta, wsum, loss_sum / agg.num_microbatches


# ---------------------------------------------------------------------------
# train step builders
# ---------------------------------------------------------------------------


def _metrics(delta, wsum, loss, n_updates):
    """eBPF-sidecar analogue: metrics fused into the aggregation event."""
    sq = sum(
        jnp.sum(jnp.square(l.astype(jnp.float32))) for l in jax.tree.leaves(delta)
    )
    return {
        "loss": loss,
        "update_norm": jnp.sqrt(sq),
        "aggregate_weight": wsum,
        "updates_aggregated": jnp.int32(n_updates),
    }


def build_train_step(
    cfg: ArchConfig,
    mesh,
    agg: AggregationConfig,
    opts: Optional[ModelOptions] = None,
):
    """-> (train_step(params, server_state, batch) -> (params', state', metrics),
           model).  Call under ``jax.set_mesh(mesh)`` / lower with shardings
           from :func:`train_shardings`."""
    dp = mesh_dp_axes(mesh)
    pod = mesh_pod_axis(mesh)
    opts = opts or ModelOptions(
        attn_impl="chunked_sp",  # context-parallel flash (DESIGN.md §5)
        moe_impl="ep" if cfg.moe is not None else "dense",
        ssm_impl="sharded",      # §Perf F1
        dp_axes=dp if (agg.hierarchy == "flat" or pod is None) else ("data",),
        model_axis="model",
        vocab_axis="model",
    )
    model = build_model(cfg, opts)

    def flat_step(params, server_state, batch):
        delta, wsum, loss = accumulate_updates(model, params, batch, agg)
        # flat: XLA's automatic all-reduce over (pod, data) — NH baseline
        new_params, new_state = apply_server_opt(
            agg.server_opt, params, server_state, delta, lr=agg.server_lr
        )
        return new_params, new_state, _metrics(delta, wsum, loss, agg.num_microbatches)

    if pod is None or agg.hierarchy == "flat":
        return flat_step, model

    # hierarchical: manual over `pod`, GSPMD-auto inside the pod
    def hier_step(params, server_state, batch):
        def per_pod(p, b):
            delta, wsum, loss = accumulate_updates(model, p, b, agg)
            # ---- LIFL top aggregator: the only DCN crossing ----
            if agg.compress == "int8":
                delta = pod_mean_compressed(delta, pod)
            else:
                delta = pod_mean(delta, pod)
            wsum = jax.lax.psum(wsum, pod)
            loss = jax.lax.pmean(loss, pod)
            return delta, wsum, loss

        n_axes = jax.tree.map(lambda _: P(), params)
        delta, wsum, loss = jax.shard_map(
            per_pod,
            mesh=mesh,
            in_specs=(n_axes, jax.tree.map(lambda x: P("pod"), batch)),
            out_specs=(n_axes, P(), P()),
            axis_names={"pod"},
            check_vma=False,
        )(params, batch)
        new_params, new_state = apply_server_opt(
            agg.server_opt, params, server_state, delta, lr=agg.server_lr
        )
        return new_params, new_state, _metrics(
            delta, wsum, loss, agg.num_microbatches * mesh.shape["pod"]
        )

    return hier_step, model


# ---------------------------------------------------------------------------
# serving step builders
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ArchConfig, mesh, opts: Optional[ModelOptions] = None):
    dp = mesh_dp_axes(mesh)
    opts = opts or ModelOptions(
        attn_impl="chunked_sp",
        moe_impl="ep" if cfg.moe is not None else "dense",
        ssm_impl="sharded",
        dp_axes=dp, model_axis="model", vocab_axis="model",
    )
    model = build_model(cfg, opts)

    def prefill_step(params, batch):
        return model.prefill(params, batch)

    return prefill_step, model


def build_decode_step(cfg: ArchConfig, mesh, opts: Optional[ModelOptions] = None):
    dp = mesh_dp_axes(mesh)
    opts = opts or ModelOptions(
        moe_impl="ep" if cfg.moe is not None else "dense",
        dp_axes=dp, model_axis="model", vocab_axis="model",
    )
    model = build_model(cfg, opts)

    def decode_step(params, tokens, caches, pos):
        return model.decode_step(params, tokens, caches, pos)

    return decode_step, model


# ---------------------------------------------------------------------------
# abstract inputs + shardings (dry-run contract)
# ---------------------------------------------------------------------------


def abstract_params(model) -> Any:
    """ShapeDtypeStruct param pytree — no allocation."""
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    train:   {"tokens","labels"[,"frontend"]}   (global_batch, seq)
    prefill: {"tokens"[,"frontend"]}
    decode:  {"tokens": (B,1), "pos": scalar}  (+ caches built separately)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    emb_dtype = jnp.dtype(cfg.dtype)
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        out["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:  # decode
        out["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        out["pos"] = jax.ShapeDtypeStruct((), i32)
    if cfg.frontend and shape.kind in ("train", "prefill"):
        out["frontend"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), emb_dtype
        )
    return out


def abstract_caches(model, shape: ShapeConfig):
    return jax.eval_shape(
        lambda: model.init_decode(shape.global_batch, shape.seq_len)
    )


def train_shardings(model, mesh, agg: AggregationConfig, fsdp=None):
    """(in_shardings pytree of PartitionSpecs) for (params, state, batch)."""
    dp = mesh_dp_axes(mesh)
    if fsdp is None:
        fsdp = dp if agg.hierarchy == "flat" else ("data",)
    aparams = abstract_params(model)
    pspecs = divisibility_fix(param_specs(aparams, fsdp=fsdp), aparams, mesh)
    state = jax.eval_shape(partial(init_server_state, agg.server_opt), aparams)
    sspecs = divisibility_fix(param_specs(state, fsdp=fsdp), state, mesh)
    return pspecs, sspecs


def serve_shardings(model, mesh, fsdp=("data",)):
    aparams = abstract_params(model)
    return divisibility_fix(param_specs(aparams, fsdp=fsdp), aparams, mesh)
