"""Mamba-1 selective SSM block, TPU-adapted (chunked parallel scan).

GPU Mamba uses a hand-written CUDA "hardware-aware" scan that never
materializes the (B, S, d_inner, N) state tensor in HBM.  The TPU-native
adaptation here blocks the sequence into chunks of ``chunk`` steps:

  * within a chunk: an associative scan over affine maps
    (a_t, b_t) with (a2,b2)∘(a1,b1) = (a1·a2, a2·b1 + b2) — log-depth,
    MXU/VPU friendly, and the materialized state is only
    (B, chunk, d_inner, N);
  * across chunks: a sequential ``lax.scan`` carrying the (B, d_inner, N)
    state — O(S/chunk) steps.

Decode is a single affine state update: O(1) in context length, which is
why falcon-mamba is the long_500k flagship.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


from repro.configs.base import ArchConfig
from repro.models.layers import dense_init


def init_ssm(key, cfg: ArchConfig, d_model: int, dtype) -> dict:
    s = cfg.ssm
    d_in = s.expand * d_model
    dt_rank = s.resolved_dt_rank(d_model)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], (d_model, 2 * d_in), dtype),
        "conv_w": dense_init(ks[1], (s.d_conv, d_in), dtype, scale=0.5),
        "x_proj": dense_init(ks[2], (d_in, dt_rank + 2 * s.d_state), dtype),
        "dt_proj": dense_init(ks[3], (dt_rank, d_in), dtype),
        "dt_bias": jnp.full((d_in,), -4.6, dtype=jnp.float32),  # softplus ≈ 0.01
        "A_log": jnp.log(
            jnp.tile(jnp.arange(1, s.d_state + 1, dtype=jnp.float32), (d_in, 1))
        ),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[4], (d_in, d_model), dtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, state: jnp.ndarray = None):
    """Depthwise causal conv. x: (B,S,C), w: (K,C).  With ``state``
    (B,K-1,C) the left context comes from the decode buffer."""
    K = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    # sum_k w[k] * x[t - (K-1) + k]
    S = x.shape[1]
    out = jnp.zeros_like(x)
    for k in range(K):
        out = out + xp[:, k : k + S, :] * w[k][None, None, :]
    return out


def _ssm_inputs(cfg: ArchConfig, params, u: jnp.ndarray):
    """u: (B,S,d_in) post-conv activations -> (dt, B_t, C_t, A)."""
    s = cfg.ssm
    dt_rank = params["dt_proj"].shape[0]
    proj = u @ params["x_proj"]  # (B,S,dt_rank+2N)
    dt_low = proj[..., :dt_rank]
    B_t = proj[..., dt_rank : dt_rank + s.d_state].astype(jnp.float32)
    C_t = proj[..., dt_rank + s.d_state :].astype(jnp.float32)
    dt = jax.nn.softplus(
        (dt_low @ params["dt_proj"]).astype(jnp.float32) + params["dt_bias"]
    )  # (B,S,d_in)
    A = -jnp.exp(params["A_log"])  # (d_in, N)
    return dt, B_t, C_t, A


def _affine_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def ssm_scan_chunked(
    cfg: ArchConfig,
    params: dict,
    u: jnp.ndarray,  # (B, S, d_in) conv+silu output
    h0: jnp.ndarray,  # (B, d_in, N) fp32 initial state
    chunk: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (y (B,S,d_in), h_final)."""
    B, S, d_in = u.shape
    N = cfg.ssm.d_state
    chunk = min(chunk, S)
    while S % chunk:  # largest divisor of S not exceeding the requested chunk
        chunk -= 1
    n = S // chunk

    dt, B_t, C_t, A = _ssm_inputs(cfg, params, u)
    uf = u.astype(jnp.float32)

    def chunk_body(h, xs):
        dt_c, B_c, C_c, u_c = xs  # (B, c, ·)
        a = jnp.exp(dt_c[..., None] * A[None, None])            # (B,c,d_in,N)
        b = (dt_c * u_c)[..., None] * B_c[:, :, None, :]        # (B,c,d_in,N)
        a_cum, h_intra = jax.lax.associative_scan(_affine_combine, (a, b), axis=1)
        h_t = a_cum * h[:, None] + h_intra                      # (B,c,d_in,N)
        y_c = jnp.einsum("bcdn,bcn->bcd", h_t, C_c)
        return h_t[:, -1], y_c

    xs = tuple(
        t.reshape(B, n, chunk, *t.shape[2:]).swapaxes(0, 1)
        for t in (dt, B_t, C_t, uf)
    )
    h_final, ys = jax.lax.scan(chunk_body, h0, xs)
    y = ys.swapaxes(0, 1).reshape(B, S, d_in)
    y = y + uf * params["D"][None, None]
    return y, h_final


def ssm_scan_sharded(
    cfg: ArchConfig,
    params: dict,
    u: jnp.ndarray,
    h0: jnp.ndarray,
    *,
    chunk: int,
    dp_axes,
    model_axis: str,
    intra_chunk: str = "seq",
):
    """§Perf iteration F1: the chunked scan inside a shard_map (batch →
    data axes, d_inner → model axis).

    GSPMD cannot infer shardings through ``associative_scan``'s log-depth
    combinator tree, so the baseline materializes replicated
    (B, chunk, d_inner, N) state tensors — measured 779 s of HBM time on
    falcon-mamba train_4k.  Manual sharding keeps every scan operand
    local; the only collective is one small psum for the x_proj
    contraction over the sharded d_inner.  The chunk body is
    checkpointed so the backward recomputes in-chunk state instead of
    saving 8 log-levels of it."""
    from jax.sharding import PartitionSpec as P

    scan_params = {
        k: params[k]
        for k in ("x_proj", "dt_proj", "dt_bias", "A_log", "D")
    }
    pspecs = {
        "x_proj": P(model_axis, None),   # (d_in, dt_rank+2N): contract -> psum
        "dt_proj": P(None, model_axis),
        "dt_bias": P(model_axis),
        "A_log": P(model_axis, None),
        "D": P(model_axis),
    }

    def body(p, u_loc, h_loc):
        def inputs_fn(cfg_, p_, u_):
            # replicate _ssm_inputs with the sharded contraction psum'd
            s = cfg_.ssm
            dt_rank = p_["dt_proj"].shape[0]
            proj = jax.lax.psum(u_ @ p_["x_proj"], model_axis)
            dt_low = proj[..., :dt_rank]
            B_t = proj[..., dt_rank : dt_rank + s.d_state].astype(jnp.float32)
            C_t = proj[..., dt_rank + s.d_state :].astype(jnp.float32)
            dt = jax.nn.softplus(
                (dt_low @ p_["dt_proj"]).astype(jnp.float32) + p_["dt_bias"]
            )
            A = -jnp.exp(p_["A_log"])
            return dt, B_t, C_t, A

        B, S, d_loc = u_loc.shape
        N = cfg.ssm.d_state
        c = min(chunk, S)
        while S % c:
            c -= 1
        n = S // c
        dt, B_t, C_t, A = inputs_fn(cfg, p, u_loc)
        uf = u_loc.astype(jnp.float32)

        @jax.checkpoint
        def chunk_body(h, xs):
            dt_c, B_c, C_c, u_c = xs  # (B,c,d) (B,c,N) (B,c,N) (B,c,d)
            if intra_chunk == "seq":
                # §Perf F2 (default): reads (a,b) inputs once per step and
                # never materializes (B,c,d,N) level tensors — 2.7× fewer
                # HBM bytes than the associative form under the corrected
                # cost model (22.3s vs 59.2s on falcon train_4k).  Trade:
                # serial steps; on TPU the same dataflow belongs in a
                # Pallas kernel (state in VMEM, lanes over (B,d,N)).
                def step(h_, ts):
                    dt_t, B_t_, C_t_, u_t = ts
                    a_t = jnp.exp(dt_t[..., None] * A[None])
                    b_t = (dt_t * u_t)[..., None] * B_t_[:, None, :]
                    h_ = a_t * h_ + b_t
                    y_t = jnp.einsum("bdn,bn->bd", h_, C_t_)
                    return h_, y_t

                ts = tuple(t.swapaxes(0, 1) for t in (dt_c, B_c, C_c, u_c))
                h_last, y_c = jax.lax.scan(step, h, ts)
                return h_last, y_c.swapaxes(0, 1)
            a = jnp.exp(dt_c[..., None] * A[None, None])
            b = (dt_c * u_c)[..., None] * B_c[:, :, None, :]
            a_cum, h_intra = jax.lax.associative_scan(
                _affine_combine, (a, b), axis=1
            )
            h_t = a_cum * h[:, None] + h_intra
            y_c = jnp.einsum("bcdn,bcn->bcd", h_t, C_c)
            return h_t[:, -1], y_c

        xs = tuple(
            t.reshape(B, n, c, *t.shape[2:]).swapaxes(0, 1)
            for t in (dt, B_t, C_t, uf)
        )
        h_final, ys = jax.lax.scan(chunk_body, h_loc, xs)
        y = ys.swapaxes(0, 1).reshape(B, S, d_loc)
        y = y + uf * p["D"][None, None]
        return y.astype(u_loc.dtype), h_final

    u_spec = P(dp_axes, None, model_axis)
    h_spec = P(dp_axes, model_axis, None)
    y, h_final = jax.shard_map(
        body,
        in_specs=(pspecs, u_spec, h_spec),
        out_specs=(u_spec, h_spec),
        check_vma=False,
        axis_names=set(dp_axes) | {model_axis},
    )(scan_params, u, h0)
    return y.astype(jnp.float32), h_final


def ssm_block(
    cfg: ArchConfig,
    params: dict,
    x: jnp.ndarray,  # (B, S, d_model)
    chunk: int = 256,
    *,
    dp_axes=(),
    model_axis: str = "model",
    sharded: bool = False,
) -> jnp.ndarray:
    """Full mamba block: in_proj -> conv -> SSM -> gate -> out_proj."""
    B, S, _ = x.shape
    d_in = params["dt_proj"].shape[1]
    xz = x @ params["in_proj"]
    u, z = xz[..., :d_in], xz[..., d_in:]
    u = jax.nn.silu(_causal_conv(u, params["conv_w"]))
    h0 = jnp.zeros((B, d_in, cfg.ssm.d_state), jnp.float32)
    if sharded:
        y, _ = ssm_scan_sharded(
            cfg, params, u, h0, chunk=chunk,
            dp_axes=dp_axes, model_axis=model_axis,
        )
    else:
        y, _ = ssm_scan_chunked(cfg, params, u, h0, chunk=chunk)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ params["out_proj"]


# ---------------------------------------------------------------------------
# decode: O(1) state update
# ---------------------------------------------------------------------------


def init_ssm_cache(cfg: ArchConfig, d_model: int, batch: int, dtype):
    s = cfg.ssm
    d_in = s.expand * d_model
    return {
        "h": jnp.zeros((batch, d_in, s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, d_in), dtype),
    }


def ssm_decode(cfg: ArchConfig, params: dict, x: jnp.ndarray, cache: dict):
    """x: (B, 1, d_model) -> (y (B,1,d_model), new cache)."""
    d_in = params["dt_proj"].shape[1]
    xz = x @ params["in_proj"]
    u, z = xz[..., :d_in], xz[..., d_in:]
    raw = u  # pre-conv input, buffered for the next step's conv window
    u = jax.nn.silu(_causal_conv(u, params["conv_w"], state=cache["conv"]))
    conv_new = jnp.concatenate([cache["conv"][:, 1:], raw], axis=1)

    dt, B_t, C_t, A = _ssm_inputs(cfg, params, u)
    a = jnp.exp(dt[:, 0, :, None] * A[None])                    # (B,d_in,N)
    b = (dt[:, 0] * u.astype(jnp.float32)[:, 0])[..., None] * B_t[:, 0, None, :]
    h = a * cache["h"] + b
    y = jnp.einsum("bdn,bn->bd", h, C_t[:, 0])
    y = y + u.astype(jnp.float32)[:, 0] * params["D"][None]
    y = (y[:, None].astype(x.dtype)) * jax.nn.silu(z)
    return y @ params["out_proj"], {"h": h, "conv": conv_new}
