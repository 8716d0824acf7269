"""Vocab-sharded embedding / unembedding / cross-entropy.

A vocab-sharded table with a plain ``jnp.take`` trips XLA's involuntary
full rematerialization (the table gets replicated per device — measured
+47 GB temp on llama3.2-3b train_4k).  The production pattern instead
keeps the table P(model, None) and does an ownership-masked local gather
with a psum over ``model``; the unembedding computes vocab-shard-local
logits so the (tokens, V) matrix is never assembled, with logsumexp /
label-gather reduced by tiny (tokens,) psums.

Vocab is padded to a multiple of 256 (model-axis shards × lane
alignment); padded logits are masked out of the CE and stripped from
decode logits.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

VOCAB_PAD_MULTIPLE = 256


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


# ---------------------------------------------------------------------------
# embedding lookup
# ---------------------------------------------------------------------------


def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray,
                 model_axis: Optional[str]) -> jnp.ndarray:
    """tokens (B,S) -> (B,S,D).  Table rows sharded over ``model_axis``."""
    if model_axis is None:
        return jnp.take(table, tokens, axis=0)

    def body(tbl, tok):
        v_loc = tbl.shape[0]
        lo = jax.lax.axis_index(model_axis) * v_loc
        idx = tok - lo
        ok = (idx >= 0) & (idx < v_loc)
        rows = jnp.take(tbl, jnp.clip(idx, 0, v_loc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        # f32 psum: exactly one shard contributes per token (no precision
        # cost) and bf16 collectives trip an XLA:CPU float-normalization
        # CHECK ("Invalid binary instruction opcode copy") in this path.
        return jax.lax.psum(rows.astype(jnp.float32), model_axis).astype(tbl.dtype)

    return jax.shard_map(
        body,
        in_specs=(P(model_axis, None), P()),
        out_specs=P(),
        check_vma=False,
        axis_names={model_axis},
    )(table, tokens)


# ---------------------------------------------------------------------------
# chunked cross-entropy with vocab-shard-local logits
# ---------------------------------------------------------------------------


def _ce_chunk_local(w_chunk, h, y, *, vocab: int, tied: bool, model_axis: str):
    """Executed per model shard: local logits + CE partials."""
    v_loc = w_chunk.shape[0] if tied else w_chunk.shape[1]
    lo = jax.lax.axis_index(model_axis) * v_loc
    hf = h.astype(jnp.float32)
    wf = w_chunk.astype(jnp.float32)
    logits = hf @ (wf.T if tied else wf)  # (B, c, v_loc)
    # mask padded vocab rows out of the softmax
    col = lo + jnp.arange(v_loc)
    logits = jnp.where((col < vocab)[None, None, :], logits, -1e30)

    # softmax is shift-invariant: the max needs no gradient (and pmax has
    # no differentiation rule anyway)
    m_loc = jnp.max(jax.lax.stop_gradient(logits), axis=-1)
    m = jax.lax.stop_gradient(jax.lax.pmax(m_loc, model_axis))
    z = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), model_axis)
    logz = m + jnp.log(z)

    idx = y - lo
    ok = (idx >= 0) & (idx < v_loc)
    tok_logit = jnp.take_along_axis(
        logits, jnp.clip(idx, 0, v_loc - 1)[..., None], axis=-1
    )[..., 0]
    tok_logit = jax.lax.psum(jnp.where(ok, tok_logit, 0.0), model_axis)

    valid = (y >= 0).astype(jnp.float32)
    return jnp.sum((logz - tok_logit) * valid), jnp.sum(valid)


def chunked_lm_loss_sharded(
    hidden: jnp.ndarray,     # (B, S, D)
    w: jnp.ndarray,          # (Vp, D) tied or (D, Vp)
    labels: jnp.ndarray,     # (B, S) int32, -1 ignore
    *,
    vocab: int,
    tied: bool,
    model_axis: Optional[str],
    chunk: int = 256,
):
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    n = S // chunk

    if model_axis is None:
        from repro.models.layers import chunked_lm_loss

        wt = w[:vocab] if tied else w[:, :vocab]
        return chunked_lm_loss(hidden, wt, labels, tied, chunk=chunk)

    w_spec = P(model_axis, None) if tied else P(None, model_axis)

    @jax.checkpoint
    def chunk_loss(h_c, y_c):
        # f32 at the shard_map boundary: the transpose rule psums the
        # replicated-input cotangent over `model`, and bf16 collectives
        # hit an XLA:CPU float-normalization CHECK failure.
        return jax.shard_map(
            lambda wc, hh, yy: _ce_chunk_local(
                wc, hh, yy, vocab=vocab, tied=tied, model_axis=model_axis
            ),
            in_specs=(w_spec, P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
            axis_names={model_axis},
        )(w, h_c.astype(jnp.float32), y_c)

    def body(carry, xs):
        h_c, y_c = xs
        l, c = chunk_loss(h_c, y_c)
        return (carry[0] + l, carry[1] + c), None

    hs = hidden.reshape(B, n, chunk, D).swapaxes(0, 1)
    ys = labels.reshape(B, n, chunk).swapaxes(0, 1)
    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0)), (hs, ys)
    )
    return total / jnp.maximum(count, 1.0)


# ---------------------------------------------------------------------------
# decode logits
# ---------------------------------------------------------------------------


def decode_logits(hidden: jnp.ndarray, w: jnp.ndarray, *, vocab: int,
                  tied: bool, model_axis: Optional[str]) -> jnp.ndarray:
    """(B, 1, D) -> (B, 1, vocab) fp32 (replicated)."""
    if model_axis is None:
        wt = w[:vocab] if tied else w[:, :vocab]
        return hidden.astype(jnp.float32) @ (
            wt.T.astype(jnp.float32) if tied else wt.astype(jnp.float32)
        )

    w_spec = P(model_axis, None) if tied else P(None, model_axis)

    def body(wc, h):
        hf = h.astype(jnp.float32)
        wf = wc.astype(jnp.float32)
        logits = hf @ (wf.T if tied else wf)  # (B, 1, v_loc)
        return jax.lax.all_gather(logits, model_axis, axis=2, tiled=True)

    full = jax.shard_map(
        body,
        in_specs=(w_spec, P()),
        out_specs=P(),
        check_vma=False,
        axis_names={model_axis},
    )(w, hidden)
    return full[..., :vocab]
