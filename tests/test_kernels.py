"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes (assignment requirement (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import fedavg_oracle
from repro.kernels.fedavg import (
    eager_accumulate,
    fedavg_accumulate_k,
    fedavg_reduce,
    fedavg_reduce_tree,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quantize import QBLOCK, dequantize, quantize

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# fedavg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,N", [(2, 64), (4, 1000), (8, 8192 + 17), (3, 64 * 128 * 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_pallas_vs_ref(K, N, dtype):
    U = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    W = jnp.asarray(RNG.uniform(0.5, 4.0, size=(K,)), jnp.float32)
    got = fedavg_reduce(U, W, impl="pallas_interpret")
    ref = fedavg_reduce(U, W, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    oracle = fedavg_oracle(
        [np.asarray(u, np.float32) for u in U], [float(w) for w in W]
    )
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), oracle, rtol=tol, atol=tol)


@pytest.mark.parametrize("N", [64, 999, 64 * 128 + 1])
def test_eager_accumulate_pallas_vs_ref(N):
    acc = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    got = eager_accumulate(acc.copy(), u, 1.75, impl="pallas_interpret")
    ref = acc + 1.75 * u
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("K,N", [(2, 64), (5, 999), (8, 64 * 128 + 1)])
def test_fedavg_accumulate_k_pallas_vs_ref(K, N):
    """K-way burst fold (aliased accumulator, single grid sweep)."""
    acc = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    U = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    W = jnp.asarray(RNG.uniform(0.5, 4.0, size=(K,)), jnp.float32)
    got = fedavg_accumulate_k(acc.copy(), U, W, impl="pallas_interpret")
    ref = acc + jnp.sum(U * W[:, None], axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # a burst then one single fold == K+1 sequential folds
    seq = acc
    for k in range(K):
        seq = eager_accumulate(seq, U[k], W[k], impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(seq),
                               rtol=1e-5, atol=1e-5)


def test_fedavg_reduce_tree_matches_oracle():
    trees = [
        {"a": jnp.asarray(RNG.normal(size=(7, 3)), jnp.float32),
         "b": [jnp.asarray(RNG.normal(size=(11,)), jnp.float32)]}
        for _ in range(5)
    ]
    ws = [1.0, 2.0, 0.5, 3.0, 1.5]
    got = fedavg_reduce_tree(trees, ws, impl="jnp")
    for path in ("a",):
        oracle = fedavg_oracle([np.asarray(t["a"]) for t in trees], ws)
        np.testing.assert_allclose(np.asarray(got["a"]), oracle, rtol=1e-5)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N", [QBLOCK, QBLOCK * 3 + 5, 100, 70000])
def test_quantize_pallas_vs_ref_and_error_bound(N):
    x = jnp.asarray(RNG.normal(size=(N,)) * 3, jnp.float32)
    qp, sp = quantize(x, impl="pallas_interpret")
    qr, sr = quantize(x, impl="jnp")
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), rtol=1e-6)
    back = dequantize(qp, sp, N, impl="pallas_interpret")
    # error bound: |x - deq| <= scale/2 per block
    err = np.abs(np.asarray(back) - np.asarray(x))
    scales = np.repeat(np.asarray(sp), QBLOCK)[:N]
    assert np.all(err <= scales / 2 + 1e-7)


def test_quantize_zero_block():
    x = jnp.zeros((QBLOCK * 2,), jnp.float32)
    q, s = quantize(x, impl="pallas_interpret")
    back = dequantize(q, s, x.shape[0], impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(back), 0.0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,K,G,D,window", [
    (1, 128, 1, 1, 32, -1),
    (2, 256, 2, 3, 64, -1),
    (1, 256, 4, 1, 64, 64),
    (2, 192, 2, 2, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_vs_naive(B, S, K, G, D, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, K, G, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, K, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, K, D)), dtype)
    scale = D ** -0.5
    out = flash_attention(
        q, k, v, window=window, causal=True, scale=scale,
        impl="pallas_interpret", bq=64, bk=64,
    )
    ref = attention_ref(
        q.astype(jnp.float32).reshape(B, S, K * G, D).transpose(0, 2, 1, 3),
        k.astype(jnp.float32).transpose(0, 2, 1, 3),
        v.astype(jnp.float32).transpose(0, 2, 1, 3),
        scale=scale, window=window, causal=True,
    ).transpose(0, 2, 1, 3).reshape(B, S, K, G, D)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


# ---------------------------------------------------------------------------
# impl="auto": compiled Pallas on TPU, the jnp reference on CPU, never a
# silent interpret-mode run
# ---------------------------------------------------------------------------

def test_flash_auto_on_cpu_runs_reference_not_interpreter(monkeypatch):
    from repro.kernels.flash_attention import ops as fa_ops

    def no_kernel(*args, **kwargs):
        raise AssertionError("impl='auto' reached the Pallas kernel on CPU")

    monkeypatch.setattr(fa_ops, "flash_attention_fwd_pallas", no_kernel)
    B, S, K, G, D = 1, 96, 2, 1, 16     # a shape no other test traces
    q = jnp.asarray(RNG.normal(size=(B, S, K, G, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, K, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, K, D)), jnp.float32)
    out = flash_attention(q, k, v, scale=0.25, impl="auto")
    ref = flash_attention(q, k, v, scale=0.25, impl="jnp")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("platform,expected", [
    ("tpu", (True, False)), ("cpu", (False, False))])
def test_auto_impl_follows_platform(monkeypatch, platform, expected):
    from repro.kernels.impl import use_pallas

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert use_pallas("auto") == expected
    assert use_pallas("pallas_interpret") == (True, True)


def test_auto_impl_unknown_platform_raises(monkeypatch):
    from repro.kernels.impl import use_pallas

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="gpu"):
        use_pallas("auto")
    with pytest.raises(ValueError, match="unknown impl"):
        use_pallas("warpdrive")


def test_flash_vjp_matches_naive_grads():
    from repro.models.flash import flash_self_attention
    from repro.models.attention import _attend_naive

    B, S, K, G, D = 2, 64, 2, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, K, G, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, K, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, K, D)), jnp.float32)
    pos = jnp.arange(S)
    for window in (-1, 8):
        gn = jax.grad(
            lambda q, k, v: jnp.sum(
                _attend_naive(q, k, v, pos, pos, window, True, 0.25) ** 2
            ), argnums=(0, 1, 2),
        )(q, k, v)
        gf = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_self_attention(q, k, v, window, True, 0.25, 16) ** 2
            ), argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gn, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
