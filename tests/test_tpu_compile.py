"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each test compiles one kernel for a described (not
attached) ``v5e:2x2`` topology at the ResNet-18 update size and checks
that the compiled program holds the Mosaic kernel (``tpu_custom_call``)
— catching what interpret mode cannot, such as block shapes the chip's
compiler refuses.  The topology is described in a fixture, never at
import, so only the worker that runs this file loads the TPU library.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels.fedavg import (  # noqa: E402
    eager_accumulate,
    fedavg_accumulate_k,
    fedavg_reduce,
)
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.quantize import QBLOCK, dequantize, quantize  # noqa: E402

N = 11_199_486          # ResNet-18 flat update (f32 elements)
K = 8


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure to describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fedavg_accumulate_k_compiles(one_chip, dtype):
    _assert_kernel(fedavg_accumulate_k, _sds((N,), jnp.float32, one_chip),
                   _sds((K, N), dtype, one_chip),
                   _sds((K,), jnp.float32, one_chip), impl="pallas")


def test_eager_accumulate_compiles(one_chip):
    _assert_kernel(eager_accumulate, _sds((N,), jnp.float32, one_chip),
                   _sds((N,), jnp.float32, one_chip),
                   _sds((), jnp.float32, one_chip), impl="pallas")


def test_fedavg_reduce_compiles(one_chip):
    _assert_kernel(fedavg_reduce, _sds((K, N), jnp.float32, one_chip),
                   _sds((K,), jnp.float32, one_chip), impl="pallas")


def test_quantize_compiles(one_chip):
    _assert_kernel(quantize, _sds((N,), jnp.float32, one_chip),
                   impl="pallas")


def test_dequantize_compiles(one_chip):
    nb = -(-N // QBLOCK)
    _assert_kernel(dequantize, _sds((nb, QBLOCK), jnp.int8, one_chip),
                   _sds((nb,), jnp.float32, one_chip), n=N, impl="pallas")


def test_flash_attention_compiles(one_chip):
    B, S, KV, G, D = 1, 2048, 8, 2, 128
    _assert_kernel(flash_attention,
                   _sds((B, S, KV, G, D), jnp.bfloat16, one_chip),
                   _sds((B, S, KV, D), jnp.bfloat16, one_chip),
                   _sds((B, S, KV, D), jnp.bfloat16, one_chip),
                   scale=D ** -0.5, impl="pallas")
