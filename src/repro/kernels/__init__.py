"""Pallas TPU kernels for the paper's compute hot-spots, each with an
ops.py jit wrapper and a ref.py pure-jnp oracle:

  fedavg/          K-way weighted reduce + in-place eager accumulate
                   (the §4.1 aggregation hot loop; input_output_aliases
                   = the kernel-level zero-copy consume)
  quantize/        per-block int8 quant/dequant (DCN update compression)
  flash_attention/ blockwise online-softmax attention forward

``impl.py`` resolves every wrapper's ``impl=`` (``"auto"``: compiled
Pallas on TPU, the jnp oracle on CPU).  All validated against their
oracles with interpret=True shape/dtype sweeps in tests/test_kernels.py,
and compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""
