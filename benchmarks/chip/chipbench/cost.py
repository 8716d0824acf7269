"""Operations and bytes that the aggregation path needs, from shapes.

These are the least the algorithm must move, not what the program
happens to move: a roofline share is achieved over minimal.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

ACC_BYTES = 4           # the running sum is f32


def accumulate_bytes(n: int, k: int, update_dtype) -> int:
    """One K-way fold ``acc += sum_k w[k] * u[k]`` over ``n`` elements:
    read the K update rows once, read and write the f32 accumulator
    once, and read the K f32 weights.  ``k == 1`` is ``eager_accumulate``;
    ``k > 1`` is ``fedavg_accumulate_k`` over a (K, N) slab."""
    b = np.dtype(update_dtype).itemsize
    return int(k * b * n + 2 * ACC_BYTES * n + 4 * k)


def accumulate_flops(n: int, k: int) -> int:
    """A multiply and an add per update element."""
    return int(2 * k * n)


def round_min_bytes(n: int, k: int, update_dtype) -> int:
    """The least one published round of K updates can move: each update
    read once, and the f32 parameters read and written once by a server
    step fused with the fold (the mean never needs to leave the chip)."""
    b = np.dtype(update_dtype).itemsize
    return int(k * b * n + 2 * ACC_BYTES * n)


def round_min_flops(n: int, k: int) -> int:
    """Weighted sum (2 per update element), one scale and one add for
    the server step."""
    return int(2 * k * n + 2 * n)


def window_min_seconds(n: int, cohorts: Iterable[int], update_dtype,
                       peak) -> Tuple[float, float, float]:
    """Least device time the published rounds of a window need at the
    chip's peaks -> (seconds, bytes, flops)."""
    byts = sum(round_min_bytes(n, k, update_dtype) for k in cohorts)
    flops = sum(round_min_flops(n, k) for k in cohorts)
    return (max(byts / peak.hbm_bytes_per_s, flops / peak.flops_per_s),
            float(byts), float(flops))
