"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` string JAX reports.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s per chip.  A kind that is not in the table is an
error, never a default: a roofline share against the wrong peak is a
wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peak:
    flops_per_s: float      # bf16 matrix-unit peak
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
