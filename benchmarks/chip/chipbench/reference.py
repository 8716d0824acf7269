"""The plain reference of what the service publishes.

FedAvg with the ``fedavg`` server step at ``server_lr = 1`` publishes,
after each round ``r`` with cohort ``S_r``,

    P_r = P_{r-1} + sum_{u in S_r} w_u x_u / sum_{u in S_r} w_u

so ``P_r = P_0 + sum_j C_r[j] x_j`` over the rows ``x_j`` of the update
pool, where ``C_r[j]`` adds up every round's share of row ``j``.  The
reference evaluates that in float64 from the benchmark's own initial
parameters, pool and submitted weights, over the cohorts the service
logged; it imports nothing of the program.

The control is the same reference with every update row rounded to
bfloat16 first: the precision step a later change could be tempted by
(shipping f32 updates as bf16).  It must read as not correct.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

BLOCK = 1 << 18


def pool_rows(base: np.ndarray, n: int, stride: int, count: int
              ) -> List[np.ndarray]:
    """Row ``j`` of the pool is ``base[j*stride : j*stride + n]``."""
    return [base[j * stride: j * stride + n] for j in range(count)]


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round f32 values to the nearest bfloat16 (ties to even), as f32."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


class Chain:
    """Running pool coefficients of the published parameters."""

    def __init__(self, pool: int, server_lr: float = 1.0):
        self.coef = np.zeros(pool, np.float64)
        self.server_lr = float(server_lr)

    def publish(self, cohort: Sequence[Tuple[int, float]]) -> np.ndarray:
        """Fold one published round's (pool row, weight) pairs; returns
        the coefficients of the parameters it published."""
        if cohort:
            total = float(sum(w for _r, w in cohort))
            for row, w in cohort:
                self.coef[row] += self.server_lr * w / total
        return self.coef.copy()


def gaps(p0: np.ndarray, rows: Sequence[np.ndarray],
         published: Dict[int, np.ndarray], coefs: Dict[int, np.ndarray],
         control: bool = False) -> Dict[int, Dict[str, float]]:
    """For each checked round: the widest gap between the program's
    published parameters and the reference, as a share of the largest
    reference parameter (``program``), and, with ``control``, the same
    for the bfloat16 control (``control``)."""
    keys = sorted(published)
    n = p0.size
    c = np.stack([coefs[k] for k in keys])                  # (R, M)
    num = np.zeros(len(keys))
    num_ctl = np.zeros(len(keys))
    den = np.zeros(len(keys))
    for off in range(0, n, BLOCK):
        end = min(off + BLOCK, n)
        x = np.stack([r[off:end] for r in rows])             # (M, B) f32
        ref = p0[off:end].astype(np.float64) + c @ x.astype(np.float64)
        den = np.maximum(den, np.abs(ref).max(axis=1))
        for i, k in enumerate(keys):
            got = published[k][off:end].astype(np.float64)
            num[i] = max(num[i], float(np.abs(got - ref[i]).max()))
        if control:
            ctl = (p0[off:end].astype(np.float64)
                   + c @ bf16_round(x).astype(np.float64))
            num_ctl = np.maximum(num_ctl, np.abs(ctl - ref).max(axis=1))
    out = {}
    for i, k in enumerate(keys):
        out[k] = {"program": num[i] / den[i]}
        if control:
            out[k]["control"] = num_ctl[i] / den[i]
    return out
