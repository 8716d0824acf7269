"""CPU tests of the chip benchmark's yardstick: traffic, quantiles,
byte counts and the peak table."""
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import cost, peaks, spec, stats, traffic  # noqa: E402


def _take(t, p, seed, n):
    return list(itertools.islice(traffic.submissions(t, p, seed), n))


def _mix(name):
    """A mix's file and a cell's parameters, read by name."""
    cell = {"backlog": "r18-backlog", "steady": "r18-steady"}[name]
    return (json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json")
                       .read_text("utf-8")),
            json.loads((spec.BENCH_DIR / "cells" / f"{cell}.json")
                       .read_text("utf-8")))


@pytest.mark.parametrize("mix", ["backlog", "steady"])
def test_traffic_is_deterministic_per_seed(mix):
    t, p = _mix(mix)
    seed = 2**33 + 17
    a = _take(t, p, seed, 600)
    b = _take(t, p, seed, 600)
    c = _take(t, p, seed + 1, 600)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix", ["backlog", "steady"])
def test_traffic_offers_the_same_work_to_every_seed(mix):
    t, p = _mix(mix)
    lo, hi = p["weights"]
    n = hi - lo
    a = _take(t, p, 1, n)
    b = _take(t, p, 99, n)
    m = p["pool_updates"]
    for x in (a, b):
        # each pass over the pool and the weight range holds every value
        assert sorted(s.pool for s in x[:m]) == list(range(m))
        assert sorted(s.weight for s in x) == list(range(lo, hi))
    assert [s.pool for s in a] != [s.pool for s in b]
    if mix == "steady":
        k = t["block"]
        assert sorted(s.gap_s for s in a[:k]) == sorted(s.gap_s for s in b[:k])
        assert [s.gap_s for s in a[:k]] != [s.gap_s for s in b[:k]]


def test_poisson_gaps_hold_the_cell_rate_over_every_block():
    t, p = _mix("steady")
    k = t["block"]
    subs = _take(t, p, 5, 4 * k)
    for i in range(0, 4 * k, k):
        span = sum(s.gap_s for s in subs[i:i + k])
        assert span == pytest.approx(k / float(p["rate_per_s"]), rel=1e-12)
    assert len({s.gap_s for s in subs[:k]}) == k


@pytest.mark.parametrize("n", [1, 2, 7, 20, 101])
def test_quantile_is_exact(n):
    xs = list(np.random.default_rng(n).random(n))
    for q in (0.0, 0.5, 0.95, 1.0):
        assert stats.quantile(xs, q) == pytest.approx(
            float(np.quantile(xs, q)), rel=0, abs=1e-15)
    assert stats.quantile([3.0, 1.0, 2.0], 0.5) == 2.0


@pytest.mark.parametrize("dtype,b", [("float32", 4), ("bfloat16", 2)])
@pytest.mark.parametrize("k", [1, 8, 16])
def test_accumulate_bytes_from_shapes(dtype, b, k):
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    n = 11_199_486
    assert cost.accumulate_bytes(n, k, dtype) == k * b * n + 8 * n + 4 * k
    assert cost.accumulate_flops(n, k) == 2 * k * n
    assert cost.round_min_bytes(n, k, dtype) == (k * b + 8) * n


def test_window_least_time_is_bytes_bound_on_v5e():
    peak = peaks.peak_for("TPU v5 lite")
    secs, byts, flops = cost.window_min_seconds(1000, [16, 8], "float32",
                                                peak)
    assert byts == (16 * 4 + 8) * 1000 + (8 * 4 + 8) * 1000
    assert secs == byts / 819e9 > flops / 197e12


def test_peak_table_raises_for_unknown_device_kind():
    assert peaks.peak_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v99 imaginary")
