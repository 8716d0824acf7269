"""The trace reduction, on one second of a trace recorded on a TPU v5e
during an ``r18-backlog`` window (trimmed: the device's ``XLA Ops`` line
and the driving thread's host spans)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import cost, devtrace, peaks  # noqa: E402

TRACE = Path(__file__).resolve().parent / "testdata" / "trace-r18"
N = 11_199_486


@pytest.fixture(scope="module")
def data():
    return devtrace.load(devtrace.find_xplane(str(TRACE)))


@pytest.fixture(scope="module")
def red(data):
    return devtrace.reduce(data)


def test_window_and_busy_time(data, red):
    assert red.window_s == pytest.approx(1.0)
    assert 0 < red.busy_s < red.window_s
    # the union never exceeds the summed op time, and covers the kernel
    assert red.busy_s <= sum(red.op_s.values()) + 1e-12
    assert red.busy_s >= max(red.op_s.values())


def test_kernel_time_and_roofline(red):
    calls, secs = red.kernel("eager_accumulate")
    assert calls > 0 and secs > 0
    least = calls * cost.accumulate_bytes(N, 1, "float32") \
        / peaks.peak_for("TPU v5 lite").hbm_bytes_per_s
    assert 0 < least / secs <= 1.0
    assert red.kernel("no_such_kernel") == (0, 0.0)


def test_idle_gaps_are_named_and_add_up(red):
    idle = red.window_s - red.busy_s
    assert sum(s for _n, s in red.idle_gaps) == pytest.approx(idle, rel=1e-9)
    names = [n for n, _s in red.idle_gaps]
    assert all(n for n in names) and len(set(names)) == len(names)
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "eager_accumulate"


def test_op_names_drop_instance_and_text():
    assert devtrace.op_name(
        "%eager_accumulate.1 = f32[11199486]{0:T(1024)} custom-call(...)"
    ) == "eager_accumulate"
    assert devtrace.op_name("%broadcast_multiply_fusion = f32[64] fusion()"
                            ) == "broadcast_multiply_fusion"


def test_union_merges_overlaps():
    assert devtrace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4),
                                                                (5, 6)]


def test_a_directory_without_a_trace_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        devtrace.find_xplane(str(tmp_path))
