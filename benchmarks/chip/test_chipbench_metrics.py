"""CPU tests of the per-layer readers on a hand-made window: each reads
what its file says, and returns nothing where it has nothing to read."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import cost, devtrace, spec  # noqa: E402
from chipbench.harness import Ctx  # noqa: E402
from chipbench.traffic import Sent, Submission  # noqa: E402
from repro.obs.trace import RoundTrace, Span  # noqa: E402

N = 1000


def _round(t_open, k, fold_mid, fold_top, spawn, release, t_close):
    wall = t_close - 0.010 - t_open
    spans = [Span(kind="round", t0=t_open, dur_s=wall),
             Span(kind="spawn", dur_s=spawn),
             Span(kind="dispatch", dur_s=wall - spawn - release - 0.05),
             Span(kind="fold", dur_s=0.05),
             Span(kind="release", dur_s=release),
             Span(kind="fold.mid", dur_s=fold_mid),
             Span(kind="fold.top", dur_s=fold_top)]
    return {"t_open": t_open, "t_close": t_close, "cohort": [],
            "outcome": SimpleNamespace(accepted=k, skipped=[]),
            "trace": RoundTrace(round_id=0, wall_s=wall, spans=spans)}


def _ctx(trace=None):
    rounds = [_round(10.0, 16, 0.16, 0.02, 0.01, 0.01, 11.0),
              _round(10.5, 8, 0.08, 0.02, 0.01, 0.01, 12.0)]
    sent = {}
    for i in range(100):
        t = 10.0 + i * 0.02
        sent[f"u{i}"] = Sent(Submission(i, 0, 1.0, 0.0), t, t,
                             1e-4 * (i + 1), 0, 0.0)
    sent["early"] = Sent(Submission(100, 0, 1.0, 0.0), 1.0, 1.0, 9.0, 0, 0.0)
    return Ctx(n=N, update_dtype="float32", rounds=rounds,
               window_s=2.0, t0=10.0, t_last=12.0, sent=sent,
               updates_per_s=12.0, device_kind="TPU v5 lite", trace=trace)


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_host_side_readers():
    ctx = _ctx()
    # exact p95 of 0.1 ms .. 10 ms in steps of 0.1 ms; the early
    # submission is outside the window
    assert _read("admit_p95_ms", ctx) == pytest.approx(9.505)
    assert _read("fold_exec_ms", ctx) == pytest.approx(
        1e3 * (0.16 + 0.02 + 0.08 + 0.02) / 24)
    # closes are 10 ms after each round span's end
    assert _read("publish_ms", ctx) == pytest.approx(10.0)
    assert _read("publish_ms.steady", ctx) == _read("publish_ms", ctx)
    b = [r["trace"].breakdown() for r in ctx.rounds]
    assert _read("control_share", ctx) == pytest.approx(
        100 * sum(x["control_s"] for x in b) / sum(x["wall_s"] for x in b))
    least = (cost.round_min_bytes(N, 16, "float32")
             + cost.round_min_bytes(N, 8, "float32")) / 819e9
    assert _read("step_mfu", ctx) == pytest.approx(100 * least / 2.0)


def test_device_readers_need_a_trace():
    ctx = _ctx()
    assert _read("device_idle_share", ctx) is None
    assert _read("eager_accumulate_roofline", ctx) is None
    red = devtrace.Reduced(window_s=2.0, busy_s=0.5,
                           op_s={"eager_accumulate": 0.001},
                           op_calls={"eager_accumulate": 4},
                           idle_gaps=[])
    ctx = _ctx(red)
    assert _read("device_idle_share", ctx) == pytest.approx(75.0)
    want = 100 * 4 * cost.accumulate_bytes(N, 1, "float32") / 819e9 / 0.001
    assert _read("eager_accumulate_roofline", ctx) == pytest.approx(want)


def test_readers_return_nothing_for_an_empty_window():
    ctx = _ctx()
    ctx.rounds, ctx.sent = [], {}
    for name in ("admit_p95_ms", "fold_exec_ms", "publish_ms",
                 "control_share", "step_mfu"):
        assert _read(name, ctx) is None, name
