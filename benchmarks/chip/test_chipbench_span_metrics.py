"""CPU tests of the readers of the per-layer span metrics (store put,
upload, read-back, server step, gateway queue) on hand-made windows:
each reads what its file says, and returns nothing where the program
records no such span."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spec, stats  # noqa: E402
from chipbench.harness import Ctx  # noqa: E402
from repro.obs.trace import RoundTrace, Span  # noqa: E402

NAMES = ("store_put_ms", "h2d_gbps", "readback_ms", "server_step_ms",
         "queue_wait_p95_s")


def _round(k, puts, uploads, readbacks, step, waits):
    spans = [Span(kind="round", dur_s=1.0)]
    spans += [Span(kind="store.put", dur_s=d, n=4e6) for d in puts]
    spans += [Span(kind="fold.upload", dur_s=d, n=n) for d, n in uploads]
    spans += [Span(kind="readback", dur_s=d, n=4e6) for d in readbacks]
    spans += [Span(kind="server_step", dur_s=d, n=62.0) for d in step]
    spans += [Span(kind="queue.wait", dur_s=d, n=1.0) for d in waits]
    return {"t_open": 0.0, "t_close": 1.0, "cohort": [],
            "outcome": SimpleNamespace(accepted=k, skipped=[]),
            "trace": RoundTrace(round_id=0, wall_s=1.0, spans=spans)}


def _ctx(rounds):
    return Ctx(n=1000, update_dtype="float32", rounds=rounds, window_s=2.0,
               t0=10.0, t_last=12.0, sent={}, updates_per_s=12.0,
               device_kind="TPU v5 lite")


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def _window():
    waits_a = [0.1 * i for i in range(1, 9)]
    waits_b = [2.0 + 0.1 * i for i in range(4)]
    return _ctx([
        _round(8, [0.01] * 10, [(0.004, 4e6)] * 10, [0.02, 0.02, 0.03],
               [0.05], waits_a),
        _round(4, [0.02] * 5, [(0.002, 1e6)] * 5, [0.01], [0.07],
               waits_b),
    ]), waits_a + waits_b


def test_span_readers_on_a_window():
    ctx, waits = _window()
    # ten puts of 10 ms and five of 20 ms over the twelve folded updates
    assert _read("store_put_ms", ctx) == pytest.approx(1e3 * 0.2 / 12)
    # 45 MB in 50 ms
    assert _read("h2d_gbps", ctx) == pytest.approx(45e6 / 0.05 / 1e9)
    # per round 70 ms and 10 ms
    assert _read("readback_ms", ctx) == pytest.approx(40.0)
    assert _read("server_step_ms", ctx) == pytest.approx(60.0)
    # exact p95 over all twelve waits of both rounds
    assert _read("queue_wait_p95_s", ctx) == pytest.approx(
        stats.quantile(waits, 0.95))
    assert _read("queue_wait_p95_s", ctx) == pytest.approx(2.245)


def test_span_readers_skip_rounds_without_a_trace():
    ctx, _ = _window()
    ctx.rounds[1]["trace"] = None
    assert _read("readback_ms", ctx) == pytest.approx(70.0)
    assert _read("server_step_ms", ctx) == pytest.approx(50.0)
    assert _read("h2d_gbps", ctx) == pytest.approx(1.0)


def test_span_readers_return_nothing_where_the_program_has_no_such_span():
    # a program without the spans (only the phase spans): no value, no
    # exception
    bare = _round(8, [], [], [], [], [])
    for ctx in (_ctx([bare, bare]), _ctx([])):
        for name in NAMES:
            assert _read(name, ctx) is None, name


def test_span_metrics_are_in_the_backlog_cell():
    cell = spec.load_cell("r18-backlog")
    names = [m.name for m in cell.per_layer]
    assert names[-len(NAMES):] == list(NAMES)
    units = {m.name: m.unit for m in cell.per_layer}
    assert units["h2d_gbps"] == "GB/s" and units["queue_wait_p95_s"] == "s"
