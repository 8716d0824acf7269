"""Live fleet telemetry (PR 9): histograms, SLO tracking, the monitor.

Five seams this file holds:

  * **Histogram algebra** (property-based) — merge is associative on
    everything observable (buckets, zero count, quantiles; the float
    ``sum`` up to round-off), drain-then-absorb is indistinguishable
    from never draining, and every quantile answer is within the
    advertised relative error of the true order statistic;
  * **metric-map integration** — streaming histograms live in the
    ``MetricsMap`` next to the (sum, count) series, with the same
    non-destructive-snapshot / destructive-drain / prefixed-absorb
    contract ``drain_series`` has;
  * **pressure pricing** — the gateway's ``retry_after_s`` rises with
    the *measured* ingest p99, not just queue depth;
  * **the agent loop** — the FleetMonitor scrapes land mid-round
    (between SPAWN and FOLD), a sustained straggler fires one typed
    ``SLOBreached`` per episode, and a SIGKILLed daemon shows
    ``stale=True`` on the next scrape while the driver's round-edge
    view still believes the node is alive;
  * **surface parity** — ``Session.status()`` mirrors
    ``AggregationService.health()`` key-for-key, and the new gauges
    ride ``Session.metrics()``.
"""
import math
import os
import signal
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.api import Session  # noqa: E402
from repro.core import ClientInfo, MetricsMap, NodeState, RoundConfig  # noqa: E402
from repro.obs import summary_line, to_prometheus  # noqa: E402
from repro.obs.live import FleetMonitor, Histogram, SLOTarget, SLOTracker  # noqa: E402
from repro.runtime.events import SLOBreached  # noqa: E402
from repro.runtime.netrt import (  # noqa: E402
    RemoteRuntime, reap_local_daemon, spawn_local_daemon,
)
from repro.serve import (  # noqa: E402
    AdmissionPolicy, AggregationService, IngressGateway, MinCohortIdleGap,
)

settings.register_profile("ci", max_examples=40, deadline=None)
settings.load_profile("ci")

REL = 0.05
vals = st.lists(st.floats(1e-6, 1e4, allow_nan=False),
                min_size=1, max_size=120)


def _fill(values):
    h = Histogram(rel_err=REL)
    for v in values:
        h.observe(v)
    return h


def _same(a: Histogram, b: Histogram) -> None:
    """Observational equality: everything but float-sum round-off."""
    wa, wb = a.to_wire(), b.to_wire()
    sa, sb = wa.pop("sum"), wb.pop("sum")
    assert wa == wb
    assert math.isclose(sa, sb, rel_tol=1e-9, abs_tol=1e-12)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert a.quantile(q) == b.quantile(q)


# ---------------------------------------------------------------------------
# histogram algebra (property-based)
# ---------------------------------------------------------------------------

@given(vals, vals, vals)
def test_hist_merge_associative_and_commutative(xs, ys, zs):
    left = _fill(xs).merge(_fill(ys)).merge(_fill(zs))
    right = _fill(xs).merge(_fill(ys).merge(_fill(zs)))
    _same(left, right)
    swapped = _fill(zs).merge(_fill(ys)).merge(_fill(xs))
    _same(left, swapped)
    assert left.count == len(xs) + len(ys) + len(zs)


@given(vals, vals)
def test_hist_drain_then_absorb_equals_never_drained(xs, ys):
    """The agent's destructive retrieval loses nothing: draining after
    the first batch and absorbing the snapshot back gives the same
    histogram as observing both batches straight through."""
    drained = _fill(xs)
    snap = drained.drain()
    assert drained.count == 0 and drained.sum == 0.0
    for v in ys:
        drained.observe(v)
    drained.merge(snap)
    _same(drained, _fill(xs + ys))


@given(vals)
def test_hist_quantile_relative_error_bound(values):
    """quantile(q) is within rel_err of the true order statistic for
    any stream inside the tracked range (the DDSketch guarantee)."""
    h = _fill(values)
    ordered = sorted(values)
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        truth = ordered[math.floor(q * (len(ordered) - 1))]
        est = h.quantile(q)
        assert abs(est - truth) <= REL * truth + 1e-12, (q, truth, est)


@given(vals)
def test_hist_wire_roundtrip_exact(values):
    h = _fill(values)
    back = Histogram.from_wire(h.to_wire())
    assert back.to_wire() == h.to_wire()
    import json
    assert json.loads(json.dumps(h.to_wire())) == h.to_wire()


def test_hist_zero_bucket_and_edges():
    h = Histogram(rel_err=REL, min_value=1e-8)
    for v in (0.0, -3.0, 1e-9, float("nan")):
        h.observe(v)
    assert h.zero == 4 and h.count == 4
    assert h.quantile(0.5) == 0.0
    assert h.quantile(0.5, default=7.0) == 0.0   # non-empty: no default
    assert Histogram().quantile(0.5, default=7.0) == 7.0
    # out-of-range values clamp into edge buckets, never KeyError
    h.observe(1e12)
    assert h.count == 5 and h.quantile(1.0) > 0.0


def test_hist_merge_shape_mismatch_raises():
    with pytest.raises(ValueError):
        Histogram(rel_err=0.05).merge(Histogram(rel_err=0.01))
    with pytest.raises(ValueError):
        Histogram(n_buckets=288).merge(Histogram(n_buckets=64))


# ---------------------------------------------------------------------------
# metric-map integration
# ---------------------------------------------------------------------------

def test_metricsmap_hist_snapshot_drain_absorb():
    m = MetricsMap()
    for v in (0.010, 0.020, 0.040):
        m.observe("tta", "jobA", v)
    assert m.quantile("tta", "jobA", 0.5) == pytest.approx(0.020, rel=0.06)
    assert m.quantile("tta", "nope", 0.5, default=-1.0) == -1.0
    # snapshot is non-destructive; hist() returns an isolated copy
    snap1 = m.hists_snapshot()
    snap2 = m.hists_snapshot()
    assert snap1 == snap2 and "tta/jobA" in snap1
    m.hist("tta", "jobA").observe(9.9)          # mutating the copy...
    assert m.hists_snapshot() == snap1          # ...changes nothing
    # drain is destructive; absorb with a node prefix rebuilds it
    drained = m.drain_hists()
    assert m.hists_snapshot() == {}
    m2 = MetricsMap()
    m2.absorb_hists(drained, prefix="n0.")
    assert m2.quantile("n0.tta", "jobA", 0.5) == pytest.approx(
        0.020, rel=0.06)
    # absorbing into an existing histogram merges, not replaces
    m2.absorb_hists(drained, prefix="n0.")
    assert m2.hist("n0.tta", "jobA").count == 6


# ---------------------------------------------------------------------------
# pressure pricing
# ---------------------------------------------------------------------------

def test_retry_after_rises_with_measured_ingest_p99():
    pol = AdmissionPolicy(retry_base_s=0.01, retry_cap_s=10.0,
                          ingest_gain=4.0)
    flat = pol.retry_after(5, 10, ingest_p99_s=0.0)
    slow = pol.retry_after(5, 10, ingest_p99_s=0.5)
    slower = pol.retry_after(5, 10, ingest_p99_s=1.0)
    assert flat < slow < slower                  # measured p99 lifts it
    assert pol.retry_after(50, 10, 0.5) > slow   # so does depth pressure
    assert pol.retry_after(10**6, 10, 10.0) == 10.0   # capped
    # same thing end-to-end through the gateway's measured histogram
    gw = IngressGateway(pol)
    gw.register("j", lambda *a, **k: True, lambda: 5)
    before = gw.retry_after_now()
    for _ in range(50):
        gw.ingest_hist.observe(0.5)
    assert gw.retry_after_now() > before


def test_slo_tracker_hysteresis_one_event_per_episode():
    fired = []
    slo = SLOTracker(breach_after=3, emit=fired.append)
    slo.set_target("j", SLOTarget(p99_tta_s=0.1))
    bad = dict(p99_tta_s=0.5, shed_frac=0.0)
    assert slo.observe("j", **bad) is None       # 1st violation
    assert slo.observe("j", **bad) is None       # 2nd
    ev = slo.observe("j", **bad)                 # 3rd: sustained
    assert isinstance(ev, SLOBreached)
    assert ev.metric == "p99_tta_s" and ev.measured == 0.5
    assert slo.observe("j", **bad) is None       # latched: no re-fire
    assert slo.status("j")["breached"] is True
    slo.observe("j", p99_tta_s=0.01, shed_frac=0.0)   # clean: re-arm
    assert slo.status("j")["breached"] is False
    for _ in range(3):
        slo.observe("j", **bad)
    assert len(fired) == 2                       # one per episode
    # the shed axis breaches independently, with its own metric name
    slo.set_target("k", {"max_shed_frac": 0.2})
    for _ in range(3):
        ev = slo.observe("k", p99_tta_s=0.0, shed_frac=0.9)
    assert ev.metric == "shed_frac" and ev.target == 0.2


# ---------------------------------------------------------------------------
# the agent loop (inproc service)
# ---------------------------------------------------------------------------

class _Model:
    def loss(self, params, batch):
        return jnp.sum(params["w"] ** 2), {}


N = 64


def _service(**kw):
    svc = AggregationService(
        admission=AdmissionPolicy(max_queue=64, job_quota=32), **kw)
    svc.add_job("j", _Model(), {"w": jnp.zeros((N,), jnp.float32)},
                [ClientInfo(client_id=f"c{i}", num_samples=10)
                 for i in range(8)],
                round_cfg=RoundConfig(aggregation_goal=4),
                # paced pushers are the injected stragglers: real TTA
                # runs tens of ms against a 1 ms promise
                slo=SLOTarget(p99_tta_s=0.001))
    return svc


def test_monitor_scrapes_mid_round_and_slo_breaches():
    svc = _service()
    breaches = []
    svc.driver.on(SLOBreached, breaches.append)
    mon = svc.start_monitor(period_s=0.01)
    assert svc.start_monitor() is mon            # idempotent
    stop = threading.Event()

    def pusher():
        k = 0
        while not stop.is_set():
            v = svc.submit("j", f"u{k}", np.full(N, 1.0, np.float32),
                           1.0, submission_id=f"u{k}")
            if v["admitted"]:
                k += 1
            time.sleep(0.02)                     # the straggler trickle

    th = threading.Thread(target=pusher, daemon=True)
    th.start()
    try:
        svc.run_rounds({"j": 8}, policy=MinCohortIdleGap(
            min_cohort=4, idle_gap_s=5.0))
    finally:
        stop.set()
        th.join(timeout=5)
    mc = mon.counters()
    # ≥1 scrape landed between SPAWN and FOLD of an open round — the
    # live-drain point the round-edge path can never see
    assert mc["mid_round_scrapes"] >= 1
    mid = [r for r in mon.log if r["mid_round"]]
    assert mid and any(p in ("spawn", "dispatch", "collect", "fold")
                       for r in mid for p in r["phases"])
    # the sustained straggler fired the typed event on the driver bus
    assert breaches and breaches[0].job == "j"
    assert breaches[0].metric == "p99_tta_s"
    assert breaches[0].measured > breaches[0].target
    assert svc.slo.status("j")["breached"] is True
    snap = svc.health()
    assert snap["jobs"]["j"]["tta"]["count"] >= 8
    assert snap["monitor"]["scrapes"] == mc["scrapes"]
    svc.close()
    assert svc.monitor is None                   # close stops the agent


def test_health_export_renders():
    svc = _service()
    svc.submit("j", "u0", np.zeros(N, np.float32), 1.0)
    snap = svc.health()
    prom = to_prometheus(snap)
    assert "lifl_open_rounds" in prom
    assert 'lifl_job_queue_depth{job="j"} 1' in prom
    assert 'lifl_job_tta_seconds{job="j",quantile="p99"}' in prom
    assert "lifl_gateway_retry_after_seconds" in prom
    line = summary_line(snap)
    assert "rounds" in line and "gateway" in line
    svc.close()


def test_session_status_health_key_parity():
    svc = _service()
    svc_keys = set(svc.health())
    svc.close()
    with Session.open(_Model(), {"w": jnp.zeros((N,), jnp.float32)}, [],
                      admission=True) as s:
        assert set(s.status()) == svc_keys
        m = s.metrics()
        for gauge in ("open_rounds", "gateway_queue_depth",
                      "fleet_nodes_alive"):
            assert gauge in m and m[gauge] >= 0
        assert s.status()["fleet_nodes_alive"] == m["fleet_nodes_alive"]


# ---------------------------------------------------------------------------
# the agent loop (real daemons)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_stats_frame_and_sigkill_shows_stale_before_round_edge():
    daemons = [spawn_local_daemon(f"mn{i}", runtime="inproc")
               for i in range(2)]
    procs = [p for p, _ in daemons]
    svc = _service(nodes={f"mn{i}": NodeState(node=f"mn{i}",
                                              max_capacity=20.0)
                          for i in range(2)},
                   runtime=RemoteRuntime([a for _, a in daemons]))
    rt = svc.runtime
    mon = FleetMonitor(svc, period_s=0.05)       # driven by hand
    try:
        mon.scrape_once()
        view = mon.fleet_view()
        assert set(view) == {"mn0", "mn1"}
        for f in view.values():
            assert f["stale"] is False and f["rtt_s"] > 0.0
            h = f["health"]
            for k in ("open_conns", "shm_bytes", "workers",
                      "workers_busy", "workers_parked", "ring_depth"):
                assert k in h, k
        # poll_stats: same frame through the controller's own conns,
        # non-destructive — no series count may shrink between polls
        # (the daemon's own tx counters legitimately grow per reply)
        s1 = rt.poll_stats()
        s2 = rt.poll_stats()
        assert set(s1) == {"mn0", "mn1"}
        for name in s1:
            for key, (_total, n) in s1[name]["series"].items():
                assert s2[name]["series"][key][1] >= n, key
            assert s1[name]["uptime_s"] <= s2[name]["uptime_s"]

        os.kill(procs[1].pid, signal.SIGKILL)
        time.sleep(0.3)
        mon.scrape_once()
        # the heartbeat sees the death NOW; the driver's round-edge
        # view hasn't run a round, so it still believes mn1 is alive
        assert mon.fleet_view()["mn1"]["stale"] is True
        assert mon.fleet_view()["mn0"]["stale"] is False
        assert rt._nodes["mn1"].alive is True
        assert mon.counters()["stale_events"] == 1
        mon.scrape_once()                        # still stale: no re-count
        assert mon.counters()["stale_events"] == 1
    finally:
        mon.stop()
        svc.close()
        for p in procs:
            reap_local_daemon(p)


@pytest.mark.slow
def test_spawn_daemon_log_file_lifecycle():
    proc, _addr = spawn_local_daemon("logx", runtime="inproc")
    path = proc.lifl_log_path
    assert path and os.path.exists(path)
    reap_local_daemon(proc)
    assert not os.path.exists(path)              # clean reap unlinks
    # a caller-supplied stdout opts out of the log file entirely
    import subprocess
    proc2, _addr2 = spawn_local_daemon("logy", runtime="inproc",
                                       stdout=subprocess.DEVNULL)
    assert proc2.lifl_log_path == ""
    reap_local_daemon(proc2)


# ---------------------------------------------------------------------------
# the minutes-long soak (excluded from tier-1; ``-m soak`` opts in)
# ---------------------------------------------------------------------------

@pytest.mark.soak
def test_soak_gates():
    from benchmarks.bench_soak import run as soak_run

    rows = {r["case"]: r["derived"] for r in soak_run(fast=True)}
    fleet = rows["fleet"]
    assert "soak_bitexact=1" in fleet
    frac = float(fleet.split("scrape_overhead_frac=")[1].split(";")[0])
    assert frac < 0.02
    mid = int(fleet.split("mid_round_scrapes=")[1].split(";")[0])
    assert mid >= 1
    for job in ("alpha", "beta"):
        assert f"slo_{job}" in rows
        assert "p99_tta_ms=" in rows[f"slo_{job}"]
