"""The obs layer (paper §4.3, the LIFL agent): event-edge spans, the
per-round TTA breakdown, daemon telemetry drained over the wire, the
tolerant JSONL trace log, and the telemetry→capacity-model feedback.

Contracts covered:
  * every SPAN_KINDS entry survives the wire codec (same seam contract
    as events.EVENT_TYPES);
  * a disabled Tracer is inert (begin → -1, end(-1)/point no-ops);
  * ``breakdown()`` attributes ≥ 95% of round wall on the inproc,
    shmproc, and 2-node paths — the acceptance floor;
  * the JSONL trace file survives a FaultPlan daemon kill mid-round
    and ``read_traces`` skips the truncated/corrupt lines a kill
    leaves behind;
  * ``TopFolded.exec_s`` / ``PartialShipped.wire_s`` feed the RC
    capacity model and actually move the root-fold placement.
"""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core.sidecar import MetricsMap, series_flatten
from repro.obs.trace import (
    NULL_TRACER,
    SPAN_KINDS,
    RoundTrace,
    Span,
    Tracer,
    read_traces,
    span_from_wire,
    span_to_wire,
    write_trace,
)
from repro.runtime.driver import InProcRuntime, RoundDriver

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

def test_every_span_kind_roundtrips_on_the_wire():
    for i, kind in enumerate(SPAN_KINDS):
        s = Span(kind=kind, owner=f"mid@n{i}", node=f"n{i}", round_id=i,
                 t0=1.5 + i, dur_s=0.25 * (i + 1), id=i, parent=i - 1,
                 worker=i % 3 - 1, n=float(i * 10))
        assert span_from_wire(span_to_wire(s)) == s
        # str form decodes too (JSONL readers hand lines around as str)
        assert span_from_wire(span_to_wire(s).decode()) == s
    # every kind a real round emits is registered: a deep (fanout-3)
    # plan adds fold.inner, the in-process runtime the store, read-back
    # and publish spans
    from repro.core.placement import build_fold_plan

    asg = {f"n{i:02d}": [2 * i, 2 * i + 1] for i in range(9)}
    ups = [(f"n{i // 2:02d}", f"c{i}", np.full(8, float(i), np.float32), 1.0)
           for i in range(18)]
    rt = InProcRuntime()
    drv = RoundDriver(rt)
    drv.run_round(round_id=0, assignment=asg, updates=ups, goal=18,
                  n_elems=8, fold_plan=build_fold_plan(
                      asg, topology="controller", fanout=3))
    rt.close()
    emitted = drv.last_trace.spans
    assert {"fold.inner", "store.put", "readback"} <= {s.kind for s in emitted}
    for s in emitted:
        assert span_from_wire(span_to_wire(s)) == s


def test_span_wire_rejects_unknown_kinds():
    with pytest.raises(TypeError, match="not a wire-registered"):
        span_to_wire(Span(kind="made-up"))
    with pytest.raises(ValueError, match="unknown span kind"):
        span_from_wire(b'{"span":"made-up","owner":""}')


# ---------------------------------------------------------------------------
# Tracer semantics
# ---------------------------------------------------------------------------

def test_tracer_begin_end_point_drain():
    tr = Tracer(enabled=True)
    tok = tr.begin("round", owner="driver", round_id=7)
    span = tr.end(tok, n=6.0)
    assert span.kind == "round" and span.round_id == 7 and span.n == 6.0
    assert span.dur_s >= 0.0
    tr.point("fold.top", 0.125, owner="top@n0", worker=2)
    got = tr.drain()
    assert [s.kind for s in got] == ["round", "fold.top"]
    assert tr.drain() == []                 # drain took everything
    # reset drops abandoned begins (exception paths)
    tr.begin("spawn")
    tr.reset()
    assert tr.drain() == [] and not tr._open


def test_disabled_tracer_is_inert():
    tr = Tracer(enabled=False)
    assert tr.begin("round") == -1
    assert tr.end(-1) is None               # callers never branch
    assert tr.point("fold.top", 0.1) is None
    with tr.span("dispatch") as tok:
        assert tok == -1
    tr.add(Span(kind="round"))
    assert tr.drain() == []
    assert NULL_TRACER.enabled is False


def test_end_with_stale_token_is_a_noop():
    tr = Tracer(enabled=True)
    tok = tr.begin("collect")
    assert tr.end(tok) is not None
    assert tr.end(tok) is None              # double-end


# ---------------------------------------------------------------------------
# MetricsMap: the agent's map drain
# ---------------------------------------------------------------------------

def test_metrics_map_drain_series_is_destructive():
    m = MetricsMap()
    m.update("netd", "ship_s", 0.25)
    m.update("netd", "ship_s", 0.75)
    m.update("mid@n0", "agg_exec_s", 0.5)
    series = m.drain_series()
    assert series["netd/ship_s"] == [1.0, 2]
    assert series["mid@n0/agg_exec_s"] == [0.5, 1]
    assert m.drain_series() == {}           # the drain reset the map
    # absorb_series merges a remote drain without inflating counts,
    # namespacing owners the way the controller files each daemon's map
    m.absorb_series(series, prefix="nodeB.")
    assert m.peek("nodeB.netd", "ship_s") == (1.0, 2)
    assert series_flatten(m.snapshot())["nodeB.mid@n0/agg_exec_s"] == [0.5, 1]


# ---------------------------------------------------------------------------
# breakdown coverage: inproc / shmproc / 2-node
# ---------------------------------------------------------------------------

def _drive_one(drv, nodes, ups, ws, n_elems, rid=0, fold_plan=None):
    assignment = {n: [i for i in range(len(ups)) if i % len(nodes) == j]
                  for j, n in enumerate(nodes)}
    updates = ((nodes[i % len(nodes)], f"c{i}", u, w)
               for i, (u, w) in enumerate(zip(ups, ws)))
    return drv.run_round(round_id=rid, assignment=assignment,
                         updates=updates, goal=len(ups), n_elems=n_elems,
                         fold_plan=fold_plan)


def _mk_updates(n_updates, n_elems, seed=0):
    rng = np.random.default_rng(seed)
    return ([rng.normal(size=n_elems).astype(np.float32)
             for _ in range(n_updates)],
            [float(1 + i % 3) for i in range(n_updates)])


def _assert_accounts(trace, floor=0.95):
    b = trace.breakdown()
    assert b["coverage"] >= floor, trace.summary()
    # the tiers are a partition: they sum to the wall by construction
    parts = (b["client_train_s"] + b["wire_s"] + b["mid_fold_s"]
             + b["top_fold_s"] + b["control_s"] + b["unaccounted_s"])
    assert parts == pytest.approx(b["wall_s"], rel=1e-6)
    return b


def test_breakdown_accounts_inproc_round():
    # big enough that the fixed inter-phase bookkeeping (~0.1 ms) stays
    # well under the 5% residual floor even on a loaded machine
    N = 1 << 20
    ups, ws = _mk_updates(6, N)
    rt = InProcRuntime()
    drv = RoundDriver(rt)                   # tracing on by default
    out = _drive_one(drv, ["n0", "n1"], ups, ws, N)
    assert out.count == 6
    trace = drv.last_trace
    assert trace is not None and trace.round_id == 0
    b = _assert_accounts(trace)
    assert b["wall_s"] == pytest.approx(trace.wall_s)
    # phase spans all fired exactly once
    for kind in ("round", "spawn", "dispatch", "collect", "fold"):
        assert len(trace.spans_of(kind)) == 1, kind
    # per-subtree latency points carry the subtree's update count
    subs = trace.spans_of("subtree")
    assert sorted(s.owner for s in subs) == ["mid@n0", "mid@n1"]
    assert sum(s.n for s in subs) == 6
    rt.close()


@pytest.mark.slow
def test_breakdown_accounts_shmproc_round_with_worker_spans():
    if not os.path.isdir("/dev/shm"):
        pytest.skip("POSIX shared memory required")
    from repro.runtime.driver import ShmProcRuntime

    N = 1 << 18
    ups, ws = _mk_updates(8, N)
    rt = ShmProcRuntime()
    drv = RoundDriver(rt)
    try:
        _drive_one(drv, ["n0", "n1"], ups, ws, N, rid=0)   # warm the pool
        out = _drive_one(drv, ["n0", "n1"], ups, ws, N, rid=1)
        assert out.count == 8 and out.crashes == 0
        trace = drv.last_trace
        _assert_accounts(trace)
        # worker spans were reconstructed from the shm ring records
        tasks = trace.spans_of("worker.task")
        assert tasks and all(s.worker >= 0 for s in tasks)
        assert all(s.node == rt.name for s in tasks)
        # ring-wait (TELEM) never exceeds its task's wall
        waits = {s.worker: s.dur_s for s in trace.spans_of("worker.wait")}
        for t in tasks:
            if t.worker in waits:
                assert waits[t.worker] <= t.dur_s + 0.01
    finally:
        rt.close()


@pytest.mark.slow
def test_two_node_node_top_round_drains_daemon_telemetry():
    """THE acceptance scenario: a 2-node node-top round accounts ≥ 95%
    of its wall, with each daemon's MetricsMap drained over the wire —
    including the fold-phase samples (partial ship, top-fold serve)
    that land after the quiesce edge."""
    from repro.core.placement import build_fold_plan
    from repro.runtime.netrt import RemoteRuntime, spawn_local_daemon

    N = 1 << 15
    ups, ws = _mk_updates(6, N, seed=3)
    procs, addrs = [], []
    try:
        for name in ("nodeA", "nodeB"):
            p, a = spawn_local_daemon(name, runtime="inproc",
                                      stdout=subprocess.DEVNULL)
            procs.append(p)
            addrs.append(a)
        rt = RemoteRuntime(addrs)
        drv = RoundDriver(rt)
        assignment_nodes = ["nodeA", "nodeB"]
        plan = build_fold_plan(
            {n: [i for i in range(6) if i % 2 == j]
             for j, n in enumerate(assignment_nodes)},
            topology="node")
        out = _drive_one(drv, assignment_nodes, ups, ws, N, rid=0,
                         fold_plan=plan)
        assert out.count == 6 and out.fold_tier == "node"
        trace = drv.last_trace
        _assert_accounts(trace)
        # per-daemon maps came over the wire, keyed by node name
        assert set(trace.telemetry) == {"nodeA", "nodeB"}
        # mid-tier fold exec was measured daemon-side on both nodes
        for node in ("nodeA", "nodeB"):
            s, c = 0.0, 0
            for key, sc in trace.telemetry[node].items():
                if key.endswith("/agg_exec_s"):
                    s += sc[0]
                    c += sc[1]
            assert c > 0 and s >= 0.0, node
        # exactly one sealed partial shipped daemon→daemon, and the
        # ship sample was pulled into THIS round's trace (not the next)
        ship_s, ship_n = trace.telemetry_series("netd/ship_s")
        assert ship_n == 1 and ship_s > 0.0
        _, served = trace.telemetry_series("netd/fetch_serve_s")
        assert served == 1                  # controller fetched the root fold
        # frame-conn sidecar series rode along (wire/tx_* per daemon)
        assert any(k.startswith("wire/tx_")
                   for k in trace.telemetry[out.root_node])
        rt.shutdown_nodes()
        rt.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# ---------------------------------------------------------------------------
# JSONL trace log: tolerant reader, fault survival
# ---------------------------------------------------------------------------

def test_read_traces_skips_truncated_and_corrupt_lines(tmp_path):
    path = str(tmp_path / "traces.jsonl")
    for rid in range(2):
        write_trace(path, RoundTrace(
            round_id=rid, wall_s=0.5,
            spans=[Span(kind="round", owner="driver", round_id=rid,
                        dur_s=0.5)],
            telemetry={"nodeA": {"netd/ship_s": [0.01, 1]}}))
    with open(path, "a") as f:
        f.write('{"round_id": 2, "wall_s": 0.1, "spa')   # killed mid-write
    got = read_traces(path)
    assert [t.round_id for t in got] == [0, 1]
    assert got[1].telemetry["nodeA"]["netd/ship_s"] == [0.01, 1]
    assert got[0].spans[0].kind == "round"
    # corrupt middle lines are skipped, later good lines still load
    with open(path, "a") as f:
        f.write("\nnot json at all\n")
        f.write('{"schema": "drift"}\n')
    write_trace(path, RoundTrace(round_id=3, wall_s=0.2))
    assert [t.round_id for t in read_traces(path)] == [0, 1, 3]
    assert read_traces(str(tmp_path / "never-written.jsonl")) == []


@pytest.mark.slow
def test_trace_jsonl_survives_fault_plan_daemon_kill():
    """A FaultPlan(kill_after=N) daemon SIGKILLs itself mid-round; the
    driver re-dispatches to the survivor and every round's trace still
    lands in the JSONL file, parseable by the tolerant reader."""
    from repro.runtime.netrt import FaultPlan, RemoteRuntime, \
        spawn_local_daemon

    N = 2048
    ups, ws = _mk_updates(6, N, seed=4)
    path = tempfile.mktemp(suffix=".traces.jsonl")
    procs = []
    try:
        pa, aa = spawn_local_daemon("nodeA", runtime="inproc",
                                    stdout=subprocess.DEVNULL)
        procs.append(pa)
        # frame 4 on nodeB is the second deliver: the daemon dies
        # MID-DISPATCH, before publishing its partial, so the driver's
        # redispatch path (not the retriable publish/fetch abort) runs
        pb, ab = spawn_local_daemon("nodeB", runtime="inproc",
                                    stdout=subprocess.DEVNULL,
                                    fault_spec=FaultPlan(kill_after=4))
        procs.append(pb)
        rt = RemoteRuntime([aa, ab])
        drv = RoundDriver(rt, trace_sink=lambda t: write_trace(path, t))
        for rid in range(3):
            nodes = ["nodeA", "nodeB"] if rid == 0 else ["nodeA"]
            out = _drive_one(drv, nodes, ups, ws, N, rid=rid)
            assert out.count == 6           # goal reached despite the kill
        assert rt.stats["node_lost"] == 1   # the fault plan fired
        rt.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    try:
        got = read_traces(path)
        assert [t.round_id for t in got] == [0, 1, 2]
        # the kill round recorded its crash in the trace meta
        assert got[0].meta["crashes"] >= 1
        assert all(t.meta["completed"] for t in got)
    finally:
        os.unlink(path)


# ---------------------------------------------------------------------------
# Session surface: metrics series + trace accessor
# ---------------------------------------------------------------------------

def _mk_session_fixtures():
    jax = pytest.importorskip("jax")
    from repro.configs.resnet import RESNET18
    from repro.core import ClientInfo
    from repro.data import (build_client_datasets, dirichlet_partition,
                            synthetic_femnist)
    from repro.models import build_resnet
    from repro.runtime import ClientRuntime

    model = build_resnet(RESNET18.reduced())
    params = model.init(jax.random.PRNGKey(0))
    imgs, labels = synthetic_femnist(120, num_classes=10, seed=0)
    shards = dirichlet_partition(labels, 8, alpha=0.5)
    clients = [ClientRuntime(ClientInfo(d.client_id, d.num_samples), d)
               for d in build_client_datasets(imgs, labels, shards)]
    return model, params, clients


@pytest.mark.slow
def test_session_metrics_series_and_trace(tmp_path):
    from repro.api import Session
    from repro.core import RoundConfig

    model, params, clients = _mk_session_fixtures()
    trace_path = str(tmp_path / "session.jsonl")
    with Session.open(model, params, clients,
                      round_cfg=RoundConfig(aggregation_goal=4),
                      trace_path=trace_path) as s:
        s.run_round(client_lr=0.05)
        s.run_round(client_lr=0.05)
        m = s.metrics()
        # the legacy flat-sum view and the full series view cover the
        # same keys; sum/count/mean are mutually consistent
        assert set(m["sidecar"]) == set(m["sidecar_series"])
        assert m["sidecar_series"], "sidecar saw no events"
        for key, stats in m["sidecar_series"].items():
            assert m["sidecar"][key] == stats["sum"]
            if stats["count"]:
                assert stats["mean"] == pytest.approx(
                    stats["sum"] / stats["count"])
        exec_series = [v for k, v in m["sidecar_series"].items()
                       if k.endswith("/agg_exec_s")]
        assert exec_series and all(v["count"] >= 1 for v in exec_series)
        # trace accessor: latest round by default, by id explicitly
        t1 = s.trace()
        assert t1.round_id == 1 and s.trace(1) is t1
        assert s.trace(0).round_id == 0
        assert s.trace(99) is None
        _assert_accounts(t1)
        assert "coverage" in t1.breakdown()
    # the JSONL sink got every round, independent of the in-memory cache
    assert [t.round_id for t in read_traces(trace_path)] == [0, 1]


# ---------------------------------------------------------------------------
# telemetry → capacity model feedback (satellite: placement shifts)
# ---------------------------------------------------------------------------

def _coordinator(nodes):
    from repro.core.coordinator import Coordinator, Selector

    return Coordinator(Selector([]), nodes)


def test_topfolded_exec_feeds_root_node_ewma_and_shifts_placement():
    from repro.core.placement import NodeState, choose_top_node
    from repro.runtime.events import TopFolded

    nodes = {"nA": NodeState(node="nA", max_capacity=20.0),
             "nB": NodeState(node="nB", max_capacity=20.0)}
    co = _coordinator(nodes)
    # tie on assignment share → deterministic RC/name tie-break picks nB
    tie = {"nA": [0, 1], "nB": [2, 3]}
    assert choose_top_node(nodes, tie) == "nB"
    # an expensive measured root fold ON nB (node tier) prices load
    # into its EWMA — the next root choice shifts to nA
    for _ in range(4):
        co.handle_event(TopFolded(round_id=0, agg_id="top@nB", node="nB",
                                  tier="node", count=16, weight=16.0,
                                  exec_s=4.0))
    assert nodes["nB"].exec_time_s > 1.0    # EWMA moved off the default
    assert nodes["nB"].residual_capacity < nodes["nA"].residual_capacity
    assert choose_top_node(nodes, tie) == "nA"


def test_controller_tier_topfolded_does_not_price_the_node():
    """A controller-tier fold burns controller CPU — it must not touch
    the EWMA of the node it is nominally named for."""
    from repro.core.placement import NodeState
    from repro.runtime.events import TopFolded

    nodes = {"nA": NodeState(node="nA", max_capacity=20.0)}
    co = _coordinator(nodes)
    co.handle_event(TopFolded(round_id=0, agg_id="top@nA", node="nA",
                              tier="controller", count=16, weight=16.0,
                              exec_s=9.0))
    assert nodes["nA"].exec_time_s == 1.0   # untouched default


def test_partialshipped_wire_ewma_prices_uplink_into_rc():
    from repro.core.placement import NodeState, choose_top_node
    from repro.runtime.events import PartialShipped

    nodes = {"nA": NodeState(node="nA", max_capacity=20.0),
             "nB": NodeState(node="nB", max_capacity=20.0)}
    co = _coordinator(nodes)
    rc0 = nodes["nB"].residual_capacity
    for _ in range(3):
        co.handle_event(PartialShipped(round_id=0, key="p0", src="nB",
                                       dst="nA", nbytes=1 << 20,
                                       wire_s=2.0))
    assert nodes["nB"].wire_time_s > 0.0
    assert nodes["nB"].residual_capacity < rc0
    # the tie-break now avoids the node with the loaded uplink
    assert choose_top_node(nodes, {"nA": [0, 1], "nB": [2, 3]}) == "nA"


# ---------------------------------------------------------------------------
# benchmark harness: gate verdicts ride the JSON rows
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_benchmarks_json_rows_carry_gate_verdicts(tmp_path):
    """`run.py --json` smoke: the output parses and every row carries a
    ``gates`` mapping with pass/fail verdicts (the obs suite's FATAL
    overhead gate among them)."""
    import json

    out_path = str(tmp_path / "bench.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "obs",
         "--json", out_path],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(out_path) as f:
        doc = json.load(f)
    rows = doc["rows"]
    assert rows and all("gates" in row for row in rows)
    obs = [row for row in rows if row["bench"] == "obs"]
    assert obs and obs[0]["gates"].get("obs_overhead") == "pass"
    assert all(v in ("pass", "fail")
               for row in rows for v in row["gates"].values())
