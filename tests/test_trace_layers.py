"""Per-layer spans of an update's path, and their mirror on the
profiler's clock.

Each update crosses five layers the round phases do not separate: the
gateway queue (``queue.wait``), the object store (``store.put``), the
host→device upload (``fold.upload``), the device→host read-back of an
accumulator (``readback``) and, once per round, the trainer's server
step (``server_step``).  Held here:

  * in a rolling two-open service run every new kind lands in its own
    round's ``RoundTrace``, under that round's driver id, with the
    byte counts and per-round counts the code path implies;
  * ``server_step`` — which runs after the driver sealed the trace —
    is in ``trainer.trace(ticket)`` and in every ``trace_path`` line;
  * each ``queue.wait`` is the serve feed's pull stamp minus the stamp
    ``submit_update`` took;
  * under a ``jax.profiler`` session the leaf spans appear as
    ``lifl.<kind>`` events on the thread that ran the rounds, each as
    long as its span to within 1 ms; with no session nothing is
    entered;
  * the tracer loses no span under thread contention, and closes the
    annotation of a span its block or ``reset`` abandons;
  * completed rounds wait for their server step before their line is
    written, at most as many as ``traces`` keeps;
  * a netd daemon driving traced rounds still never imports jax.
"""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.obs.trace import MIRRORED_KINDS, Tracer, read_traces

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import ClientInfo, NodeState, RoundConfig  # noqa: E402
from repro.serve import (  # noqa: E402
    AdmissionPolicy, AggregationService, GoalPolicy,
)

N = 4096                    # f32 update elements: 16 KiB
GOAL = 8
NEW_KINDS = ("store.put", "fold.upload", "readback", "server_step",
             "queue.wait")


class _Model:
    def loss(self, params, batch):
        return jnp.sum(params["w"] ** 2), {}


def _service(goal=GOAL, n=N):
    nodes = {f"node{i}": NodeState(node=f"node{i}", max_capacity=64.0)
             for i in range(2)}
    svc = AggregationService(
        nodes, runtime="inproc", agg_engine="jnp",
        admission=AdmissionPolicy(max_queue=256, job_quota=256))
    tr = svc.add_job(
        "fl", _Model(), {"w": jnp.zeros((n - 8,), jnp.float32),
                         "b": jnp.zeros((8,), jnp.float32)},
        [ClientInfo(f"slot{i}", 1) for i in range(goal)],
        round_cfg=RoundConfig(aggregation_goal=goal, over_provision=1.0,
                              topology="controller",
                              placement_policy="worstfit"))
    return svc, tr


def _submit(svc, k, n=N, start=0):
    rng = np.random.default_rng(start)
    for i in range(start, start + k):
        svc.submit("fl", f"c{i}", rng.standard_normal(n).astype(np.float32),
                   weight=1.0 + i % 3)


def test_rolling_rounds_file_each_layer_span_in_its_own_round():
    svc, tr = _service()
    try:
        _submit(svc, 3 * GOAL)
        recs = svc.run_rounds({"fl": 3}, policy=GoalPolicy())
        assert svc.pipeline_overlap() > 0      # two rounds were open
        nbytes = 4.0 * N
        for rec in recs:
            rid = rec["ticket"]
            t = tr.trace(rid)
            assert t is not None and t.round_id == rid
            assert all(s.round_id == rid for s in t.spans), rid
            for kind in NEW_KINDS:
                assert t.spans_of(kind), (rid, kind)
            k = int(rec["outcome"].accepted)
            mids = len([a for a in rec["assignment"].values() if a])
            assert k == GOAL and mids == 2
            # every update and every published partial goes into the store
            puts = t.spans_of("store.put")
            assert len(puts) == k + mids
            assert all(s.n == nbytes for s in puts)
            assert sum(s.owner == "driver" for s in puts) == k
            # one upload per folded update, one per partial the
            # controller's top fold adds
            ups = t.spans_of("fold.upload")
            assert len(ups) == k + mids and all(s.n == nbytes for s in ups)
            # each mid reads its accumulator back twice (result, then
            # the publish), the controller top fold once
            rbs = t.spans_of("readback")
            assert len(rbs) == 2 * mids + 1
            assert all(s.n == nbytes for s in rbs)
            (step,) = t.spans_of("server_step")
            assert step.n == 2.0 and step.owner == "fl"   # two leaves
            waits = t.spans_of("queue.wait")
            assert len(waits) == k and all(s.n == 1.0 for s in waits)
            assert sorted(s.owner for s in waits) == sorted(
                cid for _node, cid, _w in rec["cohort"])
            # the dispatch loop's puts lie inside deliver's time:
            # deliver still covers put plus deliver
            driver_puts = sum(s.dur_s for s in puts if s.owner == "driver")
            assert driver_puts <= t.sum_kind("deliver")
        # the removed sidecar series stays removed
        assert not any(m == "queue_delay_s"
                       for _o, m in svc.metrics.snapshot())
    finally:
        svc.close()


def test_server_step_reaches_the_trace_and_every_jsonl_line(tmp_path):
    # serve path: the scheduler finalizes after the trace was sealed
    path = str(tmp_path / "serve.jsonl")
    svc, tr = _service()
    tr.trace_path = path
    try:
        _submit(svc, 2 * GOAL)
        recs = svc.run_rounds({"fl": 2}, policy=GoalPolicy())
        for rec in recs:
            assert len(tr.trace(rec["ticket"]).spans_of("server_step")) == 1
    finally:
        svc.close()
    lines = read_traces(path)
    assert sorted(t.round_id for t in lines) == [r["ticket"] for r in recs]
    for t in lines:
        assert len(t.spans_of("server_step")) == 1, t.round_id

    # library path: run_round finalizes in place
    from repro.runtime import ClientRuntime, FederatedTrainer

    path = str(tmp_path / "library.jsonl")
    params = {"w": jnp.zeros((N,), jnp.float32)}
    # externals take every cohort slot: no client ever trains
    roster = [ClientRuntime(info=ClientInfo(f"slot{i}", 1), dataset=None)
              for i in range(4)]
    trainer = FederatedTrainer(
        _Model(), params, roster,
        nodes={"node0": NodeState(node="node0", max_capacity=64.0)},
        round_cfg=RoundConfig(aggregation_goal=4, over_provision=1.0),
        agg_engine="jnp", trace_path=path)
    try:
        rng = np.random.default_rng(1)
        for r in range(2):
            for i in range(4):
                trainer.submit_update(
                    f"c{r}.{i}", rng.standard_normal(N).astype(np.float32))
            trainer.run_round()
            assert len(trainer.trace().spans_of("server_step")) == 1
    finally:
        trainer.close()
    lines = read_traces(path)
    assert [t.round_id for t in lines] == [0, 1]
    assert all(len(t.spans_of("server_step")) == 1 for t in lines)


def test_queue_wait_is_pull_minus_queue_stamp():
    svc, tr = _service()
    stamps = {}
    pulls = []
    make_feed = svc._make_feed

    def timed_feed(*a, **kw):
        feed = make_feed(*a, **kw)

        def wrapped():
            before = time.perf_counter()
            item = feed()
            after = time.perf_counter()
            if isinstance(item, tuple):
                pulls.append((item[1], before, after))
            return item

        return wrapped

    svc._make_feed = timed_feed
    try:
        rng = np.random.default_rng(2)
        for i in range(2 * GOAL):
            svc.submit("fl", f"c{i}", rng.standard_normal(N).astype(
                np.float32))
            stamps[f"c{i}"] = tr._external[-1][3]
        recs = svc.run_rounds({"fl": 2}, policy=GoalPolicy())
    finally:
        svc.close()
    waits = {s.owner: s for rec in recs
             for s in tr.trace(rec["ticket"]).spans_of("queue.wait")}
    assert len(waits) == 2 * GOAL == len(pulls)
    for cid, before, after in pulls:
        s = waits[cid]
        assert s.t0 == stamps[cid]                 # the submit stamp
        assert before <= s.t0 + s.dur_s <= after   # the pull stamp


def _lifl_lines(xplane_path):
    """{line index: [(name, start_ns, dur_ns)]} of the host lines that
    hold ``lifl.*`` or ``test.rounds`` events."""
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                   if e.name.startswith("lifl.") or e.name == "test.rounds"]
            if evs:
                out[(plane.name, i)] = evs
    return out


def test_leaf_spans_are_mirrored_onto_the_profiler_clock(tmp_path):
    svc, tr = _service()
    try:
        _submit(svc, 2 * GOAL)
        svc.run_rounds({"fl": 1}, policy=GoalPolicy())   # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("test.rounds"):
                recs = svc.run_rounds({"fl": 1}, policy=GoalPolicy())
        finally:
            jax.profiler.stop_trace()
        trace = tr.trace(recs[0]["ticket"])
    finally:
        svc.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = _lifl_lines(path)
    # every mirrored event is on the thread that ran the rounds: the
    # line that holds the test's own annotation around them
    (line,) = [evs for evs in lines.values()
               if any(n == "test.rounds" for n, _a, _d in evs)]
    assert sum(len(evs) for evs in lines.values()) == len(line)
    names = {n for n, _a, _d in line}
    assert {"lifl.store.put", "lifl.readback", "lifl.fold.upload",
            "lifl.server_step", "lifl.release", "lifl.quiesce"} <= names
    assert not any(n in names for n in ("lifl.round", "lifl.dispatch",
                                        "lifl.collect", "lifl.fold",
                                        "lifl.queue.wait"))
    for kind in ("store.put", "readback"):
        evs = sorted(d for n, _a, d in line if n == "lifl." + kind)
        spans = sorted(s.dur_s * 1e9 for s in trace.spans_of(kind))
        assert len(evs) == len(spans) > 0, kind
        assert max(abs(e - s) for e, s in zip(evs, spans)) < 1e6, kind


class _Note:
    """A stand-in profiler annotation that logs what the tracer does."""

    log = []
    on = False

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        cls.log.append("check")
        return cls.on

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_mirror_enters_only_leaf_kinds_and_only_while_recording():
    tr = Tracer()
    tr.mirror_to(_Note)
    _Note.log, _Note.on = [], False
    tr.end(tr.begin("store.put"))
    assert _Note.log == ["check"]           # one check, nothing entered
    _Note.log, _Note.on = [], True
    for kind in ("round", "dispatch", "collect", "fold"):
        tr.end(tr.begin(kind))              # phases cross yields
    assert _Note.log == []
    for kind in sorted(MIRRORED_KINDS):
        span = tr.end(tr.begin(kind), n=3.0)
        assert span.kind == kind and span.n == 3.0
    assert _Note.log == [x for k in sorted(MIRRORED_KINDS)
                         for x in ("check", ("enter", f"lifl.{k}"),
                                   ("exit", f"lifl.{k}"))]
    tr.mirror_to(None)
    _Note.log = []
    tr.end(tr.begin("readback"))
    assert _Note.log == [] and tr.annotate("readback") is None


def test_end_keep_false_returns_the_span_without_buffering_it():
    tr = Tracer()
    kept = tr.end(tr.begin("release", round_id=3))
    loose = tr.end(tr.begin("server_step", round_id=3), n=62.0, keep=False)
    assert loose.kind == "server_step" and loose.n == 62.0
    assert tr.drain() == [kept]


def test_tracer_loses_no_span_under_thread_contention():
    tr = Tracer()
    threads, per = 16, 2000
    got = []
    stop = threading.Event()

    def work(i):
        for j in range(per):
            if j % 2:
                tr.point("subtree", 0.0, owner=f"t{i}", n=float(j))
            else:
                tr.end(tr.begin("store.put", owner=f"t{i}"), n=float(j))

    def drainer():
        while not stop.is_set():
            got.extend(tr.drain())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        d = threading.Thread(target=drainer)
        d.start()
        ws = [threading.Thread(target=work, args=(i,))
              for i in range(threads)]
        for w in ws:
            w.start()
        for w in ws:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in ws)
        stop.set()
        d.join(timeout=60)
        assert not d.is_alive()
    finally:
        sys.setswitchinterval(old)
    got.extend(tr.drain())
    assert len(got) == threads * per
    assert len({s.id for s in got}) == threads * per     # unique tokens
    assert not tr._open


def test_abandoned_spans_close_their_profiler_annotation():
    tr = Tracer()
    tr.mirror_to(_Note)
    _Note.log, _Note.on = [], True
    with pytest.raises(RuntimeError):
        with tr.span("readback", n=8.0, round_id=1):
            raise RuntimeError("device OOM")
    assert _Note.log == ["check", ("enter", "lifl.readback"),
                         ("exit", "lifl.readback")]
    assert [(s.kind, s.n) for s in tr.drain()] == [("readback", 8.0)]
    _Note.log = []
    tr.begin("fold.upload")                 # its end never comes
    tr.reset()
    assert _Note.log == ["check", ("enter", "lifl.fold.upload"),
                         ("exit", "lifl.fold.upload")]
    assert not tr._open and tr.drain() == []
    _Note.on = False


def test_unfinalized_rounds_are_written_once_they_fall_out(tmp_path):
    from repro.obs.trace import RoundTrace

    svc, tr = _service()
    try:
        tr.trace_path = str(tmp_path / "t.jsonl")
        cap = tr._traces_cap
        for rid in range(cap + 3):
            tr._sink_trace(RoundTrace(round_id=rid,
                                      meta={"completed": True}))
        assert len(tr._unwritten) == cap
        assert [t.round_id for t in read_traces(tr.trace_path)] == [0, 1, 2]
    finally:
        svc.close()
    assert [t.round_id for t in read_traces(tr.trace_path)] == \
        list(range(cap + 3))


@pytest.mark.slow
def test_netd_stays_free_of_jax_under_traced_rounds():
    from repro.runtime.driver import RoundDriver
    from repro.runtime.netrt import RemoteRuntime, spawn_local_daemon

    proc, addr = spawn_local_daemon("nodeA", runtime="inproc",
                                    stdout=subprocess.DEVNULL)
    try:
        rt = RemoteRuntime([addr], agg_engine="blocked")
        drv = RoundDriver(rt)               # tracing on by default
        rng = np.random.default_rng(3)
        ups = [("nodeA", f"c{i}", rng.standard_normal(256).astype(
            np.float32), 1.0) for i in range(4)]
        out = drv.run_round(round_id=0, assignment={"nodeA": [0, 1, 2, 3]},
                            updates=ups, goal=4, n_elems=256)
        assert out.count == 4
        assert drv.last_trace.spans_of("store.put")
        (reply,) = rt.poll_stats().values()
        assert reply["health"]["jax_imported"] is False
        rt.shutdown_nodes()
        rt.close()
    finally:
        if proc.poll() is None:
            proc.terminate()
        proc.wait(timeout=10)


def test_tracing_modules_import_without_jax():
    code = ("import sys; import repro.obs.trace, repro.runtime.driver, "
            "repro.runtime.netrt.netd; print(json.dumps('jax' in "
            "sys.modules))")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", "import json; " + code],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) is False
