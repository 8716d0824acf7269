"""The multi-node transport: frame protocol, netd daemons, RemoteRuntime
through the unchanged RoundDriver, dead-peer teardown, and serve mode.

Daemon-based tests spawn real OS processes (``python -m
repro.runtime.netrt.netd``) joined to the controller by loopback TCP —
the acceptance scenario is two daemons each running its *own*
shared-memory runtime, producing params bit-identical to the
single-node in-proc tree over 3 rounds."""
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.core.aggregation import fedavg_oracle
from repro.runtime.driver import InProcRuntime, RoundDriver
from repro.runtime.events import NodeLost, WorkerCrashed
from repro.runtime.netrt import (
    FrameConn,
    FrameServer,
    PeerDead,
    RemoteRuntime,
    connect,
    push_update,
    spawn_local_daemon,
)
from repro.runtime.netrt.transport import parse_addr

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


# ---------------------------------------------------------------------------
# transport
# ---------------------------------------------------------------------------

def _pair():
    a, b = socket.socketpair()
    return FrameConn(a, peer="a"), FrameConn(b, peer="b")


def test_frame_roundtrip_with_blob():
    a, b = _pair()
    payload = np.arange(1000, dtype=np.float32)
    a.send("deliver", {"agg_id": "mid@n0", "weight": 2.5,
                       "dtype": "float32", "shape": [1000]},
           blob=payload)
    f = b.recv(timeout=2.0)
    assert f.kind == "deliver" and f.meta["weight"] == 2.5
    back = np.frombuffer(f.blob, np.float32)
    np.testing.assert_array_equal(back, payload)
    # counters saw the full frame both ways
    assert a.tx_bytes == b.rx_bytes > payload.nbytes
    assert a.tx_by_kind["deliver"] == b.rx_by_kind["deliver"]
    a.close(), b.close()


def test_frames_survive_partial_reads_and_coalescing():
    """Many frames written back-to-back parse out one by one, whatever
    the segmentation (the incremental parser keeps partial frames)."""
    a, b = _pair()
    for i in range(50):
        a.send("event", {"i": i}, blob=bytes([i]) * i)
    got = [b.recv(timeout=2.0) for _ in range(50)]
    assert [f.meta["i"] for f in got] == list(range(50))
    assert all(len(f.blob) == f.meta["i"] for f in got)
    a.close(), b.close()


def test_recv_timeout_returns_none_then_completes():
    a, b = _pair()
    assert b.recv(timeout=0.05) is None
    a.send("ping", {})
    assert b.recv(timeout=2.0).kind == "ping"
    a.close(), b.close()


def test_dead_peer_raises_peerdead():
    a, b = _pair()
    a.close()
    with pytest.raises(PeerDead):
        while True:
            b.recv(timeout=1.0)
    assert not b.alive


def _next_frame(srv, timeout=5.0):
    """Poll a FrameServer until a real frame arrives (accept and first
    frame usually land in separate poll calls)."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        for c, f in srv.poll(0.1):
            if f is not None:
                return c, f
    raise AssertionError("no frame within timeout")


def test_connect_retries_until_server_binds():
    """A controller may start before its daemons: connect keeps
    retrying until the listener appears."""
    held: dict = {}

    def bind_late():
        time.sleep(0.4)
        held["srv"] = FrameServer(f"127.0.0.1:{held['port']}")

    # reserve a port, release it, bind it late from the thread
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    held["port"] = probe.getsockname()[1]
    probe.close()
    t = threading.Thread(target=bind_late)
    t.start()
    conn = connect(f"127.0.0.1:{held['port']}", timeout=5.0)
    t.join()
    conn.send("ping", {})
    _, f = _next_frame(held["srv"])
    assert f.kind == "ping"
    conn.close()
    held["srv"].close()


def test_unix_socket_addr():
    path = tempfile.mktemp(suffix=".nrt.sock")
    srv = FrameServer(f"unix:{path}")
    assert srv.addr == f"unix:{path}" and os.path.exists(path)
    conn = connect(srv.addr, timeout=2.0)
    conn.send("hello", {"role": "client"})
    _, f = _next_frame(srv)
    assert f.kind == "hello"
    conn.close()
    srv.close()
    assert not os.path.exists(path)   # unlinked on close


def test_parse_addr_rejects_garbage():
    with pytest.raises(ValueError):
        parse_addr("no-port-here")


# ---------------------------------------------------------------------------
# netd daemons
# ---------------------------------------------------------------------------

def _spawn_netd(node, runtime="inproc", timeout=30.0):
    return spawn_local_daemon(node, runtime=runtime, timeout=timeout,
                              stdout=subprocess.DEVNULL)


@pytest.fixture
def two_inproc_daemons():
    procs, addrs = [], []
    for name in ("nodeA", "nodeB"):
        p, a = _spawn_netd(name, "inproc")
        procs.append(p)
        addrs.append(a)
    yield procs, addrs
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


def _mk_updates(n_updates=6, n_elems=4096, seed=0):
    rng = np.random.default_rng(seed)
    ups = [rng.normal(size=n_elems).astype(np.float32)
           for _ in range(n_updates)]
    ws = [float(1 + i % 3) for i in range(n_updates)]
    return ups, ws


def _drive(drv, nodes, ups, ws, n_elems, round_id, kill_after=None):
    """One driven round: update i → nodes[i % 2]; ``kill_after=(idx,
    fn)`` calls ``fn`` right after update ``idx`` is delivered."""
    assignment = {nodes[0]: [i for i in range(len(ups)) if i % 2 == 0],
                  nodes[1]: [i for i in range(len(ups)) if i % 2 == 1]}

    def updates():
        for i, (u, w) in enumerate(zip(ups, ws)):
            yield nodes[i % 2], f"c{i}", u, w
            if kill_after is not None and i == kill_after[0]:
                kill_after[1]()

    return drv.run_round(round_id=round_id, assignment=assignment,
                         updates=updates(), goal=len(ups), n_elems=n_elems)


@pytest.mark.slow
def test_two_shm_nodes_three_rounds_bitexact_vs_inproc():
    """THE acceptance scenario: two OS processes joined by sockets,
    each running its own shared-memory runtime (forked workers, shm
    rings), 3 hierarchical rounds — params bit-identical to the
    single-node in-proc tree, only sealed partials on the wire."""
    if not os.path.isdir("/dev/shm"):
        pytest.skip("POSIX shared memory required")
    N = 4096
    ups, ws = _mk_updates(6, N)
    procs, addrs = [], []
    try:
        for name in ("nodeA", "nodeB"):
            p, a = _spawn_netd(name, "shmproc")
            procs.append(p)
            addrs.append(a)
        rt = RemoteRuntime(addrs)
        assert list(rt.node_info()) == ["nodeA", "nodeB"]
        drv = RoundDriver(rt)
        net_deltas = []
        for rid in range(3):
            out = _drive(drv, ["nodeA", "nodeB"], ups, ws, N, rid)
            assert out.count == 6 and out.crashes == 0
            net_deltas.append(out.delta)
        # partials-only traffic: per warm round, each node ships ~one
        # model-size object payload (plus tiny frame overhead)
        wire = rt.wire_stats()
        for name in ("nodeA", "nodeB"):
            obj = wire[name]["rx_by_kind"]["object"]
            assert obj <= 3 * (4 * N) * 1.1
        # nothing in-flight leaks at rest
        assert not rt._staged and not rt._partial_home
        rt.shutdown_nodes()
        rt.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            p.wait(timeout=10)

    in_rt = InProcRuntime()
    in_drv = RoundDriver(in_rt)
    for rid in range(3):
        ref = _drive(in_drv, ["nodeA", "nodeB"], ups, ws, N, rid)
        np.testing.assert_array_equal(ref.delta, net_deltas[rid])
    in_rt.close()


@pytest.mark.slow
def test_sigkilled_netd_mid_round_redispatches_to_survivor(
        two_inproc_daemons):
    """Dead-peer teardown (the transport fix): SIGKILL one netd
    mid-round → NodeLost + synthesized WorkerCrashed → the driver
    re-dispatches the subtree's staged keys to the surviving node, the
    round reaches its FULL goal, and no in-flight bookkeeping leaks."""
    procs, addrs = two_inproc_daemons
    N = 2048
    ups, ws = _mk_updates(6, N, seed=1)
    rt = RemoteRuntime(addrs)
    drv = RoundDriver(rt)
    lost, crashed = [], []
    drv.on(NodeLost, lost.append)
    drv.on(WorkerCrashed, crashed.append)

    def kill_b():
        os.kill(procs[1].pid, signal.SIGKILL)
        procs[1].wait()
        time.sleep(0.05)

    out = _drive(drv, ["nodeA", "nodeB"], ups, ws, N, 0,
                 kill_after=(4, kill_b))
    # full goal despite the node loss: the subtree moved to nodeA
    assert out.count == 6 and out.crashes == 1 and out.redispatched == 1
    assert [e.node for e in lost] == ["nodeB"]
    assert [e.agg_id for e in crashed] == ["mid@nodeB"]
    np.testing.assert_allclose(out.delta, fedavg_oracle(ups, ws),
                               rtol=1e-5, atol=1e-6)
    # dead-peer teardown released the node's in-flight round objects
    assert not rt._staged and not rt._partial_home
    assert all(not n.delivered for n in rt._nodes.values())
    assert rt.stats["node_lost"] == 1
    # the next round still runs, on the survivor alone
    out2 = drv.run_round(
        round_id=1, assignment={"nodeA": list(range(6))},
        updates=(("nodeA", f"c{i}", u, w)
                 for i, (u, w) in enumerate(zip(ups, ws))),
        goal=6, n_elems=N)
    assert out2.count == 6 and out2.crashes == 0
    rt.close()


def test_remote_runtime_duplicate_node_name_rejected(two_inproc_daemons):
    procs, addrs = two_inproc_daemons
    p, addr = _spawn_netd("nodeA")   # name collides with the fixture's
    try:
        with pytest.raises(ValueError, match="duplicate node name"):
            RemoteRuntime([addrs[0], addr])
    finally:
        p.terminate()
        p.wait(timeout=10)


def test_daemon_survives_bad_frames(two_inproc_daemons):
    """A malformed request gets an error reply; the daemon stays up."""
    _, addrs = two_inproc_daemons
    conn = connect(addrs[0], timeout=5.0)
    conn.send("hello", {"role": "client"})
    assert conn.recv_expect(("welcome",), 5.0).meta["node"] == "nodeA"
    conn.send("deliver", {"agg_id": "mid@nodeA", "key": "nope",
                          "weight": 1.0, "round_id": 0})   # no blob, unknown
    err = conn.recv_expect(("error",), 5.0)
    assert "nope" in err.meta["msg"]
    assert conn.ping() < 5.0                 # still alive
    conn.close()


# ---------------------------------------------------------------------------
# one process per chip: netd is host-side and never loads jax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["auto", "pallas", "jnp"])
def test_netd_refuses_device_engines(engine):
    from repro.runtime.netrt.netd import NodeDaemon

    with pytest.raises(ValueError, match="host"):
        NodeDaemon("x", agg_engine=engine)


@pytest.mark.slow
def test_netd_folds_a_round_without_importing_jax():
    """A spawned shmproc daemon folds a full round (forked workers
    included) and its live stats still say jax was never imported —
    the controller is the only process that may claim the chip."""
    if not os.path.isdir("/dev/shm"):
        pytest.skip("POSIX shared memory required")
    N = 2048
    ups, ws = _mk_updates(6, N, seed=3)
    procs, addrs = [], []
    try:
        for name in ("nodeA", "nodeB"):
            p, a = _spawn_netd(name, "shmproc")
            procs.append(p)
            addrs.append(a)
        rt = RemoteRuntime(addrs, agg_engine="blocked")
        out = _drive(RoundDriver(rt), ["nodeA", "nodeB"], ups, ws, N, 0)
        np.testing.assert_allclose(out.delta, fedavg_oracle(ups, ws),
                                   rtol=1e-5, atol=1e-6)
        stats = rt.poll_stats()
        assert set(stats) == {"nodeA", "nodeB"}
        for reply in stats.values():
            assert reply["health"]["jax_imported"] is False
            assert reply["workers"] >= 1
        rt.shutdown_nodes()
        rt.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            p.wait(timeout=10)


# ---------------------------------------------------------------------------
# Session-level multi-node + serve mode
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
    clients = lambda: [  # noqa: E731 - fresh fleet per session
        ClientRuntime(ClientInfo(d.client_id, d.num_samples), d)
        for d in build_client_datasets(imgs, labels, shards)]
    return model, params, clients


@pytest.mark.slow
def test_session_multinode_params_match_inproc(two_inproc_daemons):
    """Session.open(nodes=[addr, addr]) drives the same rounds as a
    single-node inproc session with identically named/sized NodeStates:
    params bit-identical (same cohorts, same plan, same arithmetic)."""
    import jax
    from repro.api import Session
    from repro.core import NodeState, RoundConfig

    _, addrs = two_inproc_daemons
    model, params, clients = _mk_session_fixtures()
    rc = RoundConfig(aggregation_goal=4, over_provision=1.5,
                     placement_policy="locality")

    with Session.open(model, params, clients(), nodes=list(addrs),
                      round_cfg=rc) as s:
        assert set(s.nodes) == {"nodeA", "nodeB"}
        assert s.metrics()["runtime"] == "net"
        for _ in range(2):
            rec = s.run_round(client_lr=0.05)
            assert rec["updates"] == 4.0
        net_params = s.params
        side = s.metrics()["sidecar"]
        assert side.get("net/tx_bytes", 0) > 0    # updates to the nodes
        assert side.get("net/rx_bytes", 0) > 0    # fetched partials

    with Session.open(
            model, params, clients(),
            nodes={"nodeA": NodeState(node="nodeA", max_capacity=20.0),
                   "nodeB": NodeState(node="nodeB", max_capacity=20.0)},
            round_cfg=rc) as s2:
        for _ in range(2):
            s2.run_round(client_lr=0.05)
        ref_params = s2.params

    for a, b in zip(jax.tree.leaves(net_params),
                    jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_session_serve_accepts_external_client_process():
    """Serve mode: an external OS process pushes a submit_update frame
    over the wire; it takes a cohort slot in the next round."""
    import jax
    from repro.api import Session
    from repro.core import RoundConfig
    from repro.runtime.events import UpdateArrived

    model, params, clients = _mk_session_fixtures()
    n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
    with Session.open(model, params, clients(),
                      round_cfg=RoundConfig(aggregation_goal=3,
                                            over_provision=1.0)) as s:
        addr = s.serve("127.0.0.1:0")
        assert s.serve_addr == addr
        assert s.serve(addr) == addr          # idempotent
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
        code = (
            "import numpy as np\n"
            "from repro.runtime.netrt import push_update\n"
            f"ack = push_update({addr!r}, 'edge-7', "
            f"np.full({n}, 0.25, np.float32), weight=3.0)\n"
            "assert ack['queued'] == 1, ack\n")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        seen = []
        s.on(UpdateArrived, lambda ev: seen.append(ev.client_id))
        s.run_round(client_lr=0.05)
        assert "edge-7" in seen               # it took a cohort slot
    assert s.serve_addr is None               # close stopped the server


def test_serve_rejects_wrong_size_update():
    """A bad external update is refused with an error frame; the serve
    loop keeps running."""
    model, params, clients = _mk_session_fixtures()
    from repro.api import Session

    with Session.open(model, params, clients()) as s:
        addr = s.serve("127.0.0.1:0")
        with pytest.raises(ValueError, match="rejected"):
            push_update(addr, "edge-bad", np.zeros(3, np.float32))
        # still serving after the rejection
        conn = connect(addr, timeout=5.0)
        assert conn.ping() < 5.0
        conn.close()
        # a size-matching but non-1-D payload is flattened on ingest,
        # never queued with a shape the fold loop would trip over
        import jax
        n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
        push_update(addr, "edge-2d", np.zeros((1, n), np.float32))
        assert s.trainer._external[-1][1].ndim == 1
        s.trainer.submit_update("direct-2d", np.zeros((1, n), np.float32))
        assert s.trainer._external[-1][1].ndim == 1


def test_daemon_parks_runtime_when_controller_disconnects(
        two_inproc_daemons):
    """A controller that dies mid-round must not strand its open
    aggregators on the daemon: when the last controller disconnects the
    daemon quiesces, so a reconnecting controller can spawn the same
    agg_ids again."""
    _, addrs = two_inproc_daemons

    def controller():
        conn = connect(addrs[0], timeout=5.0)
        conn.send("hello", {"role": "controller"})
        conn.recv_expect(("welcome",), 5.0)
        conn.send("spawn", {"agg_id": "mid@nodeA", "goal": 2,
                            "n_elems": 64, "round_id": 0})
        return conn

    c1 = controller()
    c1.close()          # dies mid-round, task still open on the daemon
    time.sleep(0.3)     # let the daemon notice and park
    c2 = controller()   # same agg_id spawns cleanly again
    # an error reply for the spawn would arrive before the pong
    stash = []
    c2.ping(timeout=5.0, stash=stash)
    assert not [f for f in stash if f.kind == "error"]
    c2.close()


def test_daemon_error_reply_synthesizes_worker_crash(two_inproc_daemons):
    """A daemon-side spawn/deliver failure (the daemon survives, replies
    with an error frame) must not hang the round: the controller
    synthesizes a WorkerCrashed so the driver's re-dispatch — and its
    give-up cap — take over."""
    _, addrs = two_inproc_daemons
    rt = RemoteRuntime(addrs)
    rt.spawn_aggregator("mid@nodeA", goal=2, n_elems=64, round_id=3)
    # second spawn for the same open agg_id: the daemon refuses it
    rt.spawn_aggregator("mid@nodeA", goal=2, n_elems=64, round_id=3)
    deadline = time.perf_counter() + 10.0
    evs = []
    while not evs and time.perf_counter() < deadline:
        evs = [e for e in rt.poll_events(0.2)
               if isinstance(e, WorkerCrashed)]
    assert evs and evs[0].agg_id == "mid@nodeA" and evs[0].round_id == 3
    assert rt.stats["refused"] >= 1
    rt.close()


def test_quiesce_keeps_node_lost_drops_round_scoped(two_inproc_daemons):
    """The inter-round barrier must not eat cluster-state events: a
    NodeLost queued by a peer death survives quiesce (the coordinator
    still has to drop the node), while a stale WorkerCrashed — whose
    agg_id will be reused next round — does not."""
    procs, addrs = two_inproc_daemons
    rt = RemoteRuntime(addrs)
    rt.spawn_aggregator("mid@nodeB", goal=2, n_elems=64, round_id=0)
    os.kill(procs[1].pid, signal.SIGKILL)
    procs[1].wait()
    # a failed send tears the peer down and queues NodeLost + a
    # synthetic WorkerCrashed without anyone polling.  The FIRST send
    # after the kill may still land in the kernel buffer (no RST seen
    # yet), so retry until the teardown has fired — deterministic
    # within a couple of iterations.
    deadline = time.perf_counter() + 10.0
    while not rt._pending and time.perf_counter() < deadline:
        rt.drain("mid@nodeB")
        time.sleep(0.05)
    assert any(isinstance(e, NodeLost) for e in rt._pending)
    rt.quiesce()
    evs = rt.poll_events(0.0)
    assert [e.node for e in evs if isinstance(e, NodeLost)] == ["nodeB"]
    assert not [e for e in evs if isinstance(e, WorkerCrashed)]
    rt.close()


def test_session_open_rejects_runtime_with_node_addresses():
    from repro.api import Session

    with pytest.raises(ValueError, match="netd --runtime"):
        Session.open(object(), {}, [], runtime="shmproc",
                     nodes=["127.0.0.1:1"])


def test_session_multinode_close_before_first_round_closes_fleet(
        two_inproc_daemons):
    """Session.open(nodes=[...]) connects immediately, so close()
    before the first run_round must still reach the fleet — otherwise
    every daemon keeps a stale controller registered forever."""
    from repro.api import Session

    model, params, clients = _mk_session_fixtures()
    _, addrs = two_inproc_daemons
    s = Session.open(model, params, clients(), nodes=list(addrs))
    rt = s.trainer._runtime
    assert rt is not None                     # eager attach
    s.close()
    assert all(not n.alive for n in rt._nodes.values())


def test_node_death_between_publish_and_fetch_aborts_retriable(
        two_inproc_daemons):
    """The fail-stop window: a node dies after publishing its partial
    but before the top fold fetches it.  get_partial must run the full
    dead-peer teardown and raise; the driver's exception path closes
    the round retriable instead of hanging or leaking bookkeeping."""
    procs, addrs = two_inproc_daemons
    N = 512
    ups, ws = _mk_updates(4, N, seed=2)
    rt = RemoteRuntime(addrs)
    drv = RoundDriver(rt)

    real_get = rt.get_partial

    def dying_get(key):
        if rt._partial_home.get(key) == "nodeB" and procs[1].poll() is None:
            os.kill(procs[1].pid, signal.SIGKILL)
            procs[1].wait()
            time.sleep(0.05)
        return real_get(key)

    rt.get_partial = dying_get
    with pytest.raises(KeyError, match="lost with its node|unreachable"):
        _drive(drv, ["nodeA", "nodeB"], ups, ws, N, 0)
    rt.get_partial = real_get
    assert not rt._nodes["nodeB"].alive        # teardown ran
    assert not rt._staged                      # round objects released
    # the driver stays usable: retry on the survivor under the SAME rid
    out = drv.run_round(
        round_id=0, assignment={"nodeA": list(range(4))},
        updates=(("nodeA", f"c{i}", u, w)
                 for i, (u, w) in enumerate(zip(ups, ws))),
        goal=4, n_elems=N)
    assert out.count == 4
    np.testing.assert_allclose(out.delta, fedavg_oracle(ups, ws),
                               rtol=1e-5, atol=1e-6)
    rt.close()


# ---------------------------------------------------------------------------
# wire compression (FrameConn(compress=...))
# ---------------------------------------------------------------------------

def test_compressed_frame_roundtrip_and_counters():
    a, b = _pair()
    a.compress = 6
    payload = np.tile(np.arange(256, dtype=np.float32), 64)  # compressible
    a.send("deliver", {"agg_id": "mid@n0", "weight": 1.0}, blob=payload)
    f = b.recv(timeout=2.0)
    np.testing.assert_array_equal(np.frombuffer(f.blob, np.float32), payload)
    assert "_z" not in f.meta            # the marker never leaks upward
    # the wire carried far fewer bytes than the raw frame
    assert a.tx_by_kind["deliver"] < a.tx_raw_by_kind["deliver"] / 2
    assert b.rx_raw_by_kind["deliver"] == a.tx_raw_by_kind["deliver"]
    assert b.rx_by_kind["deliver"] == a.tx_by_kind["deliver"]
    a.close(), b.close()


def test_compression_falls_back_to_raw():
    a, b = _pair()
    a.compress = 6
    # incompressible blob: sent raw (no size win), decoded unchanged
    rnd = np.random.default_rng(0).integers(0, 256, 4096) \
        .astype(np.uint8).tobytes()
    a.send("x", {}, blob=rnd)
    assert b.recv(timeout=2.0).blob == rnd
    assert a.tx_by_kind["x"] == a.tx_raw_by_kind["x"]
    # tiny blobs below the threshold are never compressed
    a.send("y", {}, blob=b"abc")
    assert b.recv(timeout=2.0).blob == b"abc"
    a.close(), b.close()


def test_compressed_remote_round_bitexact(two_inproc_daemons):
    """End-to-end with compress on: the daemons decode the compressed
    update blobs, the round's delta is bit-identical to the
    uncompressed in-proc reference, and the update traffic measurably
    shrank (float32 model weights compress)."""
    _, addrs = two_inproc_daemons
    N = 4096
    # compressible updates (real weights compress less than this, but
    # the transport must win when the payload allows it)
    ups = [np.tile(np.float32(i + 1), N) for i in range(4)]
    ws = [1.0, 2.0, 1.0, 3.0]

    in_rt = InProcRuntime()
    ref = _drive(RoundDriver(in_rt), ["nodeA", "nodeB"], ups, ws, N, 0)
    in_rt.close()

    rt = RemoteRuntime(addrs, compress=6)
    out = _drive(RoundDriver(rt), ["nodeA", "nodeB"], ups, ws, N, 0)
    np.testing.assert_array_equal(out.delta, ref.delta)
    wire = rt.wire_stats()
    rt.close()
    total_tx = sum(v["tx_bytes"] for v in wire.values())
    # 4 updates × 16 KiB raw: with compression the deliver path must
    # ship far less than the raw payload bytes
    assert total_tx < 4 * 4 * N / 2
