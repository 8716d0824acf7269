"""Multi-node LIFL: two netd daemons, one Session, node-rooted rounds.

Spawns two per-node daemons as real OS processes (each owning its own
local runtime — shared-memory workers where /dev/shm exists), connects
a Session to the fleet, and drives hierarchical rounds under the
**node-top** fold topology: the round's top fold runs ON the busiest
worker node (the FoldPlan root), the other node ships its sealed
partial daemon→daemon, and only the final folded Σ c·u returns to the
controller — ~1 × model per round instead of nodes × model.  Then
turns the session into an ingest endpoint (`serve`) and pushes an
external update over the wire from a separate process, exactly as an
edge client would.

  PYTHONPATH=src python examples/multinode.py [--fast]
"""
import os
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import numpy as np

from repro.api import Session
from repro.compile_cache import enable_compile_cache
from repro.configs.resnet import RESNET18
from repro.core import ClientInfo, RoundConfig
from repro.data import build_client_datasets, dirichlet_partition, synthetic_femnist
from repro.models import build_resnet
from repro.runtime import ClientRuntime, PartialReady
from repro.runtime.events import PartialShipped, TopFolded
from repro.runtime.netrt import spawn_local_daemon

SRC = str(Path(__file__).parent.parent / "src")




def main(fast: bool = False):
    rounds = 2 if fast else 4
    node_rt = "shmproc" if os.path.isdir("/dev/shm") else "inproc"
    print(f"=== Multi-node LIFL: 2 × netd({node_rt}) over loopback TCP ===")

    cfg = RESNET18.reduced()
    model = build_resnet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    imgs, labels = synthetic_femnist(240, num_classes=10, seed=0)
    shards = dirichlet_partition(labels, 10, alpha=0.5)
    clients = [ClientRuntime(ClientInfo(d.client_id, d.num_samples), d)
               for d in build_client_datasets(imgs, labels, shards)]

    # capacity 4 < the over-provisioned cohort: the locality packer must
    # spill onto the second node, so the round actually exercises the
    # daemon→daemon partial ship (capacity 20 would fit on one node)
    daemons = [spawn_local_daemon(f"node{i}", runtime=node_rt, capacity=4)
               for i in range(2)]
    addrs = [a for _, a in daemons]
    try:
        with Session.open(
            model, params, clients, nodes=addrs,     # ← multi-node mode
            round_cfg=RoundConfig(aggregation_goal=4, over_provision=1.5,
                                  placement_policy="locality",
                                  topology="node"),  # ← node-side top fold
        ) as s:
            print(f"connected nodes: {list(s.nodes)}  "
                  f"(runtime={s.metrics()['runtime']})")
            n_model = sum(int(np.prod(np.shape(l)))
                          for l in jax.tree.leaves(params))
            model_mb = 4 * n_model / 1e6
            s.on(PartialReady,
                 lambda ev: print(f"  partial from {ev.agg_id}: "
                                  f"count={ev.count} Σc={ev.weight:.0f}"))
            s.on(PartialShipped,
                 lambda ev: print(f"  partial shipped {ev.src} → {ev.dst} "
                                  f"({ev.nbytes / 1e6:.2f} MB, "
                                  f"daemon→daemon)"))
            s.on(TopFolded,
                 lambda ev: print(f"  round rooted on {ev.node} "
                                  f"(tier={ev.tier}): top folded "
                                  f"count={ev.count}"))
            rx0 = 0.0
            for _ in range(rounds):
                rec = s.run_round(client_lr=0.05)
                rx1 = s.metrics()["sidecar"].get("net/rx_bytes", 0.0)
                ret_mb = (rx1 - rx0) / 1e6
                rx0 = rx1
                ctrl_mb = rec["nodes_used"] * model_mb
                print(f"round {int(rec['round'])}: updates={rec['updates']:.0f} "
                      f"nodes_used={rec['nodes_used']:.0f} "
                      f"workers={rec['workers']:.0f} "
                      f"wall={rec['wall_s']:.2f}s  return={ret_mb:.2f} MB "
                      f"(controller-top would return {ctrl_mb:.2f} MB)")
                # the TTA breakdown (§4.3): driver spans + each daemon's
                # telemetry, drained over the wire, one line per tier
                trace = s.trace()
                print(f"  {trace.summary()}")
                ship_s, ships = trace.telemetry_series("netd/ship_s")
                if ships:
                    print(f"  telemetry: {ships} partial ship(s) "
                          f"{ship_s * 1e3:.1f}ms on the shipping daemon, "
                          f"nodes drained: {sorted(trace.telemetry)}")

            # --- serve mode: external client process pushes an update --
            addr = s.serve("127.0.0.1:0")
            n = sum(int(np.prod(np.shape(l)))
                    for l in jax.tree.leaves(params))
            code = (
                "import numpy as np\n"
                "from repro.runtime.netrt import push_update\n"
                f"print('client:', push_update({addr!r}, 'edge-0', "
                f"np.zeros({n}, np.float32), weight=2.0))\n")
            env = dict(os.environ)
            env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
            subprocess.run([sys.executable, "-c", code], env=env, check=True)
            rec = s.run_round(client_lr=0.05)
            print(f"round {int(rec['round'])} (with external update): "
                  f"updates={rec['updates']:.0f}")
            print("sidecar bytes:",
                  {k: int(v) for k, v in s.metrics()["sidecar"].items()
                   if k.endswith("tx_bytes")})
    finally:
        for proc, _ in daemons:
            proc.terminate()
        for proc, _ in daemons:
            proc.wait(timeout=10)
    print("done: cross-node rounds drove the same RoundDriver loop; only "
          "sealed partials crossed the wire.")


if __name__ == "__main__":
    enable_compile_cache()
    main(fast="--fast" in sys.argv)
