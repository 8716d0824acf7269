"""Elastic scaling through the event protocol (Fig 10(a,b) shape).

A varying client arrival rate drives the EWMA hierarchy planner; the
elastic controller and the coordinator are ordinary event handlers on
the Session's round driver: ``NodeLost``/``NodeJoined`` injected with
``Session.emit`` reshape the *next* round's plan (the warm pool absorbs
re-plans without cold starts), and every re-plan is published as a
typed ``ScaleDecision`` event.

  PYTHONPATH=src python examples/elastic_scaling.py [--fast]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax

from repro.api import Session
from repro.compile_cache import enable_compile_cache
from repro.configs.resnet import RESNET18
from repro.core import ClientInfo, NodeState, RoundConfig
from repro.data import build_client_datasets, dirichlet_partition, synthetic_femnist
from repro.models import build_resnet
from repro.runtime import (
    ArrivalTrace,
    ClientRuntime,
    ElasticController,
    NodeJoined,
    NodeLost,
    ScaleDecision,
)


def main(rounds: int = 8):
    cfg = RESNET18.reduced()
    model = build_resnet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    imgs, labels = synthetic_femnist(300, num_classes=10, seed=0)
    shards = dirichlet_partition(labels, 10, alpha=0.5)
    clients = [ClientRuntime(ClientInfo(d.client_id, d.num_samples), d)
               for d in build_client_datasets(imgs, labels, shards)]
    nodes = {f"n{i}": NodeState(node=f"n{i}", max_capacity=20) for i in range(5)}

    ec = ElasticController(nodes)
    trace = ArrivalTrace(base_rate=40, variability=0.6, period_rounds=6)
    decisions = []

    with Session.open(
        model, params, clients, nodes=nodes,
        round_cfg=RoundConfig(aggregation_goal=4, over_provision=1.5),
    ) as sess:
        # the controller reacts to churn; anyone can watch the decisions
        sess.on(NodeLost, ec.handle)
        sess.on(NodeJoined, ec.handle)
        sess.on(ScaleDecision, decisions.append)

        print(f"{'round':>5} {'arrivals':>9} {'aggs':>5} {'nodes':>6} "
              f"{'updates':>8} {'reused':>7}")
        for r in range(rounds):
            if r == rounds // 2:
                sess.emit(NodeLost(node="n1"))            # pod failure
            if r == rounds - 2:
                sess.emit(NodeJoined(node="n5", capacity=20))  # replacement
            rate = trace.rate(r)
            sess.emit(ec.decide(r, expected_updates=rate))
            rec = sess.run_round(client_lr=0.05, client_batch_size=32)
            d = decisions[-1]
            print(f"{r:5d} {rate:9.1f} {d.aggregators_planned:5d} "
                  f"{rec['nodes_used']:6.0f} {rec['updates']:8.0f} "
                  f"{rec['reused']:7.0f}")

        print("\ncontroller events:")
        for e in ec.events[:12]:
            print(f"  round {e.round_id}: {e.kind} {e.detail}")
        print(f"scale decisions: "
              f"{[f'{d.round_id}:{d.direction}' for d in decisions]}")
    print("elastic_scaling OK")


if __name__ == "__main__":
    enable_compile_cache()
    main(rounds=4 if "--fast" in sys.argv[1:] else 8)
