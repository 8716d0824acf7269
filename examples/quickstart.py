"""Quickstart: one LIFL FL round, end to end, on CPU in ~a minute.

Shows the whole pipeline at toy scale through the public API:
  Session.open → clients → selector → BestFit placement → EWMA
  hierarchy plan → warm engines → RoundDriver event loop → eager
  hierarchical FedAvg → server update (plus an externally-submitted
  update riding a cohort slot),
then the same semantics as a single fused XLA step (the form the
512-chip dry-run lowers).

  PYTHONPATH=src python examples/quickstart.py [--fast]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Session
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS
from repro.core import ClientInfo, NodeState, RoundConfig
from repro.data import CohortTokenLoader, build_client_datasets, dirichlet_partition, synthetic_femnist
from repro.fl.round import AggregationConfig
from repro.launch.mesh import make_host_mesh
from repro.models import build_resnet, build_model, ModelOptions
from repro.configs.resnet import RESNET18
from repro.runtime import ClientRuntime, FusedFLTrainer, UpdateArrived


def part1_paper_faithful(rounds: int = 4):
    print("=== Part 1: paper-faithful LIFL round (ResNet-18-reduced, FEMNIST) ===")
    cfg = RESNET18.reduced()
    model = build_resnet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    imgs, labels = synthetic_femnist(400, num_classes=10, seed=0)
    shards = dirichlet_partition(labels, 12, alpha=0.5)
    clients = [
        ClientRuntime(ClientInfo(d.client_id, d.num_samples), d, failure_prob=0.1)
        for d in build_client_datasets(imgs, labels, shards)
    ]
    test = {"images": imgs[:128], "labels": labels[:128]}
    arrivals = []
    with Session.open(
        model, params, clients,
        nodes={f"node{i}": NodeState(node=f"node{i}", max_capacity=20)
               for i in range(3)},
        round_cfg=RoundConfig(aggregation_goal=6, over_provision=1.5),
    ) as sess:
        sess.on(UpdateArrived, lambda ev: arrivals.append(ev.client_id))
        print("  before:", sess.evaluate(test))
        for r in range(rounds):
            if r == 1:
                # an externally-computed update rides a cohort slot
                # (a params-shaped pytree delta; flat vectors work too)
                sess.submit_update(
                    "edge-client",
                    jax.tree.map(np.zeros_like, sess.params), weight=1.0)
            rec = sess.run_round(client_lr=0.05, client_batch_size=32)
            print(f"  round {r}: updates={rec['updates']:.0f} "
                  f"nodes={rec['nodes_used']:.0f} inter_node={rec['inter_node']:.0f} "
                  f"cold={rec['cold_starts']:.0f} reused={rec['reused']:.0f}")
        print("  after :", sess.evaluate(test))
        m = sess.metrics()
        print(f"  metrics: model_version={m['model_version']} "
              f"events={m['driver']['events_dispatched']} "
              f"arrivals_seen={len(arrivals)}")


def part2_fused_round(rounds: int = 6):
    print("=== Part 2: fused FL round as one XLA program (tiny llama) ===")
    cfg = ARCHS["llama3.2-3b"].reduced(dtype="float32")
    mesh = make_host_mesh()
    agg = AggregationConfig(hierarchy="flat", timing="eager", num_microbatches=4)
    opts = ModelOptions(attn_impl="chunked", moe_impl="dense", ssm_chunk=8,
                        loss_chunk=16, block_kv=8, remat=False)
    trainer = FusedFLTrainer(cfg, mesh, agg, opts=opts)
    trainer.init(seed=0)
    loader = CohortTokenLoader(cfg.vocab_size, seq_len=32, n_cohorts=4)
    for r in range(rounds):
        rec = trainer.train_round(loader.round_batch(16, r))
        print(f"  round {r}: loss={rec['loss']:.4f} "
              f"updates={rec['updates_aggregated']:.0f} "
              f"|Δ|={rec['update_norm']:.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    fast = "--fast" in sys.argv[1:]
    part1_paper_faithful(rounds=2 if fast else 4)
    part2_fused_round(rounds=2 if fast else 6)
    print("quickstart OK")
