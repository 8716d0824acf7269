"""End-to-end driver: federated training of a ~100M-parameter LM for a
few hundred rounds with the full stack — federated data pipeline, fused
LIFL rounds (eager hierarchical FedAvg), in-graph sidecar metrics,
async checkpointing, checkpoint/restart.

  PYTHONPATH=src python examples/train_fl_lm.py [--rounds 200] [--resume]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import dataclasses

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS
from repro.data import CohortTokenLoader
from repro.fl.round import AggregationConfig
from repro.launch.mesh import make_host_mesh
from repro.models import ModelOptions
from repro.runtime import FusedFLTrainer


def build_100m_config():
    """A ~100M-param llama-family config (12L, d=768, 12H/4KV, ff=2048)."""
    base = ARCHS["llama3.2-3b"]
    return dataclasses.replace(
        base,
        name="llama-fl-100m",
        num_layers=12,
        d_model=768,
        num_heads=12,
        num_kv_heads=4,
        head_dim=64,
        d_ff=2048,
        vocab_size=32000,
        dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--cohorts", type=int, default=4)
    ap.add_argument("--ckpt", default="results/fl_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--server-opt", default="fedadam")
    args = ap.parse_args()

    cfg = build_100m_config()
    print(f"model: {cfg.name} params={cfg.param_count()/1e6:.1f}M")
    mesh = make_host_mesh()
    agg = AggregationConfig(
        hierarchy="flat",              # single host 'pod'
        timing="eager",
        num_microbatches=args.cohorts,
        server_opt=args.server_opt,
        server_lr=3e-3 if args.server_opt == "fedadam" else 0.7,
    )
    opts = ModelOptions(attn_impl="chunked", moe_impl="dense",
                        loss_chunk=128, block_kv=128, remat=True)
    trainer = FusedFLTrainer(cfg, mesh, agg, opts=opts,
                             checkpoint_dir=args.ckpt, checkpoint_every=50)
    if args.resume and trainer.maybe_restore():
        print(f"resumed from round {trainer.round_id}")
    else:
        trainer.init(seed=0)

    loader = CohortTokenLoader(cfg.vocab_size, args.seq, args.cohorts)
    t0 = time.time()
    for r in range(trainer.round_id, args.rounds):
        rec = trainer.train_round(loader.round_batch(args.batch, r))
        if r % 10 == 0 or r == args.rounds - 1:
            tok_s = args.batch * args.seq * (r + 1 - trainer.round_id + 1) / max(
                time.time() - t0, 1e-9)
            print(f"round {r:4d} loss={rec['loss']:.4f} "
                  f"|Δ|={rec['update_norm']:.3f} ({tok_s:,.0f} tok/s)",
                  flush=True)
    if trainer.ckpt:
        trainer.ckpt.wait()
    print("final loss:", trainer.history[-1]["loss"])


if __name__ == "__main__":
    enable_compile_cache()
    main()
