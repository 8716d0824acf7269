import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
#   backend initialization.  Only the dry-run forces 512 placeholder
#   devices — tests/benchmarks see the single real CPU device.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent end to
end: sharding rules, collective schedule, FL aggregation hierarchy, and
memory footprint, via ``jax.jit(...).lower(...).compile()`` against
ShapeDtypeStruct inputs (no allocation).  Prints
``compiled.memory_analysis()`` (fits/doesn't) and
``compiled.cost_analysis()`` (roofline terms), parses collective bytes
from the optimized HLO, and writes a JSON record consumed by
EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      --arch llama3.2-3b --shape train_4k --mesh multi \
      [--hierarchy hierarchical|flat] [--timing eager|lazy]
      [--compress none|int8] [--micro 4] [--out results/dryrun]
"""
import argparse
import json
import time
from functools import partial
from pathlib import Path

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo import collective_stats
from repro.analysis.hlo_cost import parse_hlo_cost
from repro.analysis.roofline import from_compiled
from repro.configs import get_arch, get_shape, shape_applicable
from repro.fl.round import (
    AggregationConfig,
    abstract_caches,
    abstract_params,
    build_decode_step,
    build_prefill_step,
    build_train_step,
    input_specs,
    serve_shardings,
    train_shardings,
)
from repro.fl.server import init_server_state
from repro.launch.mesh import dp_axes as mesh_dp_axes
from repro.launch.mesh import make_production_mesh
from repro.sharding import batch_specs, cache_specs, divisibility_fix, to_named


def _memory_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        # peak per-device estimate: args + temps + outputs - aliased
        out["peak_bytes_per_device"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out or {"repr": str(ma)}


def _cost_dict(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()}


def run_cell(
    arch_name: str,
    shape_name: str,
    mesh_kind: str,
    *,
    hierarchy: str = "hierarchical",
    timing: str = "eager",
    compress: str = "none",
    micro: int = 4,
    fsdp: str = "auto",
    acc_dtype: str = "float32",
    opts_override: dict | None = None,
    verbose: bool = True,
):
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    record = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
        "hierarchy": hierarchy, "timing": timing, "compress": compress,
        "micro": micro,
    }
    if not ok:
        record["status"] = "skipped"
        record["reason"] = why
        return record

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    dp = mesh_dp_axes(mesh)
    agg = AggregationConfig(
        hierarchy=hierarchy, timing=timing, compress=compress,
        num_microbatches=micro, acc_dtype=acc_dtype,
    )
    if fsdp == "auto":
        # FSDP costs a per-layer-per-microbatch weight all-gather (scan
        # bodies can't hoist it), so shard params over `data` only when
        # TP-only residency would blow HBM: params(bf16) + grads(bf16) +
        # fp32 accumulator ≈ 8 bytes/param over the model axis.
        model_shards = mesh.shape["model"]
        tp_only_bytes = cfg.param_count() * 8 / model_shards
        if tp_only_bytes <= 6e9:
            fsdp_axes = ()
        else:
            fsdp_axes = dp if hierarchy == "flat" else ("data",)
    else:
        fsdp_axes = tuple(a for a in fsdp.split(",") if a)

    opts = None
    if opts_override:
        from repro.models.transformer import ModelOptions
        from repro.launch.mesh import pod_axis as _pod_axis
        base = dict(
            attn_impl="chunked_sp",
            moe_impl="ep" if cfg.moe is not None else "dense",
            ssm_impl="sharded",
            dp_axes=dp if (hierarchy == "flat" or _pod_axis(mesh) is None)
            else ("data",),
            model_axis="model",
            vocab_axis="model",
        )
        base.update(opts_override)
        opts = ModelOptions(**base)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step, model = build_train_step(cfg, mesh, agg, opts=opts)
            aparams = abstract_params(model)
            pspecs, sspecs = train_shardings(model, mesh, agg, fsdp=fsdp_axes)
            astate = jax.eval_shape(
                partial(init_server_state, agg.server_opt), aparams
            )
            abatch = input_specs(cfg, shape)
            bspecs = divisibility_fix(batch_specs(abatch, dp), abatch, mesh)
            fn = jax.jit(
                step,
                in_shardings=(to_named(pspecs, mesh), to_named(sspecs, mesh),
                              to_named(bspecs, mesh)),
                out_shardings=(to_named(pspecs, mesh), to_named(sspecs, mesh),
                               None),
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(aparams, astate, abatch)
        elif shape.kind == "prefill":
            step, model = build_prefill_step(cfg, mesh, opts=opts)
            aparams = abstract_params(model)
            pspecs = serve_shardings(model, mesh, fsdp=fsdp_axes)
            abatch = input_specs(cfg, shape)
            bspecs = divisibility_fix(batch_specs(abatch, dp), abatch, mesh)
            acaches = abstract_caches(model, shape)
            cspecs = divisibility_fix(
                cache_specs(acaches, dp), acaches, mesh
            )
            fn = jax.jit(
                step,
                in_shardings=(to_named(pspecs, mesh), to_named(bspecs, mesh)),
                out_shardings=(None, to_named(cspecs, mesh)),
            )
            lowered = fn.lower(aparams, abatch)
        else:  # decode
            step, model = build_decode_step(cfg, mesh, opts=opts)
            aparams = abstract_params(model)
            pspecs = serve_shardings(model, mesh, fsdp=fsdp_axes)
            inputs = input_specs(cfg, shape)
            acaches = abstract_caches(model, shape)
            cspecs = divisibility_fix(cache_specs(acaches, dp), acaches, mesh)
            ndp = 1
            for a in dp:
                ndp *= mesh.shape[a]
            tok_spec = P(dp, None) if shape.global_batch % ndp == 0 else P()
            tok_s = NamedSharding(mesh, tok_spec)
            pos_s = NamedSharding(mesh, P())
            fn = jax.jit(
                step,
                in_shardings=(to_named(pspecs, mesh), tok_s,
                              to_named(cspecs, mesh), pos_s),
                out_shardings=(None, to_named(cspecs, mesh)),
                donate_argnums=(2,),
            )
            lowered = fn.lower(aparams, inputs["tokens"], acaches, inputs["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = _memory_dict(compiled)
    cost = _cost_dict(compiled)
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    pod_size = 256
    # trip-count-aware per-device totals (cost_analysis() counts each
    # scanned layer once; parse_hlo_cost scales by known_trip_count)
    hc = parse_hlo_cost(hlo, pod_size=pod_size)
    roof = from_compiled(
        {"flops": hc.flops, "bytes accessed": hc.bytes_},
        hc.coll_total, hc.coll_dcn, chips, cfg, shape,
    )

    record.update(
        status="ok",
        chips=chips,
        fsdp=list(fsdp_axes),
        acc_dtype=acc_dtype,
        opts_override=opts_override or {},
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem,
        cost_analysis_raw={
            k: v for k, v in cost.items() if k in ("flops", "bytes accessed")
        },
        cost=hc.to_dict(),
        roofline=roof.to_dict(),
        hlo_bytes=len(hlo),
    )
    if verbose:
        print(f"== {arch_name} × {shape_name} × {mesh_kind} "
              f"({hierarchy}/{timing}/{compress}) ==")
        print(f"memory_analysis: {mem}")
        print(f"cost(trip-aware, per-device): flops={hc.flops:.3e} "
              f"bytes={hc.bytes_:.3e} coll={hc.coll_total:.3e} "
              f"dcn={hc.coll_dcn:.3e}")
        print(f"raw cost_analysis: {cost.get('flops', 0):.3e} flops")
        r = roof.to_dict()
        print(f"roofline: compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
              f"collective={r['collective_s']:.4f}s dominant={r['dominant']} "
              f"useful={r['useful_ratio']:.3f} frac={r['roofline_fraction']:.3f}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--hierarchy", choices=("hierarchical", "flat"),
                    default="hierarchical")
    ap.add_argument("--timing", choices=("eager", "lazy"), default="eager")
    ap.add_argument("--compress", choices=("none", "int8"), default="none")
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--fsdp", default="auto")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    rec = run_cell(
        args.arch, args.shape, args.mesh,
        hierarchy=args.hierarchy, timing=args.timing,
        compress=args.compress, micro=args.micro, fsdp=args.fsdp,
    )
    if args.out:
        outdir = Path(args.out)
        outdir.mkdir(parents=True, exist_ok=True)
        tag = (f"{args.arch}_{args.shape}_{args.mesh}_{args.hierarchy}"
               f"_{args.timing}_{args.compress}")
        (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
        print(f"wrote {outdir / (tag + '.json')}")


if __name__ == "__main__":
    main()
