"""One run of one cell: set up, measure a window, check the output.

The system under test is ``repro.serve.AggregationService`` on the
``inproc`` runtime with ``agg_engine="auto"`` (the Pallas ``JaxEngine``
on a TPU).  Updates are host arrays handed to ``AggregationService.
submit`` by the traffic's pusher thread; rounds roll through the
service's ``RoundScheduler`` with up to two open; each round folds on
the chip and is published by the trainer's server step.

Timeline of a run::

    set-up: device check, params on the device, update pool, service,
            ``warm_rounds`` rounds at the cell's own shapes, fed from a
            queue filled up front
    window: the traffic's pusher starts at the close of the last warm
            round; the window runs from there to the first round
            close at least ``seconds`` later; every round published in
            between counts, whole
    after:  the rounds still open close and are not counted; the chip's
            peak memory is read, the service is closed, and a sample of
            the published parameters is compared with the reference
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import reference, spec, stats
from chipbench.traffic import Pusher, Submission

JOB = "fl"
EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed32(seed: int) -> int:
    """A 32-bit key for JAX and the program from any whole-number seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


class CompileWatch:
    """Backend compile seconds, compiles and persistent-cache hits, from
    JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compile_s, self.compiles, self.cache_hits


@dataclass
class Ctx:
    """What the per-layer readers read (``metrics/<name>.py``)."""

    n: int                              # update elements
    update_dtype: str
    rounds: List[Dict[str, Any]]        # published inside the window
    window_s: float
    t0: float
    t_last: float
    sent: Dict[str, Any]                # client id -> traffic.Sent
    updates_per_s: float
    device_kind: str
    trace: Any = None                   # devtrace.Reduced, traced runs


def _device_check(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_chip and (d0.platform != "tpu" or len(devs) < chips):
        log(f"no accelerator for this cell: JAX sees {len(devs)} "
            f"{d0.platform} device(s), the cell needs {chips} TPU chip(s)")
        return None
    return devs[:chips]


def _enable_cache(cache_dir: Path) -> None:
    import jax

    cache_dir.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def with_overrides(base, overrides: Dict[str, Any], where: str):
    """``base`` (a frozen dataclass of the program's registry) with the
    configuration's ``overrides``: top-level fields replaced, a nested
    dataclass (``moe``, ``mla``, ``ssm``) overridden field by field, a
    JSON list taken as a tuple.  A field ``base`` lacks is an error."""
    import dataclasses

    fields = {f.name for f in dataclasses.fields(base)}
    changes = {}
    for key, value in overrides.items():
        if key not in fields:
            raise ValueError(f"{where}: unknown override field {key!r} "
                             f"(not a field of {type(base).__name__})")
        if isinstance(value, dict):
            inner = getattr(base, key)
            if not dataclasses.is_dataclass(inner):
                raise ValueError(f"{where}: override field {key!r} is an "
                                 f"object, but {type(base).__name__}."
                                 f"{key} is {inner!r}")
            value = with_overrides(inner, value, f"{where}.{key}")
        elif isinstance(value, list):
            value = tuple(value)
        changes[key] = value
    return dataclasses.replace(base, **changes)


def _program_model(cfg: Dict[str, Any]):
    """``architecture: "resnet"`` builds a ResNet from the file's own
    keys; any other value is an id of ``repro.configs.ARCHS``, built by
    ``repro.models.build_model`` with the file's ``overrides``."""
    if cfg["architecture"] == "resnet":
        from repro.configs.resnet import ResNetConfig
        from repro.models import build_resnet

        return build_resnet(ResNetConfig(
            name=cfg["name"], block=cfg["block"],
            stage_blocks=tuple(cfg["stage_blocks"]),
            width=int(cfg["width"]), num_classes=int(cfg["num_classes"]),
            in_channels=int(cfg["in_channels"]),
            image_size=int(cfg["image_size"])))
    from repro.configs import get_arch
    from repro.models import build_model as build_arch

    arch = with_overrides(get_arch(cfg["architecture"]),
                          cfg.get("overrides", {}), cfg["name"])
    return build_arch(arch)


def build_model(cfg: Dict[str, Any]):
    """The configuration's model through the program's own builder;
    its leaf count and size must be the configuration's.  The update
    pool is f32, so only ``update_dtype: "float32"`` is taken."""
    import jax

    if cfg.get("update_dtype") != "float32":
        raise ValueError(f"{cfg['name']}: update_dtype "
                         f"{cfg.get('update_dtype')!r} is not taken: the "
                         f"harness submits float32 updates only")
    model = _program_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    n = int(sum(int(np.prod(s.shape)) for s in leaves))
    if n != int(cfg["n_params"]) or len(leaves) != int(cfg["n_leaves"]):
        raise ValueError(f"{cfg['name']}: model has {n} parameters in "
                         f"{len(leaves)} leaves, the configuration says "
                         f"{cfg['n_params']} in {cfg['n_leaves']}")
    return model, shapes, n


def _init_leaves(key, treedef, shapes, scale):
    import jax

    ks = jax.random.split(key, len(shapes))
    out = [((jax.random.uniform(k, shape, dtype) - 0.5)
            * (2.0 * scale)).astype(dtype)
           for k, (shape, dtype) in zip(ks, shapes)]
    return jax.tree.unflatten(treedef, out)


def make_params(shapes, key32: int, scale: float):
    """Initial parameters on the device, in one jitted call from the
    seed: uniform in [-scale, scale), in each leaf's own dtype."""
    import jax

    leaves, treedef = jax.tree.flatten(shapes)
    init = jax.jit(_init_leaves, static_argnums=(1, 2, 3))
    return init(jax.random.PRNGKey(key32), treedef,
                tuple((tuple(s.shape), str(s.dtype)) for s in leaves),
                float(scale))


def flat_host(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(x, np.float32).reshape(-1)
                           for x in jax.device_get(jax.tree.leaves(tree))])


def make_pool(n: int, params: Dict[str, Any], seed: int):
    """The update pool: row j is a window of one seeded f32 buffer,
    ``base[j*stride : j*stride + n]``, uniform in [-scale, scale).
    Read-only, so nothing downstream can alter what the reference
    reads."""
    m, stride = int(params["pool_updates"]), int(params["pool_stride"])
    scale = float(params["update_scale"])
    rng = np.random.default_rng([int(seed), 1])
    base = rng.random(n + (m - 1) * stride, dtype=np.float32)
    base -= np.float32(0.5)
    base *= np.float32(2.0 * scale)
    base.flags.writeable = False
    return base, reference.pool_rows(base, n, stride, m)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = spec.ROOT, require_chip: bool = True,
        cache_dir: Optional[Path] = None, control: bool = False,
        trace_dir: Optional[Path] = None,
        t_start: Optional[float] = None):
    """-> (exit code, result dict or None)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(cell_name, root)
    p, cfg, traffic = cell.params, cell.config, cell.traffic

    import jax

    devs = _device_check(cell.chips, require_chip)
    if devs is None:
        return EXIT_NO_CHIP, None
    bench_dir = root / spec.REL
    if cache_dir is not None:
        _enable_cache(cache_dir)
    watch = CompileWatch()

    from repro.core import ClientInfo, NodeState, RoundConfig
    from repro.core.engine import JaxEngine
    from repro.serve import (AdmissionPolicy, AggregationService,
                             GoalPolicy, RoundScheduler)

    key32 = seed32(seed)
    t_dev = time.perf_counter()
    model, shapes, n = build_model(cfg)
    params0 = make_params(shapes, key32, float(p["param_scale"]))
    p0 = flat_host(params0)
    t_params = time.perf_counter()
    base, rows = make_pool(n, p, seed)
    t_pool = time.perf_counter()
    log(f"[setup] {cfg['name']}: {n} f32 parameters "
        f"({4 * n / 2**20:.1f} MiB per update); start to chip "
        f"{t_dev - t_start:.3f}s, params {t_params - t_dev:.3f}s, pool of "
        f"{len(rows)} rows {t_pool - t_params:.3f}s")

    goal, quota = int(p["goal"]), int(p["queue_quota"])
    nodes = {f"node{i}": NodeState(node=f"node{i}",
                                   max_capacity=float(p["node_capacity"]))
             for i in range(int(p["nodes"]))}
    svc = AggregationService(
        nodes, runtime="inproc", agg_engine="auto",
        admission=AdmissionPolicy(max_queue=quota, job_quota=quota),
        seed=key32)
    roster = [ClientInfo(client_id=f"slot{i}", num_samples=1)
              for i in range(goal)]
    tr = svc.add_job(
        JOB, model, params0, roster,
        round_cfg=RoundConfig(aggregation_goal=goal, over_provision=1.0,
                              placement_policy=p["placement_policy"],
                              topology="controller"),
        server_opt=cfg["server_opt"], server_lr=float(cfg["server_lr"]),
        seed=key32)
    policy = GoalPolicy() if traffic["close_out"] == "goal" else None

    def submit(sub: Submission):
        with jax.profiler.TraceAnnotation("bench.submit"):
            return svc.submit(JOB, sub.client_id, rows[sub.pool],
                              weight=sub.weight,
                              submission_id=str(sub.seq))

    pusher = Pusher(traffic, p, seed, submit, lambda: svc.gateway.depth(JOB))
    warm = int(p["warm_rounds"])
    if goal * warm > quota:
        raise ValueError(f"{cell_name}: {warm} warm rounds of {goal} do not "
                         f"fit the queue quota {quota}")
    sample_k = int(p["check_rounds"])
    sample_rng = np.random.default_rng([int(seed), 2])
    closed: List[Dict[str, Any]] = []
    kept: Dict[int, Any] = {}            # window ordinal -> params
    st: Dict[str, Any] = {"t0": None, "t_last": None, "closing": False,
                          "in_window": 0, "compile0": None, "span": None}
    trace_path = trace_dir
    if trace and trace_dir is None:
        trace_path = bench_dir / ".trace" / cell_name
        shutil.rmtree(trace_path, ignore_errors=True)

    def open_next():
        if st["closing"]:
            return None
        return svc.open_round(JOB, policy=policy)

    def on_open(rnd):
        rnd.serve_record["t_open"] = time.perf_counter()

    def start_window():
        if trace:
            jax.profiler.start_trace(str(trace_path))
            st["span"] = jax.profiler.TraceAnnotation("bench.window")
            st["span"].__enter__()
        st["compile0"] = watch.snapshot()
        st["t0"] = time.perf_counter()
        pusher.start()

    def on_close(rnd):
        with jax.profiler.TraceAnnotation("bench.publish_wait"):
            jax.block_until_ready(tr.params)
        t = time.perf_counter()
        rec = rnd.serve_record
        rec["t_close"] = t
        rec["outcome"] = rnd.handle.outcome
        rec["trace"] = tr.trace(rec["ticket"])
        closed.append(rec)
        if st["t0"] is None:
            c = watch.snapshot()
            log(f"[setup] warm round {len(closed)}: "
                f"{int(rec['outcome'].accepted)} updates, closed at "
                f"{t - t_start:.3f}s; compiles so far {c[1]} ({c[0]:.3f}s), "
                f"persistent-cache hits {c[2]}")
            if len(closed) == warm:
                start_window()
            return
        if st["t_last"] is not None:
            return
        rec["in_window"] = True
        i = st["in_window"]
        st["in_window"] += 1
        # reservoir sample of the window's published params, seeded
        if len(kept) < sample_k:
            kept[i] = tr.params
        else:
            j = int(sample_rng.integers(0, i + 1))
            if j < sample_k:
                kept.pop(sorted(kept)[j])
                kept[i] = tr.params
        if t - st["t0"] >= seconds:
            st["t_last"] = t
            st["closing"] = True
            st["compile1"] = watch.snapshot()
            kept[i] = tr.params          # the window's last round
            if trace:
                st["span"].__exit__(None, None, None)
                jax.profiler.stop_trace()

    # the warm rounds' cohorts are queued up front and the pusher starts
    # with the window, so a slow (compiling) warm-up leaves no backlog
    # behind for the window to drain
    pusher.prime(goal * warm)
    try:
        sched = RoundScheduler(open_next, max_open=svc.driver.max_open_rounds,
                               on_open=on_open, on_close=on_close)
        sched.run()
    finally:
        pusher.stop()
    if pusher.error is not None:
        raise pusher.error
    if require_chip:
        engines = list(svc.runtime._engines.values())
        bad = [e for e in engines
               if not (isinstance(e, JaxEngine) and e.impl == "pallas")]
        if not engines or bad:
            raise RuntimeError(f"agg_engine='auto' did not resolve to the "
                               f"Pallas JaxEngine: {engines}")

    t0, t_last = st["t0"], st["t_last"]
    window = [r for r in closed if r.get("in_window")]
    window_s = t_last - t0
    folded = sum(int(r["outcome"].accepted) for r in window)
    c0, c1 = st["compile0"], st["compile1"]
    inside = [s for s in pusher.sent.values() if t0 <= s.due <= t_last]
    late = max(inside, key=lambda s: s.late_s, default=None)
    slow = max(inside, key=lambda s: s.admit_s, default=None)
    log(f"[window] {len(window)} rounds, {folded} updates in "
        f"{window_s:.3f}s; compiles inside: {c1[1] - c0[1]} "
        f"({c1[0] - c0[0]:.3f}s), cache loads inside: {c1[2] - c0[2]}; "
        f"set-up compile {c0[0]:.3f}s over {c0[1]} programs, "
        f"persistent-cache hits {c0[2]}")
    if late is not None:
        log(f"[window] generator at most {late.late_s * 1e3:.3f} ms late "
            f"(due at +{late.due - t0:.3f}s); slowest submit "
            f"{slow.admit_s * 1e3:.3f} ms (at +{slow.t_admit - t0:.3f}s)")

    lat = publish_latencies(window, pusher.sent)
    if lat:
        half = len(lat) // 2
        log(f"[window] publish latency mean {np.mean(lat):.4f}s "
            f"(first half {np.mean(lat[:half] or lat):.4f}s, second half "
            f"{np.mean(lat[half:]):.4f}s), {len(lat)} updates; gateway "
            f"queue at the close {svc.gateway.depth(JOB)}; "
            f"shed {sum(s.shed for s in pusher.sent.values())}")

    kind = devs[0].device_kind
    mem = [d.memory_stats() or {} for d in devs]
    peak_mem = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    ctx = Ctx(n=n, update_dtype=cfg["update_dtype"],
              rounds=window, window_s=window_s, t0=t0, t_last=t_last,
              sent=dict(pusher.sent), updates_per_s=folded / window_s,
              device_kind=kind)
    device: Dict[str, Any] = {"platform": devs[0].platform, "kind": kind,
                              "count": len(devs),
                              "memory_peak_bytes": peak_mem}
    result: Dict[str, Any] = {}
    if trace:
        from chipbench import devtrace

        t_red = time.perf_counter()
        xplane = devtrace.find_xplane(str(trace_path))
        red = devtrace.reduce(devtrace.load(xplane))
        log(f"[trace] {os.path.getsize(xplane) / 2**20:.1f} MiB, reduced "
            f"in {time.perf_counter() - t_red:.3f}s; device busy "
            f"{red.busy_s:.6f}s of {red.window_s:.6f}s")
        if trace_dir is None:
            shutil.rmtree(trace_path, ignore_errors=True)
        ctx.trace = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m.name, bench_dir)(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
    else:
        e2e = {"updates_per_s": ctx.updates_per_s, "setup_s": t0 - t_start}
        if any(m.name == "publish_p95_s" for m in cell.end_to_end):
            e2e["publish_p95_s"] = stats.quantile(
                publish_latencies(window, ctx.sent), 0.95)
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end}

    # --- the check: after the window, with the program's state freed
    checked = {k: flat_host(v) for k, v in kept.items()}
    kept.clear()
    unfolded, coefs = _replay(closed, ctx.sent, window, checked,
                              len(rows), float(cfg["server_lr"]))
    svc.close()
    del svc, tr, params0, sched
    gc.collect()
    t_ref = time.perf_counter()
    res = reference.gaps(p0, rows, checked, coefs, control=control)
    gap = max((v["program"] for v in res.values()), default=float("inf"))
    limit = float(p["param_gap_limit"])
    checks = {"param_gap": [gap, limit], "unfolded": [unfolded, 0],
              "unchecked_rounds": [0 if checked else 1, 0]}
    if control:
        ctl = max(v["control"] for v in res.values())
        checks["control_param_gap"] = [ctl, limit]
        log(f"[control] bf16-rounded updates: param_gap {ctl!r}")
    correct = all(v <= lim for v, lim in checks.values())
    log(f"[check] {len(checked)} published rounds against the float64 "
        f"reference in {time.perf_counter() - t_ref:.3f}s")
    for name, (v, lim) in checks.items():
        log(f"[check] {name} = {v!r} limit {lim!r}")
    attempted = sum(len(r["cohort"]) for r in window)
    result.update({
        "correct": bool(correct), "attempted": attempted,
        "failed": unfolded_in(window), "metrics": metrics,
        "device": device,
        "checks": {k: {"value": v, "limit": lim}
                   for k, (v, lim) in checks.items()}})
    # "checks" comes last in the line
    ordered = {k: result[k] for k in result if k != "checks"}
    ordered["checks"] = result["checks"]
    return 0, ordered


def publish_latencies(window, sent) -> List[float]:
    """Publish time minus due time of every update published in the
    window."""
    out = []
    for rec in window:
        skipped = {cid for _n, cid, _f, _w in rec["outcome"].skipped}
        for _node, cid, _w in rec["cohort"]:
            if cid not in skipped:
                out.append(rec["t_close"] - sent[cid].due)
    return out


def unfolded_in(window) -> int:
    return sum(len(r["cohort"]) - int(r["outcome"].accepted)
               for r in window)


def _replay(closed, sent, window, checked, pool: int, server_lr: float):
    """Pool coefficients of every published round in close order, from
    the cohorts the service logged and the weights the pusher sent.
    -> (updates whose logged weight differs from the sent one or that
    were pulled and never folded, {checked key: coefficients})."""
    chain = reference.Chain(pool, server_lr)
    coefs: Dict[Any, np.ndarray] = {}
    index = {id(r): i for i, r in enumerate(window)}
    bad = 0
    for rec in closed:
        skipped = {cid for _n, cid, _f, _w in rec["outcome"].skipped}
        cohort = []
        for _node, cid, w in rec["cohort"]:
            s = sent[cid].sub
            if w != s.weight:
                bad += 1
            if cid not in skipped:
                cohort.append((s.pool, s.weight))
        if len(cohort) != int(rec["outcome"].accepted):
            bad += abs(len(cohort) - int(rec["outcome"].accepted))
        c = chain.publish(cohort)
        i = index.get(id(rec))
        if i is not None and i in checked:
            coefs[i] = c
    missing = [k for k in checked if k not in coefs]
    if missing:
        raise RuntimeError(f"checked rounds without a record: {missing}")
    return bad, coefs


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the bfloat16 control (not part of a "
                         "benchmark run)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here")
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    args = parse(argv)
    rc, result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), control=bool(args.control),
                     cache_dir=spec.BENCH_DIR / ".jax_cache",
                     trace_dir=(Path(args.trace_dir) if args.trace_dir
                                else None),
                     t_start=t_start)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc
