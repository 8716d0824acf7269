"""Benchmark harness — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (scaffold contract).  ``--full``
uses paper-scale payloads (232 MB updates); default is a fast mode with
scaled payloads that preserves every ordering/ratio claim.

``--json PATH`` additionally writes the agg-kernel + dataplane rows
(the perf-trajectory benchmarks: fold GB/s old vs new) as a JSON list,
so future PRs have a baseline to regress against (see BENCH_agg.json).

  PYTHONPATH=src python -m benchmarks.run [--full] [--only name]
                                          [--json BENCH_agg.json]
"""
import argparse
import json
import os
import sys
import time

# suites whose rows land in the --json perf-trajectory file
JSON_SUITES = ("agg_kernel", "dataplane_fig7", "shmrt", "control_overhead",
               "net", "obs", "serve", "soak")

# PR-1 acceptance floor: blocked fold ≥ 2× naive.  A regression here
# silently rots every throughput claim downstream, so the harness fails
# loudly instead of recording the bad rows.
ENGINE_FOLD_FLOOR = 2.0

# Every gate check stamps its verdict into the row (``gates:
# {name: pass|fail}``) and RETURNS failure messages instead of exiting,
# so a failing run still writes the JSON rows — with the verdicts — and
# exits FATAL afterwards (main collects the messages).


def _stamp(r, gate: str, ok: bool) -> bool:
    r.setdefault("gates", {})[gate] = "pass" if ok else "fail"
    return ok


def _check_engine_fold_floor(rows) -> list:
    """Parse engine_fold_* speedups out of the agg_kernel rows and fail
    the run if the blocked/naive ratio fell below the PR-1 floor."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "agg_kernel" or "speedup_blocked" not in r["derived"]:
            continue
        m = re.search(r"speedup_blocked=([\d.]+)x", r["derived"])
        if m and not _stamp(r, "engine_fold_floor",
                            float(m.group(1)) >= ENGINE_FOLD_FLOOR):
            fails.append(
                f"FATAL: engine_fold regression — blocked/naive = "
                f"{m.group(1)}x < {ENGINE_FOLD_FLOOR}x floor "
                f"(row {r['case']!r}; see ROADMAP.md perf trajectory)")
    return fails


def _check_driver_dispatch_gate(rows) -> list:
    """PR-3 acceptance gate: one RoundDriver event dispatch must stay
    under 5% of a warm shmrt task dispatch (the event seam is free
    relative to the cheapest real control-plane action it mediates)."""
    import re

    fails = []
    for r in rows:
        if r["case"] != "driver_dispatch":
            continue
        m = re.search(r"overhead_frac=([\d.]+)", r["derived"])
        g = re.search(r"gate_frac=([\d.]+)", r["derived"])
        if m and g and not _stamp(r, "driver_dispatch",
                                  float(m.group(1)) < float(g.group(1))):
            fails.append(
                f"FATAL: driver dispatch overhead regression — "
                f"{float(m.group(1)):.4f} ≥ {g.group(1)} of warm shmrt "
                f"dispatch (row {r['case']!r}; see ROADMAP.md)")
    return fails


def _check_placement_gate(rows) -> list:
    """PR-10 acceptance gates: a from-scratch 10k-client placement must
    plan under its gate (trending toward the paper's 17 ms budget), a
    steady-state delta replan (plan cache + incremental PlacementState)
    under its own much tighter gate, and the deep fold tree must stay
    bit-identical to the flat plan with partials-only traffic."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "control_overhead":
            continue
        if r["case"] in ("placement_10k_clients",
                         "placement_10k_incremental"):
            m = re.search(r"\bms=([\d.]+);gate_ms=([\d.]+)", r["derived"])
            if m and not _stamp(r, "placement_budget",
                                float(m.group(1)) <= float(m.group(2))):
                fails.append(
                    f"FATAL: control-plane planning regression — "
                    f"{m.group(1)} ms > {m.group(2)} ms gate "
                    f"(row {r['case']!r}; see ROADMAP.md)")
        if r["case"] == "deep_fold_100node":
            b = re.search(r"bitexact=(\d)", r["derived"])
            if b and not _stamp(r, "deep_fold_bitexact", b.group(1) == "1"):
                fails.append(
                    "FATAL: deep fold tree is not bit-identical to the "
                    f"two-level plan (row {r['case']!r})")
            m = re.search(r"partial_mb=([\d.]+);bound_mb=([\d.]+)",
                          r["derived"])
            if m and not _stamp(r, "deep_fold_traffic",
                                float(m.group(1)) <= float(m.group(2))):
                fails.append(
                    f"FATAL: deep fold cross-node traffic "
                    f"{m.group(1)} MB/round > partials-only bound "
                    f"{m.group(2)} MB (row {r['case']!r})")
    return fails


def _check_net_traffic_gate(rows) -> list:
    """PR-4/PR-5 acceptance gates: cross-node aggregation traffic per
    round must stay partials-only — ≤ nodes × model_size × 1.1 (this
    bound now also covers daemon→daemon shipping) — and a node-top
    round must return ≤ 1 × model × 1.1 to the controller: more means
    partials are coming home instead of folding on the root node."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "net":
            continue
        m = re.search(r"partial_mb=([\d.]+);bound_mb=([\d.]+)", r["derived"])
        if m and not _stamp(r, "net_partials_only",
                            float(m.group(1)) <= float(m.group(2))):
            fails.append(
                f"FATAL: cross-node traffic regression — partial payloads "
                f"{m.group(1)} MB/round > partials-only bound "
                f"{m.group(2)} MB (row {r['case']!r}; see ROADMAP.md)")
        g = re.search(r"return_mb=([\d.]+);return_bound_mb=([\d.]+)",
                      r["derived"])
        if g and not _stamp(r, "net_return_traffic",
                            float(g.group(1)) <= float(g.group(2))):
            fails.append(
                f"FATAL: node-top return-traffic regression — "
                f"{g.group(1)} MB/round came back to the controller > "
                f"1 × model bound {g.group(2)} MB (row {r['case']!r}; "
                f"see ROADMAP.md)")
        b = re.search(r"bitexact=(\d)", r["derived"])
        if b and not _stamp(r, "net_bitexact", b.group(1) == "1"):
            fails.append(
                f"FATAL: cross-node round is not bit-identical to the "
                f"single-node tree (row {r['case']!r})")
    return fails


def _check_obs_overhead_gate(rows) -> list:
    """Tracing must be control-plane noise: a fully-traced warm shmproc
    round ≤ 2% over the untraced round (the obs layer's event-edge-only
    contract, paper §4.3)."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "obs" or "obs_overhead_frac" not in r["derived"]:
            continue
        m = re.search(r"obs_overhead_frac=([\d.]+)", r["derived"])
        g = re.search(r"gate_frac=([\d.]+)", r["derived"])
        if m and g and not _stamp(r, "obs_overhead",
                                  float(m.group(1)) < float(g.group(1))):
            fails.append(
                f"FATAL: tracing overhead regression — traced round is "
                f"{float(m.group(1)):.4f} over untraced ≥ {g.group(1)} "
                f"gate (row {r['case']!r}; see ROADMAP.md)")
    return fails


def _check_serve_gate(rows) -> list:
    """PR-8 acceptance gates: the continuous service must stay on the
    library's arithmetic — every rolling round bit-identical to its
    cohort replayed sequentially (``bitexact=1``) — and the rolling
    seam must actually overlap round windows (``pipeline_overlap > 0``;
    0 means rounds ran strictly sequentially and the second in-flight
    round never opened)."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "serve" or r["case"] != "rolling":
            continue
        b = re.search(r"bitexact=(\d)", r["derived"])
        if b and not _stamp(r, "serve_bitexact", b.group(1) == "1"):
            fails.append(
                "FATAL: rolling rounds drifted from the sequential "
                f"run_round path (row {r['case']!r}; see ROADMAP.md)")
        m = re.search(r"pipeline_overlap=([\d.]+)", r["derived"])
        if m and not _stamp(r, "serve_overlap", float(m.group(1)) > 0.0):
            fails.append(
                "FATAL: pipeline_overlap=0 — round N+1 never opened "
                f"during round N's fold (row {r['case']!r})")
    return fails


def _check_net_leak_gate(rows) -> list:
    """PR-8 hygiene gate: the recovery row's /dev/shm leak check —
    after SIGKILL + re-adoption + reap, zero ``lifl*`` segments may
    outlive the bench (``leaked_segs=0``)."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "net" or "leaked_segs" not in r["derived"]:
            continue
        m = re.search(r"leaked_segs=(\d+)", r["derived"])
        if m and not _stamp(r, "net_shm_leak", m.group(1) == "0"):
            fails.append(
                f"FATAL: /dev/shm leak — {m.group(1)} lifl segment(s) "
                f"survived daemon SIGKILL + reap (row {r['case']!r})")
    return fails


def _check_soak_gate(rows) -> list:
    """PR-9 acceptance gates: the rolling soak must hold the library's
    arithmetic over minutes of overlap (``soak_bitexact=1``) and the
    live scrape loop must stay invisible — total scrape wall under 2%
    of the soak's wall clock (``scrape_overhead_frac < 0.02``)."""
    import re

    fails = []
    for r in rows:
        if r["bench"] != "soak" or r["case"] != "fleet":
            continue
        b = re.search(r"soak_bitexact=(\d)", r["derived"])
        if b and not _stamp(r, "soak_bitexact", b.group(1) == "1"):
            fails.append(
                "FATAL: soak rounds drifted from the sequential "
                f"run_round path (row {r['case']!r}; see ROADMAP.md)")
        m = re.search(r"scrape_overhead_frac=([\d.]+)", r["derived"])
        if m and not _stamp(r, "soak_scrape_overhead",
                            float(m.group(1)) < 0.02):
            fails.append(
                f"FATAL: live-scrape overhead regression — "
                f"{m.group(1)} of soak wall ≥ 0.02 gate "
                f"(row {r['case']!r}; see ROADMAP.md)")
    return fails


def _print_gate_table(rows) -> None:
    """One verdict line per stamped gate, after all suites ran — the
    at-a-glance answer to 'which acceptance bars did this run clear'."""
    stamped = [(r["bench"], r["case"], g, v)
               for r in rows for g, v in r.get("gates", {}).items()]
    if not stamped:
        return
    print("# gate verdicts:", file=sys.stderr)
    w = max(len(f"{b}/{c}") for b, c, _g, _v in stamped)
    for b, c, g, v in stamped:
        print(f"#   {f'{b}/{c}':<{w}}  {g:<22} {v.upper()}",
              file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="CI profile: trims the long-soak/net iteration "
                         "counts (suites taking minutes drop to seconds) "
                         "while still stamping and printing every gate "
                         "verdict; do NOT regenerate BENCH_agg.json in "
                         "this mode")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write agg-kernel + dataplane rows to PATH as JSON")
    args = ap.parse_args()
    if args.full and args.fast:
        ap.error("--full and --fast are mutually exclusive")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    fast = not args.full
    if args.json:  # fail on an unwritable path now, not after the run —
        # without creating an empty file a no-row run would leave behind
        target = args.json if os.path.exists(args.json) else (
            os.path.dirname(os.path.abspath(args.json)))
        if not os.access(target, os.W_OK):
            ap.error(f"--json path not writable: {args.json}")

    from benchmarks import (
        bench_agg_kernel,
        bench_control_overhead,
        bench_dataplane,
        bench_hierarchy,
        bench_net,
        bench_obs,
        bench_orchestration,
        bench_queuing,
        bench_serve,
        bench_shmrt,
        bench_soak,
        bench_tta,
    )

    suites = {
        "dataplane_fig7": bench_dataplane.run,
        "queuing_fig13": bench_queuing.run,
        "hierarchy_fig4": bench_hierarchy.run,
        "orchestration_fig8": bench_orchestration.run,
        "control_overhead": bench_control_overhead.run,
        "agg_kernel": bench_agg_kernel.run,
        "shmrt": bench_shmrt.run,
        "net": bench_net.run,
        "obs": bench_obs.run,
        "serve": bench_serve.run,
        "soak": bench_soak.run,
        "tta_fig9": bench_tta.run,
    }
    if args.only:
        suites = {k: v for k, v in suites.items() if args.only in k}

    gate_checks = {
        "agg_kernel": _check_engine_fold_floor,
        "control_overhead": lambda rows: (_check_driver_dispatch_gate(rows)
                                          + _check_placement_gate(rows)),
        "net": lambda rows: (_check_net_traffic_gate(rows)
                             + _check_net_leak_gate(rows)),
        "obs": _check_obs_overhead_gate,
        "serve": _check_serve_gate,
        "soak": _check_soak_gate,
    }
    all_rows: list = []
    json_rows = []
    fatal: list = []
    print("name,us_per_call,derived")
    import inspect

    for name, fn in suites.items():
        t0 = time.time()
        kwargs = {"fast": fast}
        if args.fast and "profile" in inspect.signature(fn).parameters:
            kwargs["profile"] = "ci"   # suites that support extra trimming
        try:
            rows = fn(**kwargs)
        except Exception as e:  # a failed suite must not hide the others
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            continue
        check = gate_checks.get(name)
        if check is not None:
            fatal.extend(check(rows))
        for r in rows:
            print(f"{r['bench']}/{r['case']},{r['us_per_call']:.1f},"
                  f"{r['derived']}", flush=True)
        all_rows.extend(rows)
        if name in JSON_SUITES:
            json_rows.extend(rows)
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)

    _print_gate_table(all_rows)

    if args.json:
        if json_rows:
            # every row carries its gate verdicts (possibly empty) so
            # the baseline records what was checked, not just measured
            for r in json_rows:
                r.setdefault("gates", {})
            with open(args.json, "w") as f:
                json.dump({"mode": ("full" if args.full
                                    else "ci" if args.fast else "fast"),
                           "rows": json_rows}, f, indent=2)
            print(f"# wrote {len(json_rows)} rows to {args.json}",
                  file=sys.stderr)
        else:
            # never clobber an existing perf baseline with an empty run
            # (e.g. --only filtered out both JSON suites)
            print(f"# no {'/'.join(JSON_SUITES)} rows produced; "
                  f"left {args.json} untouched", file=sys.stderr)

    if fatal:
        # verdicts are stamped and the JSON is on disk — NOW fail loudly
        sys.exit("\n".join(fatal))


if __name__ == "__main__":
    main()
