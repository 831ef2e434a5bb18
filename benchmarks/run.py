"""Benchmark driver: one function per paper table/figure + the multi-tenant
and routing scenario grids + the roofline summary. Prints
``name,us_per_call,derived`` CSV (stdout) and writes detail JSON to
results/bench_details.json.

Usage: PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only NAME]
                                               [--list]
  --full  : paper-length experiments (24 h days, 200-iter fig7) instead of
            the default reduced durations.
  --smoke : a few sim-minutes per bench — a CI-speed check that every bench
            entry still executes end to end.
  --list  : print the available bench names and exit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HOUR = 3600.0


def bench_roofline_summary():
    """Summarize the dry-run roofline table (results/dryrun_*.json)."""
    rows, detail = [], {}
    for tag, path in (("baseline", "results/dryrun_baseline.json"),
                      ("optimized", "results/dryrun_optimized.json")):
        if not os.path.exists(path):
            continue
        with open(path) as f:
            recs = json.load(f)
        # roofline terms are only meaningful for PROBED single-pod records
        # (multi-pod passes are compile proofs without depth probes)
        ok = [r for r in recs if r.get("status") == "ok"
              and r.get("mesh") == "single" and "probe_compile_s" in r]
        if not ok:
            continue
        fracs = [r["roofline"]["roofline_fraction"] for r in ok]
        bns = {}
        for r in ok:
            bns[r["roofline"]["bottleneck"]] = bns.get(r["roofline"]["bottleneck"], 0) + 1
        bns_s = "/".join(f"{k}:{v}" for k, v in sorted(bns.items()))
        rows.append((f"roofline_{tag}", 0.0,
                     f"cells={len(ok)};median_frac={sorted(fracs)[len(fracs)//2]:.4f};"
                     f"best_frac={max(fracs):.4f};bottlenecks={bns_s}"))
        detail[tag] = {"n_ok": len(ok),
                       "fracs": {f"{r['arch']}/{r['shape']}/{r['mesh']}":
                                 r["roofline"]["roofline_fraction"] for r in ok}}
    return rows, {"roofline": detail}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="a few sim-minutes per bench (CI execution check)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true",
                    help="print available bench names and exit")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks import elastic_serving as ES
    from benchmarks import multi_tenant as MT
    from benchmarks import paged_kv as PK
    from benchmarks import paper_benches as PB
    from benchmarks import reliability as RL
    from benchmarks import routing as RT
    from benchmarks import serving_matrix as SM

    if args.smoke:
        day = resp = grid = 5 * 60.0
        fig7_iters = 3
    elif args.full:
        day, resp, grid, fig7_iters = 24 * HOUR, 24 * HOUR, 6 * HOUR, 200
    else:
        day, resp, grid, fig7_iters = 6 * HOUR, 2 * HOUR, 2 * HOUR, 50
    benches = {
        "fig1": lambda: PB.bench_fig1_trace(),
        "table1": lambda: PB.bench_table1(),
        "table2": lambda: PB.bench_table2_fib(day),
        "table3": lambda: PB.bench_table3_var(day),
        "fig5": lambda: PB.bench_fig5_responsiveness(resp),
        "fig7": lambda: PB.bench_fig7_single_invocation(fig7_iters),
        "multitenant": lambda: MT.bench_multi_tenant(grid),
        "routing": lambda: RT.bench_routing(grid),
        "reliability": lambda: RL.bench_reliability(grid),
        # the storm needs a few window generations before the goodput gap
        # stabilises; never run the grid shorter than 30 sim-minutes
        "elastic": lambda: ES.bench_elastic(max(grid, 30 * 60.0)),
        "paged_kv": lambda: PK.bench_paged_kv(
            n_requests=12 if args.smoke else 24,
            kernel_requests=4 if args.smoke else 6),
        "serving_matrix": lambda: SM.bench_serving_matrix(
            archs=SM.SMOKE_ARCHS if args.smoke else None,
            slots_grid=(2,) if args.smoke else (2, 4)),
        "roofline": bench_roofline_summary,
    }
    if args.list:
        print("\n".join(benches))
        return
    if args.only:
        if args.only not in benches:
            sys.stderr.write(f"unknown bench {args.only!r}; available: "
                             f"{', '.join(benches)}\n")
            sys.exit(2)
        benches = {args.only: benches[args.only]}

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    all_detail = {}
    n_errors = 0
    print("name,us_per_call,derived")
    for key, fn in benches.items():
        t0 = time.time()
        try:
            rows, detail = fn()
        except Exception as e:  # keep the harness running
            print(f"{key},0,ERROR:{type(e).__name__}:{e}")
            n_errors += 1
            continue
        all_detail.update(detail)
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        sys.stderr.write(f"[{key}: {time.time()-t0:.1f}s]\n")
    os.makedirs("results", exist_ok=True)
    with open("results/bench_details.json", "w") as f:
        json.dump(all_detail, f, indent=1, default=str)
    if n_errors:
        sys.exit(1)


if __name__ == "__main__":
    main()
