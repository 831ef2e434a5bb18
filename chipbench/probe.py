"""Measurements for defining a cell, on the chip, in one process (not part
of a benchmark run):

  --mode readings  per seed, the program's widest logit gap and the fp8
                   control's on the same sample: the two readings a cell's
                   ``max_logit_gap`` limit is set between;
  --mode sweep     per offered rate, the end-to-end metrics, calls left
                   unfinished at the drain cap and the waiting queue at window
                   open and close: the knee is the highest rate with none
                   unfinished and no queue growth.

  python3 chipbench/probe.py --workload <cell> --mode readings --seeds 1,2,3 --seconds 10
  python3 chipbench/probe.py --workload <cell> --mode sweep --rates 4,6,8 --seconds 15 \
      [--arrivals poisson]

Each line printed is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Readings and rate sweeps for a chipbench cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("readings", "sweep"), required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--arrivals", choices=("poisson",), default=None,
                    help="sweep with steady Poisson arrivals in place of the mix's own")
    args = ap.parse_args(argv)

    import check
    import harness
    import spec
    harness.configure_jax()
    device = harness.require_chip(1)
    cell = spec.cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [cell["rate"]]
    cs = cell["config_spec"]
    if args.arrivals:
        cell["traffic_spec"] = dict(cell["traffic_spec"], arrivals=args.arrivals)
    for rate in rates if args.mode == "sweep" else [cell["rate"]]:
        for seed in seeds:
            c = dict(cell, rate=rate)
            t = time.perf_counter()
            w, setup_s, served, peak = harness.serve(c, seed, args.seconds, None, t)
            out = {"workload": args.workload, "seed": seed, "rate": rate,
                   "device": device["kind"], "peak": peak,
                   "e2e": harness.end_to_end(w, setup_s),
                   "attempted": len(harness.in_window(w)),
                   "unfinished": sum(1 for r in harness.in_window(w) if not r.done),
                   "queue": [w.queue_open, w.queue_close], "compiles": w.compiles,
                   "drain_s": w.t_end - w.t_close,
                   "counters": [w.counters_open, w.counters_close]}
            if args.mode == "readings":
                pick = check.sample(served, cell["check"]["requests"], seed)
                pairs = [served[i] for i in pick]
                t = time.perf_counter()
                ref = check.Reference(spec.reference(cs["reference"]), cs["model"],
                                      harness.make_params(cell, seed))
                out["program_gap"] = check.widest_gap(ref, pairs, cell["max_seq"])
                t_ref = time.perf_counter() - t
                out["control_gap"] = check.widest_gap(ref, pairs, cell["max_seq"], control=True)
                out["tokens"] = sum(len(p[1]) for p in pairs)
                out["reference_s"] = t_ref
                del ref
                gc.collect()
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
