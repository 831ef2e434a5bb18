"""Run one benchmark cell once on the chip and print its result line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. One process, no children. It exits non-zero,
printing no result, off a TPU, with interpreted Pallas kernels, with fewer
chips than the cell asks for, or, before set-up, for a gang cell whose gang
or events break ``spec.cell``'s rules. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``check``: each
number compared with its limit, which also close standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one chipbench cell once.")
    ap.add_argument("--workload", required=True, help="cell name (workloads/<name>.json)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    import spec
    bench = spec.benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"chipbench: unknown workload {args.workload!r}; "
                         f"known: {sorted(cells)}")
    harness.configure_jax()
    device = harness.require_chip(cells[args.workload]["chips"])
    try:
        result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                             T_START, device)
    except spec.CellError as e:
        raise SystemExit(f"chipbench: {e}")
    for name, n in result["check"].items():
        print(f"check {name} {n['value']!r} limit {n['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
