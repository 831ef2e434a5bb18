"""Record the small chip traces that test_trace.py reduces. Run on a TPU
from the checkout root: ``python3 chipbench/tests/record_trace.py [cell ...]``. Each
cell below is served for a short window whose first ``TRACE_S`` seconds are
traced (``CELLS``); the trace is written gzipped to tests/data/traces/<cell>.xplane.pb.gz."""
import gzip
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent / "src")]

import harness  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402

# cell -> traced seconds: long enough to hold an admission (prefill, graft)
CELLS = {"qwen2.5-3b.doc-faas": 0.4, "mamba2-2.7b.chat-burst": 0.8}
OUT = HERE / "data" / "traces"


def main(names):
    harness.configure_jax()
    harness.require_chip(1)
    OUT.mkdir(parents=True, exist_ok=True)
    for name in names:
        cell = spec.cell(name)
        harness.TRACE_S = CELLS[name]
        trace_dir = harness.TRACE_ROOT / f"fixture-{name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        w, *_ = harness.serve(cell, 1, 1.0, trace_dir, time.perf_counter())
        src = trace_reduce.find_xplane(trace_dir)
        (OUT / f"{name}.xplane.pb.gz").write_bytes(gzip.compress(src.read_bytes()))
        print(name, "traced span", w.trace, "bytes", src.stat().st_size, flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(CELLS))
