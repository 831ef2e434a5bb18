"""Record the small chip trace with the engine's own spans that
test_engine_spans.py reads. Run on a TPU from the checkout root:
``python3 chipbench/tests/record_spans_trace.py``. The batch-gen cell is
served for a window that opens after the first callers have finished, so
admissions mix with the decode waves; the window's last ``TRACE_S`` seconds
are traced and written gzipped to tests/data/traces/<cell>.spans.xplane.pb.gz."""
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

CELL = "qwen2.5-3b.batch-gen"
RAMP_S = 6.0     # callers' first calls (256-512 tokens) start finishing after ~5 s
WINDOW_S = 1.0
TRACE_S = 0.4
OUT = HERE / "data" / "traces"


def main():
    harness.configure_jax()
    harness.require_chip(1)
    harness.RAMP_S, harness.TRACE_S = RAMP_S, TRACE_S
    trace_dir = harness.TRACE_ROOT / f"fixture-spans-{CELL}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    w, *_ = harness.serve(spec.cell(CELL), 1, WINDOW_S, trace_dir, time.perf_counter())
    src = trace_reduce.find_xplane(trace_dir)
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{CELL}.spans.xplane.pb.gz").write_bytes(gzip.compress(src.read_bytes()))
    print(CELL, "traced span", w.trace, "bytes", src.stat().st_size, flush=True)


if __name__ == "__main__":
    main()
