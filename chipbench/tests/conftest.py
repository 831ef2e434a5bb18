"""chipbench's own tests: ``python -m pytest chipbench/tests`` on the CPU.
They import the benchmark's modules the way ``run.py`` does."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
DATA = HERE / "data"


@pytest.fixture(autouse=True)
def smoke_timing(monkeypatch):
    """A short ramp and traced span: smoke-size calls last a fraction of a
    second on the CPU, where the chip's constants would only add waiting."""
    import harness
    monkeypatch.setattr(harness, "RAMP_S", 0.5)
    monkeypatch.setattr(harness, "TRACE_S", 1.0)
