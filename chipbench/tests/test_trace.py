"""The trace reduction on small traces recorded on a TPU v5e by
record_trace.py (a 0.4 s span of each committed cell)."""
import gzip
import re

import pytest

import programs
import trace_reduce
from conftest import DATA

TRACES = {"qwen2.5-3b.doc-faas": "flash_attention", "mamba2-2.7b.chat-burst": "ssd"}


def _profile(cell):
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / "traces" / f"{cell}.xplane.pb.gz").read_bytes())
    return ProfileData.from_serialized_xspace(raw)


def _sweep_busy(profile):
    """Busy time by a sweep over interval endpoints (the reduction merges
    sorted intervals instead), clipped to the window span."""
    lo = hi = None
    for plane in profile.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        lo, hi = int(ev.start_ns), int(ev.end_ns)
    edges = []
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.name.split(" = ", 1)[0].startswith(trace_reduce.CONTAINERS):
                        continue
                    s, e = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
                    if e > s:
                        edges += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, d in sorted(edges, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy * 1e-9, (hi - lo) * 1e-9


@pytest.fixture(scope="module", params=sorted(TRACES))
def traced(request):
    prof = _profile(request.param)
    return request.param, prof, trace_reduce.reduce_profile(prof)


def test_busy_union_and_idle_share(traced):
    _, prof, red = traced
    busy, window = _sweep_busy(prof)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(window, abs=1e-9)
    assert red.busy_s == pytest.approx(busy, abs=1e-9)
    assert 0 < red.busy_s <= red.window_s
    assert red.idle_share == pytest.approx(1 - busy / window)


def test_device_time_per_program(traced):
    _, _, red = traced
    kinds = programs.classify(red.module_n)
    assert kinds["decode"] and kinds["graft"]
    decode_runs = red.module_n[kinds["decode"][0]]
    assert decode_runs > sum(red.module_n[n] for n in kinds["prefill"])
    for kind in ("decode", "graft"):
        assert programs.seconds(red, kind) > 0
    total = sum(red.module_s.values())
    assert total <= red.window_s
    assert sum(programs.seconds(red, k) for k in kinds) <= total


def test_summed_time_per_kernel(traced):
    cell, _, red = traced
    own = TRACES[cell]
    other = ({"flash_attention", "ssd"} - {own}).pop()
    assert red.ops_matching(programs.KERNELS[other]) == 0.0
    rx = re.compile(programs.KERNELS[own])
    by_hand = sum(s for name, s in red.op_s.items() if rx.search(name))
    assert red.ops_matching(programs.KERNELS[own]) == pytest.approx(by_hand)
    assert red.ops_matching(r"^%rmsnorm") > 0           # rmsnorm runs in every step
    assert not any(name.startswith("%while") for name in red.op_s)
    # every operation is attributed to the program it ran in
    assert sum(red.op_in_s.values()) == pytest.approx(sum(red.op_s.values()))
    assert {m for m, _ in red.op_in_s} <= set(red.module_s)


def test_idle_gaps_carry_host_span_labels(traced):
    _, _, red = traced
    labels = {name for name, _ in red.gaps}
    assert labels <= set(trace_reduce.HOST_SPANS) | {trace_reduce.NO_SPAN}
    assert "engine.step" in labels
    assert sum(s for _, s in red.gaps) == pytest.approx(red.window_s - red.busy_s, abs=1e-6)
    assert [s for _, s in red.gaps] == sorted((s for _, s in red.gaps), reverse=True)
    assert sum(trace_reduce.idle_by_span(red).values()) == pytest.approx(
        red.window_s - red.busy_s, abs=1e-6)
