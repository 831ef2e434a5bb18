"""The trace reduction on small traces recorded on a TPU v5e by
record_trace.py (a 0.4 s span of each committed cell)."""
import gzip
import hashlib
import json
import re

import pytest

import programs
import spec
import trace_reduce
from conftest import DATA

TRACES = {"qwen2.5-3b.doc-faas": "flash_attention", "mamba2-2.7b.chat-burst": "ssd"}
PATTERNS = {k: spec.metric_module(f"{k}_roofline").PATTERN for k in TRACES.values()}


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
    assert red.ops_matching(PATTERNS[other]) == 0.0
    rx = re.compile(PATTERNS[own])
    by_hand = sum(s for name, s in red.op_s.items() if rx.search(name))
    assert red.ops_matching(PATTERNS[own]) == pytest.approx(by_hand)
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


# The parent commit's reduction of each committed trace (``reduce_profile``
# before it took device ids): busy and window seconds, and a digest of every
# field of the reduction.
GOLDEN = {
    "mamba2-2.7b.chat-burst": (0.6758191210000001, 0.749778469,
                               "a7c8a0959c8df3164082535d5c7b30c45168bb54ffc7a72f214279ca143a6fab"),
    "qwen2.5-3b.batch-gen.spans": (0.315746146, 0.362738436,
                                   "fa5578fa028779f64e2cf568d524d34c978836bca6f7ac4599567381a4a17383"),
    "qwen2.5-3b.doc-faas": (0.28126703000000003, 0.308421005,
                            "01b2cd9b5a4ce6a24d8f84eda2d0d2f0d9fe186d935703e9b02631782d648eeb"),
}


def _digest(red):
    blob = json.dumps([red.window_s, red.busy_s, red.n_devices, sorted(red.module_s.items()),
                       sorted(red.module_n.items()), sorted(red.op_s.items()), red.gaps,
                       sorted([list(k), v] for k, v in red.op_in_s.items())])
    return hashlib.sha256(blob.encode()).hexdigest()


@pytest.mark.parametrize("devices", [None, [0]])
@pytest.mark.parametrize("cell", sorted(GOLDEN))
def test_one_plane_reduction_is_unchanged(cell, devices):
    red = trace_reduce.reduce_profile(_profile(cell), devices)
    assert (red.busy_s, red.window_s, _digest(red)) == GOLDEN[cell]


class _Plane:
    def __init__(self, name, lines):
        self.name = name
        self.lines = lines


def _gang_profile(cell):
    """The committed one-chip trace as a four-chip gang's that shrank to
    two: devices 0 and 1 run the same programs, 2 and 3 were handed back
    and run nothing."""
    prof = _profile(cell)
    planes = {p.name: _Plane(p.name, list(p.lines)) for p in prof.planes}
    dev = planes.pop("/device:TPU:0")
    gang = [_Plane(f"/device:TPU:{i}", dev.lines if i < 2 else []) for i in range(4)]
    return type("Profile", (), {"planes": list(planes.values()) + gang[::-1]})()


def test_gang_trace_reduces_over_its_member_planes():
    cell = "qwen2.5-3b.doc-faas"
    one = trace_reduce.reduce_profile(_profile(cell))
    prof = _gang_profile(cell)
    members = trace_reduce.reduce_profile(prof, [0, 1])
    assert members.n_devices == 2
    assert members.busy_s == pytest.approx(one.busy_s)
    assert members.idle_share == pytest.approx(one.idle_share)
    assert members.module_n == {k: 2 * n for k, n in one.module_n.items()}
    assert members.module_s == pytest.approx({k: 2 * t for k, t in one.module_s.items()})
    assert sum(t for _, t in members.gaps) == pytest.approx(2 * sum(t for _, t in one.gaps))
    assert programs.classify(members.module_n) == programs.classify(one.module_n)
    every = trace_reduce.reduce_profile(prof)
    assert every.n_devices == 4
    assert every.idle_share == pytest.approx((1 + one.idle_share) / 2)
    with pytest.raises(ValueError, match=r"no plane for devices \[5\]"):
        trace_reduce.reduce_profile(prof, [0, 5])
