"""The engine's own host spans and the device's program runs, read from one
profiler trace on the trace's clock.

``ContinuousEngine`` opens the spans below (``repro.serving.engine.SPANS``;
``tests/test_engine_spans.py`` ties the two lists together) inside the
harness's ``engine.add`` / ``engine.step``. Each carries small integer stats:
``req`` (and ``slot``, ``ctx``) for one request's admission, ``active`` (slots
in the wave) for a decode wave's decode, pick and emit.

Host and device planes do not share one clock to the millisecond: in the
committed traces every decode starts on the device 0.56-0.96 ms before its
host dispatch begins. So a span is compared with device times only where
that cannot change the answer: to find its wave among decode runs that lie
13 ms and more apart, and to label a gap of 20 ms or more.

  python3 chipbench/engine_spans.py <cell | trace dir | .xplane.pb[.gz]> [min_ms]

prints every idle gap of the device of at least ``min_ms`` (default 20) in
the traced ``window`` span, with the innermost host span open at the gap's
midpoint and that span's stats, then the median duration of each span.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import harness
import programs
import trace_reduce

SPANS = ADMIT, PREFILL, GRAFT, PICK, DECODE, EMIT = (
    "engine.admit", "engine.prefill", "engine.graft", "engine.pick",
    "engine.decode", "engine.emit")
STALL_S = 0.020
USAGE = "usage: engine_spans.py <cell | trace dir | .xplane.pb[.gz]> [min_ms]"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                        # ns, trace clock
    end: int
    stats: Dict[str, int]


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]           # the harness's ``window`` span
    spans: List[Span]                 # the engine's and the harness's host spans, by start
    runs: List[Tuple[int, int, str]]  # program runs on the first device, by start
    idle: List[Tuple[int, int]]       # that device's idle intervals inside the window


def from_profile(profile) -> Trace:
    """Read a ``jax.profiler.ProfileData``."""
    named = set(SPANS) | set(trace_reduce.HOST_SPANS)
    window = None
    spans: List[Span] = []
    devices = []
    for plane in profile.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (int(ev.start_ns), int(ev.end_ns))
                    elif ev.name in named:
                        spans.append(Span(ev.name, int(ev.start_ns), int(ev.end_ns),
                                          {k: int(v) for k, v in ev.stats}))
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"trace holds no host span named {trace_reduce.WINDOW_SPAN!r}")
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")
    lines = {line.name: line for line in min(devices, key=lambda p: p.name).lines}
    runs, ops = [], []
    for ev in getattr(lines.get(trace_reduce.MODULES_LINE), "events", ()):
        runs.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    for ev in getattr(lines.get(trace_reduce.OPS_LINE), "events", ()):
        if not ev.name.split(" = ", 1)[0].startswith(trace_reduce.CONTAINERS):
            ops.append((int(ev.start_ns), int(ev.end_ns)))
    lo, hi = window
    busy = trace_reduce._clip(trace_reduce._union(ops), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    return Trace(window, sorted(spans, key=lambda s: (s.start, -s.end)), sorted(runs), idle)


def read(path: Path) -> Trace:
    """A trace directory, an ``.xplane.pb`` or a gzipped one."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        path = trace_reduce.find_xplane(path)
    if path.suffix == ".gz":
        return from_profile(ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes())))
    return from_profile(ProfileData.from_file(str(path)))


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime_ns: int) -> Trace:
    return read(Path(path))


def for_cell(cell: str) -> Optional[Trace]:
    """The trace of the cell's traced run, read once for every reader; None
    where the run left none."""
    try:
        path = trace_reduce.find_xplane(harness.TRACE_ROOT / cell)
    except FileNotFoundError:
        return None
    return _read_once(str(path), path.stat().st_mtime_ns)


def wave_host_gaps(trace: Trace) -> List[float]:
    """Seconds the device idles between a decode wave and the next one, per
    wave that the host handed straight on. A wave is an ``engine.pick`` span
    with ``active`` that ends inside the window. Its decode run is the last
    run of the decode program (``programs.classify``) to start before the
    span's midpoint: a wave's decode starts near the span's start, the next
    one after its end. Its gap runs from the end of the last program before
    the next decode run to that run's start, clipped to the window. A wave
    followed by a prefill or graft (an admission) before the next decode is
    left out, as is one whose next decode starts after the window; a gap
    that the planes' offset leaves at or below zero, at the window's start,
    too."""
    lo, hi = trace.window
    counts: Dict[str, int] = {}
    for s, e, name in trace.runs:
        if e > lo and s < hi:
            counts[name] = counts.get(name, 0) + 1
    kinds = programs.classify(counts)
    admission = set(kinds["prefill"]) | set(kinds["graft"])
    dec = [r for r in trace.runs if r[2] in kinds["decode"]]
    starts = [r[0] for r in dec]
    gaps = []
    for s in trace.spans:
        if s.name != PICK or "active" not in s.stats or not lo <= s.end <= hi:
            continue
        k = bisect.bisect_left(starts, (s.start + s.end) // 2) - 1
        if k < 0 or k + 1 >= len(dec) or dec[k + 1][0] > hi:
            continue
        i, j = (bisect.bisect_left(trace.runs, dec[k]),
                bisect.bisect_left(trace.runs, dec[k + 1]))
        if any(r[2] in admission for r in trace.runs[i + 1:j]):
            continue
        last_end = max(r[1] for r in trace.runs[i:j])
        gap = dec[k + 1][0] - max(last_end, lo)
        if gap > 0:
            gaps.append(gap * 1e-9)
    return gaps


def innermost(trace: Trace, t: int) -> Optional[Span]:
    """The latest-starting host span open at ``t``: spans nest, so it is
    the innermost."""
    open_ = [s for s in trace.spans[:bisect.bisect_right([s.start for s in trace.spans], t)]
             if s.end >= t]
    return open_[-1] if open_ else None


def stalls(trace: Trace, min_s: float = STALL_S) -> List[Tuple[float, float, Optional[Span]]]:
    """(offset from the window's start, seconds idle, innermost span at the
    gap's midpoint) of every device idle gap of at least ``min_s``, in time
    order."""
    lo = trace.window[0]
    return [((s - lo) * 1e-9, (e - s) * 1e-9, innermost(trace, (s + e) // 2))
            for s, e in trace.idle if (e - s) * 1e-9 >= min_s]


def span_medians(trace: Trace) -> Dict[str, float]:
    """Median seconds of each engine span inside the window; the pick spans
    of waves (``active``) apart from those of admissions."""
    lo, hi = trace.window
    by: Dict[str, List[float]] = {}
    for s in trace.spans:
        if s.name in SPANS and lo <= s.start and s.end <= hi:
            key = s.name + (".wave" if s.name == PICK and "active" in s.stats else "")
            by.setdefault(key, []).append((s.end - s.start) * 1e-9)
    return {k: statistics.median(v) for k, v in sorted(by.items())}


def main(argv: Sequence[str]) -> int:
    if not argv or len(argv) > 2:
        print(USAGE, file=sys.stderr)
        return 2
    target = Path(argv[0])
    if not target.exists():
        target = harness.TRACE_ROOT / argv[0]
    min_s = float(argv[1]) * 1e-3 if len(argv) > 1 else STALL_S
    trace = read(target)
    found = stalls(trace, min_s)
    print(f"idle gaps >= {min_s * 1e3:g} ms: {len(found)}")
    for at, idle, span in found:
        where = f"{span.name} {span.stats}" if span else trace_reduce.NO_SPAN
        print(f"  at {at:.4f} s  idle {idle * 1e3:.3f} ms  in {where}")
    for name, s in span_medians(trace).items():
        print(f"median {name} {s * 1e3:.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
