"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: the device's busy union over the traced window, device time
per program (XLA module) and per operation, and the idle gaps labelled by
the benchmark's own host span open at that moment.

Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line holds one
event per program execution and their ``XLA Ops`` line one per operation,
keyed here by its HLO instruction name. A ``while`` (a layer scan) spans
the operations of its body, so container operations are left out of the
busy union and the per-operation times.
Host spans are the ``TraceAnnotation`` events on the host plane; the
harness wraps the whole traced window in a span named ``window``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# operations whose events span the operations of their body
CONTAINERS = ("%while", "%conditional", "%call")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
HOST_SPANS = ("client.wait", "engine.add", "engine.step")
NO_SPAN = "no span"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # mean over devices of the busy union
    n_devices: int
    module_s: Dict[str, float]        # program name -> summed device seconds
    module_n: Dict[str, int]          # program name -> executions
    op_s: Dict[str, float]            # operation (HLO instruction) -> summed device seconds
    gaps: List[Tuple[str, float]]     # (host span, idle seconds), longest first
    op_in_s: Dict[Tuple[str, str], float] = dataclasses.field(default_factory=dict)
    #                                 (program, operation) -> summed device seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_profile(profile) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_spans: List[Tuple[int, int, str]] = []
    window = None
    devices = []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (int(ev.start_ns), int(ev.end_ns))
                    elif ev.name in HOST_SPANS:
                        host_spans.append((int(ev.start_ns), int(ev.end_ns), ev.name))
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"trace holds no host span named {WINDOW_SPAN!r}")
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")
    lo, hi = window
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    op_s: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    host_spans.sort()
    op_in_s: Dict[Tuple[str, str], float] = defaultdict(float)
    for plane in devices:
        ops: List[Tuple[int, int]] = []
        runs: List[Tuple[int, int, str]] = []
        lines = {line.name: line for line in plane.lines}
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else ()):
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e > lo and s < hi:
                module_s[ev.name] += (min(e, hi) - max(s, lo)) * 1e-9
                module_n[ev.name] += 1
                runs.append((s, e, ev.name))
        runs.sort()
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            key = ev.name.split(" = ", 1)[0]
            if key.startswith(CONTAINERS):
                continue
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e > lo and s < hi:
                d = (min(e, hi) - max(s, lo)) * 1e-9
                op_s[key] += d
                op_in_s[(_label(runs, s), key)] += d
                ops.append((s, e))
        busy = _clip(_union(ops), lo, hi)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((_label(host_spans, (gs + ge) // 2), (ge - gs) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_total / len(devices),
                     n_devices=len(devices), module_s=dict(module_s),
                     module_n=dict(module_n), op_s=dict(op_s), gaps=gaps,
                     op_in_s=dict(op_in_s))


def _label(spans: List[Tuple[int, int, str]], t: int) -> str:
    """The latest-starting of sorted, non-nesting spans open at ``t``: the
    harness's host spans, or a device's program runs."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return NO_SPAN


def reduce_dir(trace_dir: Path) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(find_xplane(trace_dir))))


def idle_by_span(red: Reduction) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for name, s in red.gaps:
        out[name] += s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def describe(profile, top: int = 12) -> str:
    """Planes, lines, event counts and the most frequent event names: what
    to look at before changing the names this module reads."""
    from collections import Counter
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(ev.name for ev in evs).most_common(top)
            span = (evs[0].start_ns, evs[-1].end_ns) if evs else ()
            out.append(f"  line {line.name!r}: {len(evs)} events, span {span}")
            out.extend(f"    {n!r} x{c}" for n, c in names)
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    from jax.profiler import ProfileData
    path = find_xplane(Path(sys.argv[1]))
    prof = ProfileData.from_file(str(path))
    print(describe(prof))
    red = reduce_profile(prof)
    print({k: v for k, v in dataclasses.asdict(red).items() if k != "gaps"})
    print("idle by span", idle_by_span(red))
