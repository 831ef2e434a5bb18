"""The readers of the engine's own spans and stamps: ``engine_spans`` on
made-up traces and on a chip trace recorded with the spans
(record_spans_trace.py), and ``queue_wait_p95_ms`` on made-up windows."""
import gzip
import math
import statistics
from types import SimpleNamespace

import pytest

import engine_spans
import gen
import harness
import spec
import trace_reduce
from conftest import DATA

CELL = "qwen2.5-3b.batch-gen"
FIXTURE = DATA / "traces" / f"{CELL}.spans.xplane.pb.gz"
MS = 1_000_000                  # ns


def test_span_names_are_the_engines():
    from repro.serving import engine
    assert engine_spans.SPANS == engine.SPANS
    assert (engine_spans.ADMIT, engine_spans.PICK) == (engine.ADMIT, engine.PICK)


# --- queue_wait_p95_ms --------------------------------------------------------
def _window(waits, t_open=10.0):
    recs = []
    for i, (sent, wait) in enumerate(waits):
        queued = sent + 0.001
        req = SimpleNamespace(queued_at=queued,
                              admitted_at=None if wait is None else queued + wait)
        call = gen.Call(id=i, fn=0, prompt=[1], max_new=1)
        recs.append(harness.Rec(call=call, req=req, sent=sent))
    return harness.Window(recs=recs, t_open=t_open, t_close=20.0, t_end=25.0,
                          counters_open={}, counters_close={}, compiles=0,
                          queue_open=0, queue_close=0)


def _queue_wait(window):
    ctx = harness.Context(spec.cell(CELL), {}, window, None, {})
    return spec.metric_reader("queue_wait_p95_ms")(ctx)


def test_queue_wait_p95_over_window_requests():
    waits = [(11.0 + i * 0.1, 0.001 * (i + 1)) for i in range(20)]
    # sent before the window: not counted, however long it waited
    assert _queue_wait(_window([(9.0, 5.0)] + waits)) == pytest.approx(19.0)
    # one of 20 never admitted is the 20th value: p95 is the 19th
    assert _queue_wait(_window(waits[:19] + [(12.0, None)])) == pytest.approx(19.0)
    # two of 20 reach the 95th percentile
    assert math.isinf(_queue_wait(_window(waits[:18] + [(12.0, None)] * 2)))
    assert _queue_wait(_window([])) is None


def test_queue_wait_reads_nothing_without_stamps():
    w = _window([(11.0, 0.01)])
    w.recs[0].req = SimpleNamespace(id=0)         # a program that does not stamp
    assert _queue_wait(w) is None


# --- engine_spans on made-up traces ------------------------------------------
DEC, PRE, GRAFT, ARG = "jit__unknown(1)", "jit__unknown(2)", "jit__lambda(3)", "jit__argmax(4)"


def _made_up():
    """Five waves of a 13 ms decode plus a pick program, 2.5 ms apart, with
    the host clock 0.8 ms late against the device's; the second wave is
    followed by an admission and the last one's successor lies past the
    window."""
    runs, spans = [], []
    t = 2 * MS
    for k in range(5):
        runs += [(t, t + 13 * MS, DEC), (t + 13 * MS, t + 13 * MS + 20_000, ARG)]
        host = t + 800_000
        spans += [engine_spans.Span("engine.step", host - 300_000, host + 14 * MS, {}),
                  engine_spans.Span(engine_spans.DECODE, host - 200_000, host, {"active": 4}),
                  engine_spans.Span(engine_spans.PICK, host, host + 13 * MS + 900_000,
                                    {"active": 4})]
        end = t + 13 * MS + 20_000
        if k == 1:
            runs += [(end + MS, end + 9 * MS, PRE), (end + 9 * MS, end + 12 * MS, GRAFT),
                     (end + 12 * MS, end + 12 * MS + 10_000, ARG)]
            spans.append(engine_spans.Span(engine_spans.PICK, end + 11 * MS, end + 13 * MS,
                                           {"req": 9}))
            t = end + 12 * MS + 10_000 + 2_500_000
        else:
            t = end + 2_500_000
    runs.append((t, t + 13 * MS, DEC))
    window = (0, t - MS)
    spans.sort(key=lambda s: (s.start, -s.end))
    return engine_spans.Trace(window=window, spans=spans, runs=sorted(runs), idle=[])


def test_wave_gaps_skip_admissions_and_the_windows_edge():
    trace = _made_up()
    gaps = engine_spans.wave_host_gaps(trace)
    # waves 0, 2, 3 are handed straight on; wave 1 admits, wave 4's next
    # decode starts after the window closes
    assert gaps == pytest.approx([2.5e-3] * 3)


def test_wave_gaps_need_the_engines_pick_spans():
    trace = _made_up()
    trace.spans = [s for s in trace.spans if s.name != engine_spans.PICK]
    assert engine_spans.wave_host_gaps(trace) == []


def test_innermost_span_and_stalls():
    trace = _made_up()
    s = engine_spans.innermost(trace, 5 * MS)
    assert s.name == engine_spans.PICK and s.stats == {"active": 4}
    assert engine_spans.innermost(trace, 2_600_000).name == engine_spans.DECODE
    assert engine_spans.innermost(trace, 1 * MS) is None
    # the second gap's midpoint, 16.75 ms, falls after wave 0's pick ends
    trace.idle = [(4 * MS, 5 * MS), (6_500_000, 27 * MS)]
    (at, idle, span), = engine_spans.stalls(trace)
    assert (at, idle) == pytest.approx((0.0065, 0.0205))
    assert span.name == "engine.step"


# --- on a chip trace ------------------------------------------------------------
@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(gzip.decompress(FIXTURE.read_bytes()))
    return engine_spans.from_profile(prof), trace_reduce.reduce_profile(prof)


def test_chip_trace_holds_every_engine_span(chip):
    trace, _ = chip
    assert set(engine_spans.SPANS) <= {s.name for s in trace.spans}
    waves = [s for s in trace.spans if s.name == engine_spans.PICK and "active" in s.stats]
    assert waves and all(s.stats["active"] <= 64 for s in waves)
    for s in trace.spans:
        if s.name in (engine_spans.PREFILL, engine_spans.GRAFT) or "req" in s.stats:
            admit = [a for a in trace.spans if a.name == engine_spans.ADMIT
                     and a.stats["req"] == s.stats["req"] and a.start <= s.start <= s.end <= a.end]
            assert admit, s


def test_wave_gaps_on_chip_trace(chip):
    trace, red = chip
    gaps = engine_spans.wave_host_gaps(trace)
    lo, hi = trace.window
    waves = [s for s in trace.spans
             if s.name == engine_spans.PICK and "active" in s.stats and lo <= s.end <= hi]
    admissions = [s for s in trace.spans if s.name == engine_spans.ADMIT and lo <= s.start <= hi]
    assert admissions and 0 < len(gaps) < len(waves)
    assert all(0 < g < 0.05 for g in gaps)
    # a wave's gap is idle device time: all of them together fit in the idle
    # time of the trace's reduction
    assert sum(gaps) <= red.window_s - red.busy_s
    assert sum(e - s for s, e in trace.idle) * 1e-9 == pytest.approx(
        red.window_s - red.busy_s, abs=1e-6)


def test_stall_listing_on_chip_trace(chip, capsys):
    trace, _ = chip
    found = engine_spans.stalls(trace, 1e-3)
    assert found and all(idle >= 1e-3 for _, idle, _ in found)
    assert engine_spans.main([str(FIXTURE), "1"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0] == f"idle gaps >= 1 ms: {len(found)}"
    listed = out[1:1 + len(found)]
    known = set(engine_spans.SPANS) | set(trace_reduce.HOST_SPANS)
    for line, (_, _, span) in zip(listed, found):
        assert (span.name if span else trace_reduce.NO_SPAN) in line
        assert span is None or span.name in known
    assert any(span is not None and span.name in engine_spans.SPANS for _, _, span in found)
    assert any(line.startswith(f"median {engine_spans.PICK}.wave ") for line in out)


@pytest.mark.parametrize("fixture,reads", [
    (FIXTURE, True), (DATA / "traces" / "qwen2.5-3b.doc-faas.xplane.pb.gz", False)])
def test_wave_host_gap_reader_finds_the_cells_trace(fixture, reads, tmp_path, monkeypatch):
    """Through the trace a traced run leaves at TRACE_ROOT/<cell>; a trace
    without the engine's spans (a program that opens none) reads nothing."""
    monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path)
    out = tmp_path / CELL / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(gzip.decompress(fixture.read_bytes()))
    ctx = harness.Context(spec.cell(CELL), {}, None, None, {})
    value = spec.metric_reader("wave_host_gap_ms")(ctx)
    if reads:
        gaps = engine_spans.wave_host_gaps(engine_spans.read(FIXTURE))
        assert value == pytest.approx(1e3 * statistics.median(gaps))
    else:
        assert value is None
    monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path / "none")
    assert spec.metric_reader("wave_host_gap_ms")(ctx) is None
