"""CPU rehearsal of a run through the harness's own functions, at smoke
size with interpreted kernels; the end-to-end definitions on recorded
token times; and the command's refusal off a TPU."""
import math
import os
import shutil
import subprocess
import sys
import time

import pytest

import gen
import harness
import spec
from conftest import BENCH, DATA


def _rec(sent, stamps, max_new):
    call = gen.Call(id=0, fn=0, prompt=[1, 2], max_new=max_new)
    return harness.Rec(call=call, req=None, sent=sent, stamps=list(stamps))


def _window(recs):
    return harness.Window(recs=recs, t_open=10.0, t_close=20.0, t_end=25.0,
                          counters_open={}, counters_close={}, compiles=0,
                          queue_open=0, queue_close=0)


def test_end_to_end_from_token_times():
    recs = [_rec(9.0, [9.5, 9.6], 2),                        # sent before the window
            _rec(10.0, [10.2, 10.3, 10.7], 3),
            _rec(11.0, [11.1, 11.2], 2),
            _rec(19.5, [19.9, 20.5], 2)]                      # last token after close
    m = harness.end_to_end(_window(recs), setup_s=3.0)
    # sent in the window: TTFTs 0.2, 0.1, 0.4 -> nearest-rank p95 is the max
    assert m["ttft_p95_ms"] == pytest.approx(400.0)
    # gaps of window requests: .1 .4 .1 .6 -> p99 is the largest
    assert m["itl_p99_ms"] == pytest.approx(600.0)
    assert m["latency_p95_s"] == pytest.approx(1.0)
    # tokens that reached the host inside [10, 20): 3 + 2 + 1
    assert m["tokens_per_s"] == pytest.approx(6 / 10.0)
    assert m["setup_s"] == 3.0


def test_unfinished_request_counts_as_missing():
    done = [_rec(10.0 + i * 0.1, [10.1 + i * 0.1, 10.2 + i * 0.1], 2) for i in range(9)]
    m = harness.end_to_end(_window(done), setup_s=1.0)
    assert not math.isinf(m["latency_p95_s"])
    cut = _rec(12.0, [12.5], 3)                               # cut by the drain cap
    m = harness.end_to_end(_window(done + [cut]), setup_s=1.0)
    assert math.isinf(m["latency_p95_s"]) and not math.isinf(m["ttft_p95_ms"])
    never = _rec(13.0, [], 2)                                 # never got a token
    m = harness.end_to_end(_window(done + [never]), setup_s=1.0)
    assert math.isinf(m["latency_p95_s"]) and math.isinf(m["ttft_p95_ms"])


def test_nearest_rank():
    assert harness.nearest_rank([3.0, 1.0, 2.0], 50) == 2.0
    assert harness.nearest_rank(list(range(1, 101)), 95) == 95
    assert math.isinf(harness.nearest_rank([], 95))


@pytest.mark.parametrize("name", ["smoke-gqa", "smoke-mamba", "smoke-gqa-closed"])
def test_rehearsal_serves_and_checks(name):
    cell = spec.cell(name, DATA)
    t = time.perf_counter()
    w, setup_s, served, peak = harness.serve(cell, 2**33 + 5, 2.0, None, t)
    sent = harness.in_window(w)
    assert sent and all(r.done for r in sent)
    assert w.compiles == 0                                    # every shape warmed in set-up
    assert w.counters_close["n_decode_steps"] > w.counters_open["n_decode_steps"]
    for r in w.recs:                     # calls sent during the drain may be cut
        assert len(r.stamps) == len(r.req.generated) <= r.call.max_new
        assert r.stamps == sorted(r.stamps) and all(t >= r.sent for t in r.stamps)
    assert all(len(r.stamps) == r.call.max_new for r in sent)
    m = harness.end_to_end(w, setup_s)
    assert all(0 < v < math.inf for v in m.values())
    if cell["traffic_spec"]["arrivals"] == "closed":
        # each client sends its next call as the last one returns: never
        # more than `clients` calls in flight, and a call is sent at the
        # host time its predecessor's last token arrived
        clients = cell["traffic_spec"]["clients"]
        by_client = {}
        for r in w.recs:
            by_client.setdefault(r.client, []).append(r)
        assert sorted(by_client) == list(range(clients))
        for recs in by_client.values():
            for a, b in zip(recs, recs[1:]):
                assert a.stamps[-1] <= b.sent
        for r in w.recs:
            live = sum(1 for o in w.recs if o.sent <= r.sent and (not o.stamps or o.stamps[-1] > r.sent))
            assert live <= clients
    numbers = harness.correctness(cell, 2**33 + 5, served)
    assert harness.passed(numbers), numbers


def test_traced_span_is_the_windows_end(tmp_path):
    """The traced span is the window's last TRACE_S seconds; saving the
    trace falls in the drain, whose cap counts from the end of the save."""
    cell = spec.cell("smoke-gqa", DATA)
    w, *_ = harness.serve(cell, 2**33 + 7, 3.0, tmp_path / "trace", time.perf_counter())
    lo, hi = w.trace
    assert w.t_close - harness.TRACE_S <= lo < w.t_close - harness.TRACE_S + 0.5
    assert hi >= w.t_close and w.trace_save_s >= 0
    assert w.t_end >= hi + w.trace_save_s
    assert all(r.done for r in harness.in_window(w))


def _command(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chipbench/run.py"] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_command_refuses_off_a_tpu():
    out = _command(["--workload", "qwen2.5-3b.doc-faas", "--seed", "1", "--seconds", "1",
                    "--trace", "0"], spec.CHECKOUT)
    assert out.returncode != 0
    assert "TPU" in out.stderr
    assert not out.stdout.strip()


def test_command_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(spec.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    out = _command(["--workload", "qwen2.5-3b.doc-faas", "--seed", "1", "--seconds", "1",
                    "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()
    assert "No module named 'repro'" in out.stderr
