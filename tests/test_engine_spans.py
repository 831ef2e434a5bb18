"""The continuous engines' host spans and request stamps: every span in
``engine.SPANS`` is opened where its work happens, with the request or wave
it belongs to as stats, and the queue stamps split a request's wait from its
admission. Read back through ``jax.profiler`` on the CPU at smoke size."""
import glob
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def qwen_setup():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(lengths, max_new):
    from repro.serving.batching import GenRequest
    out = []
    for i, (n, m) in enumerate(zip(lengths, max_new)):
        r = np.random.default_rng(i)
        out.append(GenRequest(id=i, prompt=[int(t) for t in r.integers(1, 100, n)],
                              max_new=m))
    return out


def _spans(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of every host event the engine
    opened (names starting ``engine.``), by start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _traced_run(engine, reqs, trace_dir):
    """Serve ``reqs`` to quiescence under the profiler; the engine's spans."""
    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        for r in reqs:
            engine.add(r)
        engine.run()
    finally:
        jax.profiler.stop_trace()
    return _spans(trace_dir)


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _by(spans, name, key=None):
    return [s for s in spans if s[0] == name and (key is None or key in s[3])]


def test_spans_carry_request_and_wave_stats(qwen_setup, tmp_path):
    from repro.serving import engine as engine_mod
    cfg, params = qwen_setup
    eng = engine_mod.ContinuousEngine(cfg, params, n_slots=2, max_seq=64)
    reqs = _requests([5, 9, 5, 9], [3, 5, 4, 2])
    spans = _traced_run(eng, reqs, tmp_path)

    assert {s[0] for s in spans} == set(engine_mod.SPANS)
    prompt = {r.id: len(r.prompt) for r in reqs}
    admits = _by(spans, engine_mod.ADMIT)
    assert sorted(s[3]["req"] for s in admits) == sorted(prompt)
    for s in admits:
        assert s[3]["ctx"] == prompt[s[3]["req"]] and s[3]["slot"] in (0, 1)
    for name, keys in ((engine_mod.PREFILL, ("req", "ctx")),
                       (engine_mod.GRAFT, ("req", "slot")),
                       (engine_mod.PICK, ("req",))):
        inner = _by(spans, name, "req")
        assert sorted(s[3]["req"] for s in inner) == sorted(prompt), name
        for s in inner:
            # nested in its own request's admission, with the same stats
            parent = [a for a in admits if a[3]["req"] == s[3]["req"]]
            assert len(parent) == 1 and _inside(s, parent[0]), name
            assert all(s[3][k] == parent[0][3][k] for k in keys), name
    # one decode, pick and emit per wave, each with the wave's slot count
    for name in (engine_mod.DECODE, engine_mod.PICK, engine_mod.EMIT):
        waves = _by(spans, name, "active")
        assert len(waves) == eng.n_decode_steps, name
        assert sum(s[3]["active"] for s in waves) == eng.n_slot_steps, name
        assert all(s[3] == {"active": s[3]["active"]} for s in waves), name
    assert len(_by(spans, engine_mod.PICK)) == len(reqs) + eng.n_decode_steps


def test_queue_stamps_split_wait_from_admission(qwen_setup):
    from repro.serving.engine import ContinuousEngine
    cfg, params = qwen_setup
    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=64)
    reqs = _requests([5, 5, 5, 5], [3, 6, 4, 2])
    for r in reqs:
        eng.add(r)
    for r in reqs:
        assert r.queued_at is not None and r.queued_at <= time.perf_counter()
    # the first two found a free slot: admitted as soon as they were queued
    for r in reqs[:2]:
        assert 0 <= r.admitted_at - r.queued_at < 0.05
    assert reqs[2].admitted_at is None and reqs[3].admitted_at is None
    # the last two wait for a slot: request 0 finishes first (3 tokens)
    first_finish = None
    while eng.batcher.active():
        before = time.perf_counter()
        eng.step()
        if first_finish is None and eng.batcher.finished:
            first_finish = (before, time.perf_counter())
    assert eng.batcher.finished[0].id == 0
    for r in reqs[2:]:
        assert r.admitted_at >= first_finish[0] > r.queued_at
    assert reqs[2].admitted_at <= first_finish[1]   # refilled in the same wave
    assert all(len(r.generated) == r.max_new for r in reqs)


def test_drained_partial_queues_again(qwen_setup):
    from repro.serving.engine import ContinuousEngine
    cfg, params = qwen_setup
    eng = ContinuousEngine(cfg, params, n_slots=1, max_seq=64)
    (r,) = _requests([5], [6])
    eng.add(r)
    eng.step()
    first = (r.queued_at, r.admitted_at)
    (part,) = eng.drain()
    eng.add(part)
    assert part.queued_at > first[0] and part.admitted_at > first[1]
    assert part.admitted_at >= part.queued_at


def test_tokens_identical_with_profiler_on_and_off(qwen_setup, tmp_path):
    from repro.serving.engine import ContinuousEngine
    cfg, params = qwen_setup
    lengths, max_new = [5, 9, 5, 9, 5], [4, 3, 5, 2, 4]
    off = ContinuousEngine(cfg, params, n_slots=2, max_seq=64)
    for r in _requests(lengths, max_new):
        off.add(r)
    want = {r.id: r.generated for r in off.run()}
    on = ContinuousEngine(cfg, params, n_slots=2, max_seq=64)
    traced = _requests(lengths, max_new)
    _traced_run(on, traced, tmp_path)
    got = {r.id: r.generated for r in traced}
    assert got == want and len(got) == len(lengths)


def test_paged_engine_spans_and_stamps(qwen_setup, tmp_path):
    """The paged engine's own admission (block install, and a forked
    prefix's extension) and decode wave open the same spans; it has no
    graft."""
    from repro.serving import engine as engine_mod
    cfg, params = qwen_setup
    eng = engine_mod.PagedContinuousEngine(cfg, params, n_slots=2, max_seq=64,
                                           block_size=16)
    prefix = [7] * 12
    assert eng.register_prefix(prefix)
    reqs = _requests([5, 9, 5, 9], [3, 5, 4, 2])
    reqs[1].prompt = prefix + reqs[1].prompt       # admitted through _extend
    spans = _traced_run(eng, reqs, tmp_path)

    assert {s[0] for s in spans} == set(engine_mod.SPANS) - {engine_mod.GRAFT}
    assert eng.share_hits == 1
    admits = {s[3]["req"]: s for s in _by(spans, engine_mod.ADMIT)}
    assert sorted(admits) == [r.id for r in reqs]
    for s in _by(spans, engine_mod.PREFILL):
        parent = admits[s[3]["req"]]
        assert _inside(s, parent) and s[3]["ctx"] == parent[3]["ctx"]
    assert sorted(s[3]["req"] for s in _by(spans, engine_mod.PREFILL)) == sorted(admits)
    waves = _by(spans, engine_mod.DECODE, "active")
    assert len(waves) == eng.n_decode_steps
    assert sum(s[3]["active"] for s in waves) == eng.n_slot_steps
    for r in reqs:
        assert r.queued_at <= r.admitted_at and len(r.generated) == r.max_new
