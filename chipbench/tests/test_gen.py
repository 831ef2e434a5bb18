"""The traffic generator: determinism, ranges, skew, bursts, closed loop,
and the same work for every seed."""
import itertools
import json
from collections import Counter

import numpy as np
import pytest

import gen
from conftest import BENCH

MIXES = {name: json.loads((BENCH / "traffic" / f"{name}.json").read_text())
         for name in ("doc-faas", "chat-burst", "batch-gen")}
BIG_SEED = 2**31 + 12345


def schedule(name, rate, seed, horizon=60.0):
    return gen.Traffic(MIXES[name], rate, 1000, seed).open_schedule(horizon)


@pytest.mark.parametrize("name", ["doc-faas", "chat-burst"])
def test_same_seed_same_calls_and_seed_draws_tokens_only(name, monkeypatch):
    a, b = schedule(name, 6.0, BIG_SEED), schedule(name, 6.0, BIG_SEED)
    assert [(c.due, c.fn, c.prompt, c.max_new) for c in a] == \
           [(c.due, c.fn, c.prompt, c.max_new) for c in b]
    c = schedule(name, 6.0, BIG_SEED + 1)
    assert [(x.due, x.fn, len(x.prompt), x.max_new) for x in a] == \
           [(x.due, x.fn, len(x.prompt), x.max_new) for x in c]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    monkeypatch.setattr(gen, "LAYOUT_SEED", 1)
    d = schedule(name, 6.0, BIG_SEED)
    assert [x.due for x in a] != [x.due for x in d]


@pytest.mark.parametrize("name", ["doc-faas", "chat-burst", "batch-gen"])
def test_lengths_in_range_and_one_prompt_length_per_function(name):
    mix = MIXES[name]
    t = gen.Traffic(mix, 5.0, 1000, BIG_SEED)
    calls = (t.open_schedule(60.0) if mix["arrivals"] != "closed"
             else list(itertools.islice(t.closed_calls(), 600)))
    pl, ol = mix["prompt_len"], mix["output_len"]
    lens = {}
    for c in calls:
        assert pl["min"] <= len(c.prompt) <= pl["max"]
        assert ol["min"] <= c.max_new <= ol["max"]
        assert all(0 <= tok < 1000 for tok in c.prompt)
        lens.setdefault(c.fn, set()).add(len(c.prompt))
    assert all(len(v) == 1 for v in lens.values())
    assert len(t.prompt_lengths) <= mix["functions"]


def test_every_order_gets_the_same_work(monkeypatch):
    """The order is a shuffle of fixed multisets of function lengths,
    output lengths and arrival gaps."""
    runs = []
    for k in (0, 1, 2):
        monkeypatch.setattr(gen, "LAYOUT_SEED", k)
        runs.append(schedule("doc-faas", 6.0, 1))
    for a in runs[1:]:
        assert sorted(set(len(c.prompt) for c in a)) == sorted(set(len(c.prompt) for c in runs[0]))
        assert sorted(c.max_new for c in a) == sorted(c.max_new for c in runs[0])
        assert len(a) == len(runs[0])
        ga = np.diff([0.0] + [c.due for c in a])
        g0 = np.diff([0.0] + [c.due for c in runs[0]])
        assert np.allclose(np.sort(ga), np.sort(g0), rtol=1e-6, atol=1e-9)


def test_zipf_skew():
    calls = schedule("doc-faas", 10.0, BIG_SEED, horizon=100.0)
    n = len(calls)
    counts = Counter(c.fn for c in calls)
    p = 1.0 / np.arange(1, 17)
    p /= p.sum()
    for rank in range(16):
        assert abs(counts[rank] - n * p[rank]) <= 1
    assert counts[0] > 7 * counts[15]


def test_poisson_rate_and_gaps():
    calls = schedule("doc-faas", 8.0, BIG_SEED, horizon=50.0)
    assert len(calls) == 400
    gaps = np.diff([c.due for c in calls])
    assert 0.9 / 8 < gaps.mean() < 1.1 / 8
    assert 0.8 < gaps.std() / gaps.mean() < 1.2      # exponential: cv near 1


def test_burst_timing():
    mix = MIXES["chat-burst"]
    b = mix["burst"]
    rate = 6.0
    calls = schedule("chat-burst", rate, BIG_SEED, horizon=80.0)
    assert len(calls) == round(rate * 80)
    phase = np.mod([c.due for c in calls], b["period_s"])
    on = np.sum(phase < b["on_s"])
    off = len(calls) - on
    base = rate * b["period_s"] / (b["period_s"] + (b["factor"] - 1) * b["on_s"])
    # ten periods; counts within Poisson noise (three standard deviations)
    want_on = base * b["factor"] * b["on_s"] * 10
    want_off = base * (b["period_s"] - b["on_s"]) * 10
    assert abs(on - want_on) <= 3 * want_on ** 0.5
    assert abs(off - want_off) <= 3 * want_off ** 0.5


def test_closed_loop_has_no_schedule_and_counts_clients():
    t = gen.Traffic(MIXES["batch-gen"], 0, 1000, BIG_SEED)
    with pytest.raises(ValueError):
        t.open_schedule(10.0)
    assert MIXES["batch-gen"]["clients"] == 64
    first = list(itertools.islice(t.closed_calls(), gen.CLOSED_BLOCK))
    assert len({c.id for c in first}) == gen.CLOSED_BLOCK
    with pytest.raises(ValueError):
        next(gen.Traffic(MIXES["doc-faas"], 1.0, 1000, 1).closed_calls())


def test_unknown_arrivals_refused():
    with pytest.raises(ValueError, match="arrivals"):
        gen.Traffic(dict(MIXES["doc-faas"], arrivals="gamma"), 1.0, 1000, 1)
