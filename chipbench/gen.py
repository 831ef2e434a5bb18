"""The one traffic generator. A traffic mix is a data file of parameters
(``traffic/<name>.json``); this module turns it, a cell's rate and a seed
into calls.

Every seed gets the same work. Function prompt lengths are fixed quantiles of
the mix's log-normal; per-call function picks, output lengths and
inter-arrival gaps are fixed multisets (largest-remainder Zipf counts,
uniform and exponential quantiles) put in order by ``LAYOUT_SEED``, the
same for every mix. The run's seed draws only the prompts' tokens (and, in the
harness, the weights). The order stays fixed because it is part of the
work: on a bursty mix it alone moved the TTFT tail by more than half its
median from seed to seed, while reruns of one seed agreed within a few
percent.

Arrivals:
  ``poisson``  open loop at a constant rate;
  ``onoff``    open loop whose rate is ``factor`` times the base rate for
               ``on_s`` of every ``period_s`` seconds (mean = the cell's rate);
  ``closed``   ``clients`` callers, each sending its next call when the last
               one returns.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

ARRIVALS = ("poisson", "onoff", "closed")
CLOSED_BLOCK = 256  # closed-loop calls are drawn in stratified blocks of this size
LAYOUT_SEED = 0     # fixes the order of every mix's calls, lengths and gaps


@dataclasses.dataclass
class Call:
    id: int
    fn: int
    prompt: List[int]
    max_new: int
    due: Optional[float] = None  # seconds from the traffic's start; None = closed loop


def _largest_remainder(weights: np.ndarray, total: int) -> np.ndarray:
    exact = weights / weights.sum() * total
    counts = np.floor(exact).astype(int)
    short = total - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


class Traffic:
    """Calls of one mix at one rate, from one seed."""

    def __init__(self, mix: Dict[str, Any], rate: float, vocab: int, seed: int):
        if mix["arrivals"] not in ARRIVALS:
            raise ValueError(f"traffic arrivals {mix['arrivals']!r}; allowed: {ARRIVALS}")
        self.mix = mix
        self.rate = float(rate)
        self._order = np.random.default_rng(LAYOUT_SEED)
        tokens = np.random.default_rng(np.random.SeedSequence(seed))
        n = mix["functions"]
        pl = mix["prompt_len"]
        z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        lens = [int(min(max(round(pl["median"] * math.exp(pl["sigma"] * q)), pl["min"]),
                        pl["max"])) for q in z]
        rank_of = self._order.permutation(n)
        self.fn_len = [lens[rank_of[f]] for f in range(n)]
        self.popularity = 1.0 / np.arange(1, n + 1) ** mix["zipf_s"]
        self.prompts = [tokens.integers(0, vocab, size=L).tolist() for L in self.fn_len]
        self._next_id = 0

    @property
    def prompt_lengths(self) -> List[int]:
        """Every distinct prompt length this traffic sends (the prefill shapes)."""
        return sorted(set(self.fn_len))

    def _calls(self, n: int) -> List[Call]:
        """``n`` calls: Zipf-stratified functions and uniform-quantile output
        lengths, both in the mix's fixed order."""
        fns = np.repeat(np.arange(self.mix["functions"]),
                        _largest_remainder(self.popularity, n))
        ol = self.mix["output_len"]
        outs = ol["min"] + np.floor((np.arange(n) + 0.5) / n
                                    * (ol["max"] - ol["min"] + 1)).astype(int)
        self._order.shuffle(fns)
        self._order.shuffle(outs)
        out = []
        for f, o in zip(fns.tolist(), outs.tolist()):
            out.append(Call(self._next_id, f, self.prompts[f], o))
            self._next_id += 1
        return out

    def intensity_integral(self, t: np.ndarray) -> np.ndarray:
        """Expected arrivals in [0, t) for the open-loop kinds."""
        t = np.asarray(t, float)
        if self.mix["arrivals"] == "poisson":
            return self.rate * t
        b = self.mix["burst"]
        period, on, factor = b["period_s"], b["on_s"], b["factor"]
        base = self.rate * period / (period + (factor - 1) * on)
        k, r = np.floor_divide(t, period), np.mod(t, period)
        per = base * (period + (factor - 1) * on)
        return k * per + base * (factor * np.minimum(r, on) + np.maximum(r - on, 0))

    def open_schedule(self, horizon: float) -> List[Call]:
        """Open-loop calls due in [0, horizon]: exactly round(Lambda(horizon))
        of them, from exponential-quantile gaps in the mix's order mapped
        through the inverse of the cumulative rate."""
        if self.mix["arrivals"] == "closed":
            raise ValueError("a closed loop has no schedule; use closed_calls()")
        total = float(self.intensity_integral(horizon))
        n = max(int(round(total)), 1)
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        self._order.shuffle(gaps)
        tau = np.cumsum(gaps) * (total / gaps.sum())
        grid = np.linspace(0.0, horizon, int(horizon * 100) + 2)
        due = np.interp(tau, self.intensity_integral(grid), grid)
        calls = self._calls(n)
        for c, d in zip(calls, due.tolist()):
            c.due = d
        return calls

    def closed_calls(self) -> Iterator[Call]:
        """Unending closed-loop calls, stratified in blocks."""
        if self.mix["arrivals"] != "closed":
            raise ValueError("closed_calls() is for closed-loop traffic")
        while True:
            yield from self._calls(CLOSED_BLOCK)
