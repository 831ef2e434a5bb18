"""The comparison that decides ``correct``: what the timed path served,
against the plain reference.

Once the window has closed, a sample of the finished requests drawn from the
seed (the longest always in it) is run through the reference once, each
prompt followed by its served tokens, teacher-forced. For every served token
the number read is how far its logit lies below the reference's best at that
position; the run's number is the widest such gap. Greedy serving emits the
best token of its own arithmetic, so a sound program reads rounding and a
wrong cache, graft, position or kernel reads whole logits.

The control puts the reference, computed in fp8 (the precision step below
the configuration's bfloat16), in the program's place: at each of the same
positions it reads the gap of the token fp8 puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import score_next

GAP = "max_logit_gap"


def sample(served: Sequence[Tuple[List[int], List[int]]], k: int, seed: int
           ) -> List[int]:
    """Indices of ``k`` served (prompt, tokens) pairs drawn from the seed,
    the longest (prompt plus tokens) first."""
    if not served:
        return []
    lengths = [len(p) + len(t) for p, t in served]
    longest = int(np.argmax(lengths))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick.tolist())]


def _layout(prompt: List[int], tokens: List[int], max_seq: int):
    """Teacher-forced input padded to ``max_seq``: position i predicts the
    token at i + 1; served token j is predicted at len(prompt) - 1 + j."""
    seq = list(prompt) + list(tokens[:-1])
    if len(seq) > max_seq:
        raise ValueError(f"served sequence of {len(seq)} exceeds max_seq {max_seq}")
    ids = np.zeros(max_seq, np.int32)
    ids[:len(seq)] = seq
    targets = np.full(max_seq, -1, np.int32)
    start = len(prompt) - 1
    targets[start:start + len(tokens)] = tokens
    return ids, targets


class Reference:
    """One compiled reference evaluation per configuration and length."""

    def __init__(self, ref_module, m: Dict[str, Any], params):
        self.m = m
        self.params = params

        def program(params, ids, targets):
            h = ref_module.hidden(params, ids, m, None)
            best, picked, _ = score_next(params, h, targets, m)
            return best, picked

        def control(params, ids, targets):
            h = ref_module.hidden(params, ids, m, None)
            hq = ref_module.hidden(params, ids, m, "fp8")
            _, _, first = score_next(params, hq, targets, m, "fp8")
            best, picked, _ = score_next(params, h, first, m)
            return best, picked

        self._program = jax.jit(program)
        self._control = jax.jit(control)

    def gaps(self, prompt, tokens, max_seq: int, control: bool = False) -> np.ndarray:
        """Per served token: reference best minus the reference logit of the
        token served (or, for the control, of the token fp8 puts first)."""
        ids, targets = _layout(prompt, tokens, max_seq)
        fn = self._control if control else self._program
        best, picked = fn(self.params, jnp.asarray(ids), jnp.asarray(targets))
        valid = targets >= 0
        return np.asarray(best)[valid] - np.asarray(picked)[valid]


def widest_gap(ref: Reference, pairs, max_seq: int, control: bool = False) -> float:
    return float(max(float(np.max(ref.gaps(p, t, max_seq, control))) for p, t in pairs))
