"""The comparison that decides ``correct``: what the timed path served,
against the plain reference.

Once the window has closed, a sample of the finished requests drawn from the
seed (the longest always in it, and in a gang cell every request live at a
resize) is run through the reference once, each prompt followed by its
served tokens, teacher-forced, in blocks of layers. For every served token
the number read is how far its logit lies below the reference's best at that
position; the run's number is the widest such gap. Greedy serving emits the
best token of its own arithmetic, so a sound program reads rounding and a
wrong cache, graft, position or kernel reads whole logits.

The control puts the reference, computed in fp8 (the precision step below
the configuration's bfloat16), in the program's place: at each of the same
positions it reads the gap of the token fp8 puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import F32, score_next

GAP = "max_logit_gap"


def sample(served: Sequence[Tuple[List[int], List[int]]], k: int, seed: int,
           always: Sequence[int] = ()) -> List[int]:
    """Indices of ``k`` served (prompt, tokens) pairs drawn from the seed,
    the longest (prompt plus tokens) first, and after them every index in
    ``always`` not drawn already."""
    if not served:
        return []
    lengths = [len(p) + len(t) for p, t in served]
    longest = int(np.argmax(lengths))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    drawn = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    pick = [longest] + [rest[i] for i in sorted(drawn.tolist())]
    return pick + [i for i in always if i not in pick]


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


# The most float32 weight bytes a block of layers may hold on one device.
BLOCK_BYTES = 2 ** 30


def block_layers(stack, n_layers: int, block_bytes: int = BLOCK_BYTES) -> int:
    """Layers per block: the largest divisor of ``n_layers`` whose float32
    weights, spread over the devices the stack spans, stay within
    ``block_bytes`` a device (at least one layer)."""
    leaves = jax.tree.leaves(stack)
    devices = max(len(leaf.sharding.device_set) for leaf in leaves)
    per_layer = 4 * sum(leaf.size for leaf in leaves) / n_layers / devices
    most = max(1, int(block_bytes // per_layer))
    return max(b for b in range(1, min(most, n_layers) + 1) if n_layers % b == 0)


class Reference:
    """The reference over one configuration's weights. Weights on one device
    run as one jitted program per evaluation. Weights laid over several
    devices (a model no chip holds) run in blocks of ``block`` layers
    (``block_layers``): each block's weights are sliced from the stack and
    upcast to float32 in a jitted call of its own, so the whole model is
    never in float32 at once. On the CPU the two read the same gaps bit for
    bit; on a TPU their float32 rounding differs."""

    def __init__(self, ref_module, m: Dict[str, Any], params, block: Optional[int] = None):
        self.m = m
        self.params = params
        leaves = jax.tree.leaves(params)
        if block is None and max(len(leaf.sharding.device_set) for leaf in leaves) > 1:
            block = block_layers(params["stack"], m["n_layers"])
        self.block = block

        def whole(params, ids, quant):
            x = ref_module.layers(params["stack"], ref_module.embed(params, ids, m), m, quant)
            return ref_module.final(params, x, m)

        def program(params, ids, targets):
            best, picked, _ = score_next(params, whole(params, ids, None), targets, m)
            return best, picked

        def control(params, ids, targets):
            h = whole(params, ids, None)
            _, _, first = score_next(params, whole(params, ids, "fp8"), targets, m, "fp8")
            best, picked, _ = score_next(params, h, first, m)
            return best, picked

        def run_block(stack, x, lo, quant):
            blk = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, lo, self.block).astype(F32), stack)
            return ref_module.layers(blk, x, m, quant)

        def head(params, x, targets, quant):
            """(best, picked) at each position; in fp8 the token it puts first."""
            scores = score_next(params, ref_module.final(params, x, m), targets, m, quant)
            return scores[2] if quant else scores[:2]

        self._program = jax.jit(program)
        self._control = jax.jit(control)
        self._embed = jax.jit(lambda params, ids: ref_module.embed(params, ids, m))
        self._block = jax.jit(run_block, static_argnums=3)
        self._head = jax.jit(head, static_argnums=3)

    def _last(self, ids, quant=None):
        """The last layer's output (S, D) for ``ids``, block by block."""
        x = self._embed(self.params, ids)
        for lo in range(0, self.m["n_layers"], self.block):
            x = self._block(self.params["stack"], x, jnp.int32(lo), quant)
        return x

    def gaps(self, prompt, tokens, max_seq: int, control: bool = False) -> np.ndarray:
        """Per served token: reference best minus the reference logit of the
        token served (or, for the control, of the token fp8 puts first)."""
        ids, targets = _layout(prompt, tokens, max_seq)
        valid = targets >= 0
        ids, targets = jnp.asarray(ids), jnp.asarray(targets)
        if self.block is None:
            fn = self._control if control else self._program
            best, picked = fn(self.params, ids, targets)
        else:
            if control:
                targets = self._head(self.params, self._last(ids, "fp8"), targets, "fp8")
            best, picked = self._head(self.params, self._last(ids), targets, None)
        return np.asarray(best)[valid] - np.asarray(picked)[valid]


def widest_gap(ref: Reference, pairs, max_seq: int, control: bool = False) -> float:
    return float(max(float(np.max(ref.gaps(p, t, max_seq, control))) for p, t in pairs))
