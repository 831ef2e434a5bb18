"""Pytree slot-state protocol for continuous batching.

A continuous-batching engine keeps ONE device pytree holding the decode state
of every batch slot. For dense GQA that pytree is the classic per-layer K/V
cache; for MLA it is the compressed latent cache (``{"c"}``); for mamba2 the
SSM recurrent state + conv window (``{"state", "conv"}``); for zamba2 hybrids
all of the above at once. ``SlotBatchState`` abstracts over that shape so
:class:`repro.serving.engine.ContinuousEngine` never needs to know which
architecture it is serving:

* every leaf has exactly one *batch axis* — found structurally by diffing the
  model's ``cache_spec`` at two batch sizes (scan-stacked leading layer axes
  make the position leaf-dependent);
* admission produces a batch-1 state (prefill), which is *grafted* into one
  slot's batch row of the live state — right-padded with zeros on every
  non-batch axis first, so no stale state from the row's previous occupant
  survives;
* drain/migration can read or replace the whole tree (``engine.cache`` stays
  an assignable attribute for the elastic-serving migration protocol);
* both programs that rewrite the tree take it donated and update it in
  place: the engine's batched ``decode_step`` (which carries each segment's
  stacked cache through its layer scan) and the graft here. A tree read
  from ``engine.cache`` is therefore dead after the next ``step()`` or
  admitting ``add()``; read the attribute again instead of holding it.

Anything the model exposes through ``cache_spec``/``init_cache`` therefore
serves through the same engine, paged or dense, with zero engine changes.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as model_mod


def find_batch_axes(cfg: ModelConfig, max_seq: int):
    """Per-leaf batch-axis index of the decode-state pytree, found by diffing
    specs of two batch sizes. Works for every family because ``cache_spec``
    is the single source of truth for decode-state shapes."""
    s1 = model_mod.cache_spec(cfg, 1, max_seq)
    s2 = model_mod.cache_spec(cfg, 2, max_seq)

    def axis(a, b):
        diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        assert len(diff) == 1, (a.shape, b.shape)
        return diff[0]
    return jax.tree.map(axis, s1, s2)


def graft_slot(live, pre, slot, batch_axes):
    """Write a batch-1 state pytree into batch row ``slot`` of ``live``.

    The batch-1 state is right-padded (zeros) up to the live shape on every
    non-batch axis first, so the whole row is overwritten and no stale state
    from the slot's previous occupant survives. Jit this with the engine."""
    def one(z, c, ax):
        target = list(z.shape)
        target[ax] = 1
        pad = [(0, t - s) for t, s in zip(target, c.shape)]
        assert all(hi >= 0 for _, hi in pad), (z.shape, c.shape, ax)
        c = jnp.pad(c.astype(z.dtype), pad)
        return jax.lax.dynamic_update_slice_in_dim(z, c, slot, axis=ax)
    return jax.tree.map(one, live, pre, batch_axes)


class SlotBatchState:
    """The live decode state of ``n_slots`` concurrent requests, as one
    device pytree with a per-leaf batch axis."""

    def __init__(self, cfg: ModelConfig, n_slots: int, max_seq: int):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.tree = model_mod.init_cache(cfg, n_slots, max_seq)
        self.batch_axes = find_batch_axes(cfg, max_seq)
        self._graft = jax.jit(
            lambda live, pre, slot: graft_slot(live, pre, slot,
                                               self.batch_axes),
            donate_argnums=(0,))

    def graft(self, pre: Any, slot: int) -> None:
        """Install a batch-1 prefill state into ``slot``'s batch row."""
        self.tree = self._graft(self.tree, pre, jnp.int32(slot))

    @property
    def nbytes(self) -> int:
        return int(sum(leaf.nbytes for leaf in jax.tree.leaves(self.tree)))
