"""Serving runtime: the continuous-batching engine over dense or block-paged
KV caches.

Exports resolve lazily (PEP 562): ``batching``/``kvcache`` bookkeeping is
importable without JAX (the fast-tier allocator fuzz tests rely on that),
and the engines only pay the JAX import when actually touched.
"""
from __future__ import annotations

import importlib
from typing import Any

# public name -> defining submodule (resolved on first attribute access)
_EXPORTS = {
    "BlockAllocator": "repro.serving.kvcache",
    "ContinuousEngine": "repro.serving.engine",
    "GenRequest": "repro.serving.batching",
    "OutOfBlocks": "repro.serving.kvcache",
    "PagedContinuousEngine": "repro.serving.engine",
    "PagedKVCache": "repro.serving.kvcache",
    "SlotBatchState": "repro.serving.slot_state",
    "SlotBatcher": "repro.serving.batching",
    "find_batch_axes": "repro.serving.slot_state",
    "graft_slot": "repro.serving.slot_state",
    "paged_compatible": "repro.serving.kvcache",
}

__all__ = [
    "BlockAllocator",
    "ContinuousEngine",
    "GenRequest",
    "OutOfBlocks",
    "PagedContinuousEngine",
    "PagedKVCache",
    "SlotBatchState",
    "SlotBatcher",
    "find_batch_axes",
    "graft_slot",
    "paged_compatible",
]


def __getattr__(name: str) -> Any:
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    return getattr(importlib.import_module(module), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
