"""Distributed substrate: sharding path rules + elastic sharded serving.

This layer stays usable without the simulator (layering: it imports no
core/faas/platform code), and its exports resolve lazily (PEP 562) so
importing ``repro.distributed`` never pays the JAX import.
"""
from __future__ import annotations

import importlib
from typing import Any

# public name -> defining submodule (resolved on first attribute access)
_EXPORTS = {
    "ElasticReplica": "repro.distributed.elastic_serving",
    "MigrationProtocol": "repro.distributed.elastic_serving",
    "MigrationRecord": "repro.distributed.elastic_serving",
    "ambient_mesh_sizes": "repro.distributed.sharding",
    "cache_shardings": "repro.distributed.sharding",
    "input_shardings": "repro.distributed.sharding",
    "maybe_shard": "repro.distributed.sharding",
    "param_shardings": "repro.distributed.sharding",
    "serving_mesh": "repro.distributed.elastic_serving",
}

__all__ = [
    "ElasticReplica",
    "MigrationProtocol",
    "MigrationRecord",
    "ambient_mesh_sizes",
    "cache_shardings",
    "input_shardings",
    "maybe_shard",
    "param_shardings",
    "serving_mesh",
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
