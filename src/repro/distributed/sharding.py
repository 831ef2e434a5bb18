"""Path-based sharding rules: TP over "model", parameter/optimizer FSDP over
"data", pure DP over "pod" (multi-pod). MoE experts are expert-parallel over
"model" when the expert count divides the axis (deepseek 64/16), else
tensor-parallel inside each expert (mixtral 8 experts on a 16-way axis).

Every rule degrades gracefully: if a dimension is not divisible by the mesh
axis size, that axis is dropped (replicated) rather than failing to lower.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def _axis(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def ambient_mesh_sizes() -> Optional[Dict[str, int]]:
    """Axis-name -> size of the ambient mesh (the ``jax.set_mesh`` context
    the launcher established), or None when no mesh is active."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return None
    return dict(zip(am.axis_names, am.axis_sizes))


def maybe_shard(x, *axes):
    """Best-effort activation sharding constraint: applies
    ``with_sharding_constraint`` against the AMBIENT mesh (the
    ``jax.set_mesh`` context the launcher established). Axes unknown to the
    mesh or larger than the dimension are dropped; with no ambient mesh this
    is the identity — so model code can call it unconditionally and still
    run in plain CPU tests."""
    sizes = ambient_mesh_sizes()
    if sizes is None:
        return x
    clean = []
    for dim, ax in zip(x.shape, axes):
        cand = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        keep = tuple(a for a in cand if a in sizes)
        n = int(np.prod([sizes[a] for a in keep])) if keep else 1
        if not keep or dim < n:
            clean.append(None)
        else:
            clean.append(keep if len(keep) > 1 else keep[0])
    return jax.lax.with_sharding_constraint(x, P(*clean))


def _fit(spec: Tuple[Optional[str], ...], shape, mesh: Mesh):
    """Drop axes the mesh does not have (a 1-D serving gang mesh carries only
    "model") and axes that do not divide the dimension; prepend None for
    extras."""
    spec = (None,) * (len(shape) - len(spec)) + tuple(spec)
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        n = int(np.prod([_axis(mesh, a) for a in axes]))
        if not axes or dim % n != 0:
            out.append(None)
        else:
            out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


# trailing-dims rules per parameter name (see module docstring)
_RULES: Dict[str, Tuple] = {
    "tokens": ("model", "data"),
    "wq": ("data", "model"), "wk": ("data", "model"), "wv": ("data", "model"),
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    "wo": ("model", "data"),
    "w_gate": ("data", "model"), "w_up": ("data", "model"), "w_down": ("model", "data"),
    "router": ("data", None),
    "w_dkv": ("data", None), "w_krope": ("data", None),
    "w_uk": (None, "model"), "w_uv": (None, "model"),
    "in_proj": ("data", "model"),
    "conv_w": ("model", None), "conv_b": ("model",),
    "A_log": ("model",), "dt_bias": ("model",), "D_skip": ("model",),
    "norm_w": ("model",),
    "out_proj": ("model", "data"),
    "lm_head": ("data", "model"),
    "w": (None,), "b": (None,),  # norm scales/biases
}

_EXPERT_RULES_EP = {  # experts sharded over "model" (E % axis == 0)
    "w_gate": ("model", "data", None), "w_up": ("model", "data", None),
    "w_down": ("model", None, "data"),
}
_EXPERT_RULES_TP = {  # experts replicated, FFN dim tensor-parallel
    "w_gate": (None, "data", "model"), "w_up": (None, "data", "model"),
    "w_down": (None, "model", "data"),
}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            names.append(str(k.key))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            names.append(k.name)
    return tuple(names)


def param_specs(params_shape: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching a params (or ShapeDtypeStruct) pytree."""
    expert_parallel = cfg.n_experts > 0 and cfg.n_experts % _axis(mesh, "model") == 0
    expert_rules = _EXPERT_RULES_EP if expert_parallel else _EXPERT_RULES_TP

    def spec(path, leaf):
        names = _path_names(path)
        key = names[-1]
        shape = leaf.shape
        if "moe" in names and "shared" not in names and key in expert_rules:
            return _fit(expert_rules[key], shape, mesh)
        rule = _RULES.get(key)
        if rule is None:
            return P()
        return _fit(rule, shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def param_shardings(params_shape: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape, cfg, mesh))


# --- activations / batch ---------------------------------------------------------
def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if _axis(mesh, a) > 1)


def batch_spec(global_batch: int, mesh: Mesh, extra_dims: int = 1) -> P:
    axes = batch_axes(mesh)
    n = int(np.prod([_axis(mesh, a) for a in axes]))
    # no shardable batch axes (e.g. 1x1 mesh) must yield None, not P(())
    lead = axes if (axes and global_batch % n == 0) else None
    return P(lead, *([None] * extra_dims))


def input_shardings(batch_tree: Any, mesh: Mesh) -> Any:
    """Shard every input on its leading (batch) dim where divisible."""
    def spec(leaf):
        return NamedSharding(mesh, batch_spec(leaf.shape[0], mesh,
                                              extra_dims=len(leaf.shape) - 1))
    return jax.tree.map(spec, batch_tree)


def cache_specs(cache_shape: Any, cfg: ModelConfig, mesh: Mesh,
                global_batch: int, seq_shard: bool = False) -> Any:
    """Decode-cache shardings: batch over (pod,data) when divisible; for the
    attention caches either the trailing feature dim over "model" (baseline)
    or — with ``seq_shard``, the flash-decode layout — the SEQ dim over
    "model" so attention reads its cache shard locally and only tiny softmax
    stats cross the wire."""
    baxes = batch_axes(mesh)
    n = int(np.prod([_axis(mesh, a) for a in baxes]))
    b_ax = baxes if (n > 0 and global_batch % n == 0) else None
    m = _axis(mesh, "model")

    def spec(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        # leading dims are scan stacks until the batch dim (== global_batch)
        try:
            b_idx = shape.index(global_batch)
        except ValueError:
            b_idx = 1
        out = [None] * len(shape)
        out[b_idx] = b_ax
        key = names[-1]
        if key in ("k", "v", "c"):
            # k/v: (..., B, S, KV, dh); c: (..., B, S, r+rope)
            if seq_shard and shape[b_idx + 1] % m == 0:
                out[b_idx + 1] = "model"
            elif shape[-1] % m == 0:
                out[-1] = "model"
        elif key == "state":  # (..., B, H, P, N): shard heads over model
            h_idx = b_idx + 1
            out[h_idx] = "model" if shape[h_idx] % m == 0 else None
        elif key == "conv":  # (..., B, W, C): shard channels over model
            out[-1] = "model" if shape[-1] % m == 0 else None
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def cache_shardings(cache_shape: Any, cfg: ModelConfig, mesh: Mesh,
                    global_batch: int, seq_shard: bool = False) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_specs(cache_shape, cfg, mesh, global_batch, seq_shard))
