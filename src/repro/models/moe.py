"""Mixture-of-Experts FFN with three interchangeable dispatch implementations.

- ``dense``   : every expert computes every token, masked combine. O(E/k) waste;
                the correctness oracle for tests and tiny smoke configs.
- ``scatter`` : capacity-bounded scatter/gather dispatch (Switch-style). Uses
                only scatter/gather/dot HLOs, so it partitions under GSPMD on
                the production mesh — the dry-run default.
- ``ragged``  : sort-by-expert + ``jax.lax.ragged_dot`` (megablocks-style,
                exact active FLOPs, no padding). The Pallas ``moe_gmm`` kernel
                in ``repro.kernels`` is the TPU-native target of this path.

All three agree exactly when no token is dropped (capacity high enough); the
property test sweeps this.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, kernel_impl
from repro.models.layers import trunc_normal


def init_moe(rng, cfg: ModelConfig, n_stack: Optional[int] = None):
    pd = jnp.dtype(cfg.param_dtype)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    lead = () if n_stack is None else (n_stack,)
    ks = jax.random.split(rng, 6)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": trunc_normal(ks[0], lead + (d, e), s_in, pd),
        "w_gate": trunc_normal(ks[1], lead + (e, d, f), s_in, pd),
        "w_up": trunc_normal(ks[2], lead + (e, d, f), s_in, pd),
        "w_down": trunc_normal(ks[3], lead + (e, f, d), s_out, pd),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "w_gate": trunc_normal(ks[4], lead + (d, fs), s_in, pd),
            "w_up": trunc_normal(ks[5], lead + (d, fs), s_in, pd),
            "w_down": trunc_normal(ks[4], lead + (fs, d), fs ** -0.5, pd),
        }
    return p


def route(router_w, x, cfg: ModelConfig):
    """Returns (weights (T,k), expert_idx (T,k), aux) for flattened tokens."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = cfg.n_experts
    assign = jax.nn.one_hot(idx[:, 0], e)  # top-1 assignment fraction
    f_e = jnp.mean(assign, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = {"lb_loss": e * jnp.sum(f_e * p_e), "router_probs_mean": p_e}
    return weights, idx, aux


def _expert_ffn(w_gate, w_up, w_down, x):
    """x: (..., D) with expert-major weights (..., D, F)/(..., F, D)."""
    dt = x.dtype
    g = jax.nn.silu(jnp.einsum("...cd,...df->...cf", x, w_gate.astype(dt)))
    u = jnp.einsum("...cd,...df->...cf", x, w_up.astype(dt))
    return jnp.einsum("...cf,...fd->...cd", g * u, w_down.astype(dt))


def _shared_ffn(p, x, cfg: ModelConfig):
    dt = x.dtype
    g = jax.nn.silu(jnp.einsum("td,df->tf", x, p["w_gate"].astype(dt)))
    u = jnp.einsum("td,df->tf", x, p["w_up"].astype(dt))
    return jnp.einsum("tf,fd->td", g * u, p["w_down"].astype(dt))


# --- impls -------------------------------------------------------------------
def _moe_dense(p, x, weights, idx, cfg: ModelConfig):
    t, d = x.shape
    e = cfg.n_experts
    # (E, T, D): every expert computes every token — oracle only.
    h = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], jnp.broadcast_to(x, (e, t, d)))
    combine = jnp.zeros((t, e), x.dtype)
    for k in range(cfg.top_k):
        combine = combine + jax.nn.one_hot(idx[:, k], e, dtype=x.dtype) * weights[:, k:k + 1].astype(x.dtype)
    return jnp.einsum("te,etd->td", combine, h)


def _shard(cfg: ModelConfig, x, *axes):
    if not cfg.shard_activations:
        return x
    from repro.distributed.sharding import maybe_shard
    return maybe_shard(x, *axes)


def _expert_parallel(cfg: ModelConfig) -> bool:
    """True when experts shard over the ambient mesh's "model" axis."""
    from repro.distributed.sharding import ambient_mesh_sizes
    m = (ambient_mesh_sizes() or {}).get("model", 1)
    return m > 1 and cfg.n_experts % m == 0


def _moe_scatter(p, x, weights, idx, cfg: ModelConfig):
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(t * k / e * cfg.capacity_factor + 0.999)
    cap = max(8, min(t, (cap + 7) // 8 * 8))
    flat_e = idx.reshape(-1)                       # (T*k,) assignment -> expert
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot      # position within expert
    pos = jnp.sum(pos * onehot, axis=-1)           # (T*k,)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)  # overflow -> dump row
    x_rep = jnp.repeat(x, k, axis=0)               # (T*k, D)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(x_rep)
    buf3 = buf[:-1].reshape(e, cap, d)
    # Dispatch-buffer constraints: tried in three variants (EXPERIMENTS.md
    # §Perf M1-M3). M1 (capacity-sharded) fought the expert weights -> an
    # all-to-all storm; M3 (mode-aligned) helped mixtral bytes 26% but left
    # deepseek's expert compute replicated (GSPMD replicates the capacity
    # buffer under the global-index combine-gather). Default: leave the MoE
    # dispatch to XLA-auto (M2); the production fix is a shard_map all-to-all
    # dispatch + the Pallas moe_gmm kernel on locally-sorted tokens.
    if cfg.moe_dispatch_constraints and cfg.shard_activations and _expert_parallel(cfg):
        buf3 = _shard(cfg, buf3, "model", None, None)
        h = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], buf3)
        h = _shard(cfg, h, "model", None, None)
    else:
        h = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], buf3)
    y_rep = h.reshape(e * cap, d)[jnp.minimum(slot, e * cap - 1)]
    y_rep = jnp.where(keep[:, None], y_rep, 0.0)
    y_rep = y_rep * weights.reshape(-1, 1).astype(x.dtype)
    return jnp.sum(y_rep.reshape(t, k, d), axis=1)


def _moe_ragged(p, x, weights, idx, cfg: ModelConfig):
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    inv = jnp.argsort(order, stable=True)
    xs = jnp.repeat(x, k, axis=0)[order]
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    dt = x.dtype
    g = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), group_sizes))
    u = jax.lax.ragged_dot(xs, p["w_up"].astype(dt), group_sizes)
    ys = jax.lax.ragged_dot(g * u, p["w_down"].astype(dt), group_sizes)
    y_rep = ys[inv] * weights.reshape(-1, 1).astype(dt)
    return jnp.sum(y_rep.reshape(t, k, d), axis=1)


def _moe_gmm_capacity(p, x, weights, idx, cfg: ModelConfig):
    """Pallas twin of ``_moe_scatter``: identical capacity/drop bookkeeping
    (same cap, slot and keep math — so the drop set matches token-for-token),
    with the (E, C, D) expert FFN computed by the ``moe_gmm`` grouped-matmul
    kernel instead of a batched einsum."""
    from repro.kernels.ops import moe_gmm_capacity
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(t * k / e * cfg.capacity_factor + 0.999)
    cap = max(8, min(t, (cap + 7) // 8 * 8))
    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum(pos * onehot, axis=-1)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)
    x_rep = jnp.repeat(x, k, axis=0)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(x_rep)
    buf3 = buf[:-1].reshape(e, cap, d)
    bt = math.gcd(cap, 128)   # cap need not divide 128 when clamped to t
    dt = x.dtype
    g = jax.nn.silu(moe_gmm_capacity(buf3, p["w_gate"].astype(dt), block_t=bt))
    u = moe_gmm_capacity(buf3, p["w_up"].astype(dt), block_t=bt)
    h = moe_gmm_capacity(g * u, p["w_down"].astype(dt), block_t=bt)
    y_rep = h.reshape(e * cap, d)[jnp.minimum(slot, e * cap - 1)]
    y_rep = jnp.where(keep[:, None], y_rep, 0.0)
    y_rep = y_rep * weights.reshape(-1, 1).astype(x.dtype)
    return jnp.sum(y_rep.reshape(t, k, d), axis=1)


def _moe_gmm_dropless(p, x, weights, idx, cfg: ModelConfig):
    """Pallas twin of ``_moe_ragged``: dropless sort-by-expert dispatch with
    each expert's row range padded up to a ``block_t`` multiple (zero rows)
    so every tile belongs to one expert — megablocks-style. Processes the
    exact same token set as the ragged/dense reference paths."""
    from repro.kernels.ops import moe_gmm_op, pad_group_sizes
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tk = t * k
    bt = 128 if tk >= 128 else 8
    # static worst-case padded length (every group rounds up by < bt)
    t_pad = (tk + bt - 1) // bt * bt + e * bt
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    inv = jnp.argsort(order, stable=True)
    xs = jnp.repeat(x, k, axis=0)[order]
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    _, padded_offs = pad_group_sizes(group_sizes, bt)
    raw_offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(group_sizes)])
    shift = (padded_offs[:-1] - raw_offs[:-1]).astype(jnp.int32)
    dest = jnp.arange(tk, dtype=jnp.int32) + shift[flat_e[order]]
    buf = jnp.zeros((t_pad, d), x.dtype).at[dest].set(xs)
    tile_starts = jnp.arange(t_pad // bt, dtype=jnp.int32) * bt
    te = jnp.clip(
        jnp.searchsorted(padded_offs, tile_starts, side="right") - 1, 0, e - 1
    ).astype(jnp.int32)
    dt = x.dtype
    g = jax.nn.silu(moe_gmm_op(buf, p["w_gate"].astype(dt), te, block_t=bt))
    u = moe_gmm_op(buf, p["w_up"].astype(dt), te, block_t=bt)
    ys = moe_gmm_op(g * u, p["w_down"].astype(dt), te, block_t=bt)[dest]
    y_rep = ys[inv] * weights.reshape(-1, 1).astype(dt)
    return jnp.sum(y_rep.reshape(t, k, d), axis=1)


def _moe_gmm_impl(p, x, weights, idx, cfg: ModelConfig):
    """Kernel-path dispatch: mirror the reference impl's drop semantics so
    temperature-0 tokens stay identical — capacity drops for ``scatter``,
    dropless for ``ragged``/``dense``."""
    if cfg.moe_impl == "scatter":
        return _moe_gmm_capacity(p, x, weights, idx, cfg)
    return _moe_gmm_dropless(p, x, weights, idx, cfg)


_IMPLS = {"dense": _moe_dense, "scatter": _moe_scatter, "ragged": _moe_ragged,
          "gmm": _moe_gmm_impl}


def apply_moe(p, x, cfg: ModelConfig, impl: Optional[str] = None) -> Tuple[jnp.ndarray, dict]:
    """x: (B,S,D) -> (y, aux). The per-config ``kernel_impls['moe']`` policy
    swaps in the Pallas grouped-matmul path unless ``impl`` overrides it."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    weights, idx, aux = route(p["router"], xt, cfg)
    if impl is None and kernel_impl(cfg, "moe") == "kernel":
        impl = "gmm"
    if (impl or cfg.moe_impl) not in _IMPLS:
        raise ValueError(
            f"apply_moe: unknown impl {(impl or cfg.moe_impl)!r}; allowed "
            f"impls: {tuple(sorted(_IMPLS))}")
    y = _IMPLS[impl or cfg.moe_impl](p, xt, weights, idx, cfg)
    if cfg.n_shared_experts:
        y = y + _shared_ffn(p["shared"], xt, cfg)
    return y.reshape(b, s, d), aux
