"""Stack composition: scan-over-layers segments for every family.

A model trunk is an ordered list of *segments*; each segment is a homogeneous
group of blocks whose params are stacked along a leading axis and executed
with ``jax.lax.scan`` (O(1)-in-depth HLO, which keeps 512-device dry-run
compiles tractable). Families:

  dense/vlm/audio : [dense x L]
  moe             : [dense x first_dense] + [moe x (L - first_dense)]
  ssm             : [mamba x L]
  hybrid (zamba2) : [group x (L // attn_every)], each group = attn_every
                    scanned mamba blocks + ONE shared attn+MLP block whose
                    params are common to all groups (the zamba2 trick)

Each segment supports three modes: forward (train), prefill (forward + cache
emission), decode (single token against a cache).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, init_mlp, layer_norm, rms_norm


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str  # dense | moe | ssm | hybrid_group
    n: int     # scan length


def _scan(cfg: ModelConfig, body, init, xs):
    return jax.lax.scan(body, init, xs,
                        unroll=(_seg_len(xs) if cfg.unroll else 1))


def _seg_len(xs):
    return jax.tree.leaves(xs)[0].shape[0]


def segments_for(cfg: ModelConfig) -> List[Segment]:
    if cfg.family in ("dense", "vlm", "audio"):
        return [Segment("dense", "dense", cfg.n_layers)]
    if cfg.family == "moe":
        segs = []
        if cfg.first_dense_layers:
            segs.append(Segment("dense0", "dense", cfg.first_dense_layers))
        segs.append(Segment("moe", "moe", cfg.n_layers - cfg.first_dense_layers))
        return segs
    if cfg.family == "ssm":
        return [Segment("ssm", "ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        assert cfg.n_layers % cfg.attn_every == 0
        return [Segment("hybrid", "hybrid_group", cfg.n_layers // cfg.attn_every)]
    raise ValueError(cfg.family)


def _norm(x, p, cfg: ModelConfig):
    if cfg.act == "gelu":  # hubert-style encoder uses LayerNorm (with bias)
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps, cfg)


def _init_norm(cfg: ModelConfig, lead):
    pd = jnp.dtype(cfg.param_dtype)
    p = {"w": jnp.ones(lead + (cfg.d_model,), pd)}
    if cfg.act == "gelu":
        p["b"] = jnp.zeros(lead + (cfg.d_model,), pd)
    return p


# --- init ---------------------------------------------------------------------
def _init_dense_block(rng, cfg: ModelConfig, n: int | None):
    k1, k2 = jax.random.split(rng)
    lead = () if n is None else (n,)
    return {
        "ln1": _init_norm(cfg, lead),
        "attn": attn_mod.init_attention(k1, cfg, n),
        "ln2": _init_norm(cfg, lead),
        "mlp": init_mlp(k2, cfg, cfg.d_ff, n),
    }


def _init_moe_block(rng, cfg: ModelConfig, n: int):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": _init_norm(cfg, (n,)),
        "attn": attn_mod.init_attention(k1, cfg, n),
        "ln2": _init_norm(cfg, (n,)),
        "moe": moe_mod.init_moe(k2, cfg, n),
    }


def _init_ssm_stack(rng, cfg: ModelConfig, lead_shape: Tuple[int, ...]):
    """Mamba blocks (+ pre-norm) with arbitrary leading stack shape."""
    flat = 1
    for d in lead_shape:
        flat *= d
    p = {"ln": _init_norm(cfg, lead_shape),
         "mixer": ssm_mod.init_mamba(rng, cfg, flat)}
    p["mixer"] = jax.tree.map(lambda x: x.reshape(lead_shape + x.shape[1:]), p["mixer"])
    return p


def init_stack(rng, cfg: ModelConfig) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    keys = jax.random.split(rng, 4)
    for i, seg in enumerate(segments_for(cfg)):
        k = keys[i]
        if seg.kind == "dense":
            out[seg.name] = _init_dense_block(k, cfg, seg.n)
        elif seg.kind == "moe":
            out[seg.name] = _init_moe_block(k, cfg, seg.n)
        elif seg.kind == "ssm":
            out[seg.name] = _init_ssm_stack(k, cfg, (seg.n,))
        elif seg.kind == "hybrid_group":
            k1, k2 = jax.random.split(k)
            out[seg.name] = {
                "mamba": _init_ssm_stack(k1, cfg, (seg.n, cfg.attn_every)),
                "shared": _init_dense_block(k2, cfg, None),  # ONE shared block
            }
        else:
            raise ValueError(seg.kind)
    return out


# --- block bodies ---------------------------------------------------------------
def _dense_body(p, x, positions, cfg: ModelConfig):
    h = attn_mod.attention(p["attn"], _norm(x, p["ln1"], cfg), positions, cfg)
    x = x + h
    h = apply_mlp(p["mlp"], _norm(x, p["ln2"], cfg), cfg)
    return x + h


def _moe_body(p, x, positions, cfg: ModelConfig):
    h = attn_mod.attention(p["attn"], _norm(x, p["ln1"], cfg), positions, cfg)
    x = x + h
    h, aux = moe_mod.apply_moe(p["moe"], _norm(x, p["ln2"], cfg), cfg)
    return x + h, aux["lb_loss"]


def _ssm_body(p, x, cfg: ModelConfig, initial_state=None):
    h, final_state, conv_tail = ssm_mod.mamba_block(
        p["mixer"], _norm(x, p["ln"], cfg), cfg, initial_state)
    return x + h, final_state, conv_tail


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    raise ValueError(cfg.remat)


# --- forward ---------------------------------------------------------------------
def stack_forward(params, x, positions, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence forward. Returns (hidden, aux) with total MoE lb_loss."""
    lb_total = jnp.zeros((), jnp.float32)
    for seg in segments_for(cfg):
        p = params[seg.name]
        if seg.kind == "dense":
            body = _remat(lambda h, lp: _dense_body(lp, h, positions, cfg), cfg)
            x, _ = _scan(cfg, lambda h, lp: (body(h, lp), None), x, p)
        elif seg.kind == "moe":
            body = _remat(lambda h, lp: _moe_body(lp, h, positions, cfg), cfg)
            x, lbs = _scan(cfg, lambda h, lp: body(h, lp), x, p)
            lb_total = lb_total + jnp.sum(lbs)
        elif seg.kind == "ssm":
            body = _remat(lambda h, lp: _ssm_body(lp, h, cfg)[0], cfg)
            x, _ = _scan(cfg, lambda h, lp: (body(h, lp), None), x, p)
        elif seg.kind == "hybrid_group":
            shared = p["shared"]

            def group(h, gp):
                h, _ = _scan(cfg, lambda hh, lp: (_ssm_body(lp, hh, cfg)[0], None), h, gp)
                return _dense_body(shared, h, positions, cfg)
            body = _remat(group, cfg)
            x, _ = _scan(cfg, lambda h, gp: (body(h, gp), None), x, p["mamba"])
        else:
            raise ValueError(seg.kind)
    return x, {"lb_loss": lb_total}


# --- prefill (forward + cache emission) -------------------------------------------
def _attn_prefill(p, x, positions, cfg: ModelConfig):
    """Attention sublayer for prefill: returns (residual-added x, cache tuple)."""
    xin = _norm(x, p["ln1"], cfg)
    if cfg.use_mla:
        h, cache = attn_mod.mla_prefill(p["attn"], xin, positions, cfg)
        return x + h, (cache,)
    h, k, v = attn_mod.gqa_prefill(p["attn"], xin, positions, cfg)
    return x + h, (k, v)


def _dense_prefill_body(p, x, positions, cfg: ModelConfig):
    x, cache = _attn_prefill(p, x, positions, cfg)
    h = apply_mlp(p["mlp"], _norm(x, p["ln2"], cfg), cfg)
    return x + h, cache


def _moe_prefill_body(p, x, positions, cfg: ModelConfig):
    x, cache = _attn_prefill(p, x, positions, cfg)
    h, _ = moe_mod.apply_moe(p["moe"], _norm(x, p["ln2"], cfg), cfg)
    return x + h, cache


def stack_prefill(params, x, positions, cfg: ModelConfig):
    """Returns (hidden, cache dict). Cache leading dims are scan-stacked."""
    cache: Dict[str, Any] = {}
    for seg in segments_for(cfg):
        p = params[seg.name]
        if seg.kind in ("dense", "moe"):
            body_fn = _dense_prefill_body if seg.kind == "dense" else _moe_prefill_body
            x, cs = _scan(cfg, lambda h, lp: body_fn(lp, h, positions, cfg), x, p)
            if cfg.use_mla:
                cache[seg.name] = {"c": cs[0]}
            else:
                cache[seg.name] = {"k": cs[0], "v": cs[1]}
        elif seg.kind == "ssm":
            def body_s(h, lp):
                h, st, tail = _ssm_body(lp, h, cfg)
                return h, (st, tail)
            x, (states, tails) = _scan(cfg, body_s, x, p)
            cache[seg.name] = {"state": states, "conv": tails}
        elif seg.kind == "hybrid_group":
            shared = p["shared"]

            def group(h, gp):
                def inner(hh, lp):
                    hh, st, tail = _ssm_body(lp, hh, cfg)
                    return hh, (st, tail)
                h, (sts, tails) = _scan(cfg, inner, h, gp)
                h, kv = _dense_prefill_body(shared, h, positions, cfg)
                return h, (sts, tails, kv[0], kv[1])
            x, (states, tails, ks, vs) = _scan(cfg, group, x, p["mamba"])
            cache[seg.name] = {"state": states, "conv": tails, "k": ks, "v": vs}
        else:
            raise ValueError(seg.kind)
    return x, cache


# --- decode ------------------------------------------------------------------------
def _ffn_decode(p, x, cfg: ModelConfig):
    if "mlp" in p:
        return x + apply_mlp(p["mlp"], _norm(x, p["ln2"], cfg), cfg)
    h, _ = moe_mod.apply_moe(p["moe"], _norm(x, p["ln2"], cfg), cfg)
    return x + h


def _entry(a, at):
    """The entry of stacked ``a`` at the leading indices ``at``."""
    n = len(at)
    start = tuple(at) + (0,) * (a.ndim - n)
    return jax.lax.dynamic_slice(a, start, (1,) * n + a.shape[n:]).reshape(a.shape[n:])


def _put_entry(a, at, v):
    """``a`` with its entry at the leading indices ``at`` replaced by ``v``."""
    v = v.astype(a.dtype).reshape((1,) * len(at) + v.shape)
    return jax.lax.dynamic_update_slice(a, v, tuple(at) + (0,) * (a.ndim - len(at)))


def _ssm_decode_body(p, x, state, conv, at, cfg: ModelConfig):
    """One mamba layer's decode against the stacked ``state``/``conv``: reads
    the layer's entries at ``at``, then overwrites them with the new ones."""
    h, st, cv = ssm_mod.mamba_decode(p["mixer"], _norm(x, p["ln"], cfg),
                                     _entry(state, at), _entry(conv, at), cfg)
    return x + h, _put_entry(state, at, st), _put_entry(conv, at, cv)


def _scan_layers(cfg: ModelConfig, body, x, params, cache):
    """Run ``body(h, cache, lp, i) -> (h, cache)`` over the layers stacked in
    ``params``, with the segment's stacked ``cache`` carried whole: layer
    ``i`` updates its entries of the carried arrays and reads them back.
    Nothing copies a layer out of the scan's ``xs`` or into a fresh stacked
    ``ys``, so a donated cache is updated in place."""
    def step(carry, xs):
        return body(*carry, *xs), None
    idx = jnp.arange(_seg_len(params), dtype=jnp.int32)
    (x, cache), _ = _scan(cfg, step, (x, cache), (params, idx))
    return x, cache


def stack_decode(params, x, cache, pos, cfg: ModelConfig):
    """One-token decode. x: (B,1,D); pos: scalar int32 OR (B,) int32 vector
    (per-slot positions for continuous batching — each batch row attends at
    its own offset). -> (hidden, new_cache). Each segment's cache rides in
    its layer scan's carry (:func:`_scan_layers`), so the caller can donate
    ``cache`` and have it updated in place."""
    new_cache: Dict[str, Any] = {}
    for seg in segments_for(cfg):
        p = params[seg.name]
        c = cache[seg.name]
        if seg.kind in ("dense", "moe"):
            if cfg.use_mla:
                def body(h, cc, lp, i):
                    a, c_new = attn_mod.mla_decode(lp["attn"], _norm(h, lp["ln1"], cfg),
                                                   cc["c"], pos, i, cfg)
                    return _ffn_decode(lp, h + a, cfg), {"c": c_new}
            else:
                def body(h, cc, lp, i):
                    a, kc, vc = attn_mod.gqa_decode(lp["attn"], _norm(h, lp["ln1"], cfg),
                                                    cc["k"], cc["v"], pos, i, cfg)
                    return _ffn_decode(lp, h + a, cfg), {"k": kc, "v": vc}
            x, new_cache[seg.name] = _scan_layers(cfg, body, x, p, c)
        elif seg.kind == "ssm":
            def body_s(h, cc, lp, i):
                h, st, cv = _ssm_decode_body(lp, h, cc["state"], cc["conv"], (i,), cfg)
                return h, {"state": st, "conv": cv}
            x, new_cache[seg.name] = _scan_layers(cfg, body_s, x, p, c)
        elif seg.kind == "hybrid_group":
            shared = p["shared"]

            def body_g(h, cc, gp, g):
                def inner(hh, sc, lp, j):
                    hh, st, cv = _ssm_decode_body(lp, hh, sc["state"], sc["conv"], (g, j), cfg)
                    return hh, {"state": st, "conv": cv}
                h, sc = _scan_layers(cfg, inner, h, gp,
                                     {"state": cc["state"], "conv": cc["conv"]})
                xin = _norm(h, shared["ln1"], cfg)
                a, kc, vc = attn_mod.gqa_decode(shared["attn"], xin, cc["k"], cc["v"],
                                                pos, g, cfg)
                h = h + a
                h = h + apply_mlp(shared["mlp"], _norm(h, shared["ln2"], cfg), cfg)
                return h, {**sc, "k": kc, "v": vc}
            x, new_cache[seg.name] = _scan_layers(cfg, body_g, x, p["mamba"], c)
        else:
            raise ValueError(seg.kind)
    return x, new_cache
