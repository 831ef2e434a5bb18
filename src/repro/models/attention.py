"""Attention variants: GQA (with optional QKV bias + sliding window) and MLA
(DeepSeek multi-head latent attention, compressed-KV decode with absorption).

Full-sequence paths are einsum-based (the XLA/SPMD reference used for the
dry-run); the Pallas flash-attention kernel in ``repro.kernels`` is the TPU
target for the same math and is validated against ``repro.kernels.ref``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, kernel_impl
from repro.models.layers import apply_rope, trunc_normal

NEG_INF = -1e9  # large-negative instead of -inf: keeps softmax NaN-free

BATCH_AXES = ("pod", "data")


def _shard(cfg: ModelConfig, x, *axes):
    """Activation constraint, active only in shard_activations mode."""
    if not cfg.shard_activations:
        return x
    from repro.distributed.sharding import maybe_shard
    return maybe_shard(x, *axes)


# --- init -------------------------------------------------------------------
def init_attention(rng, cfg: ModelConfig, n_stack: Optional[int] = None):
    pd = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    lead = () if n_stack is None else (n_stack,)
    ks = jax.random.split(rng, 8)
    s = d ** -0.5
    if cfg.use_mla:
        qd = cfg.q_dim
        p = {
            "wq": trunc_normal(ks[0], lead + (d, qd), s, pd),
            "w_dkv": trunc_normal(ks[1], lead + (d, cfg.kv_lora_rank), s, pd),
            "w_krope": trunc_normal(ks[2], lead + (d, cfg.qk_rope_dim), s, pd),
            "w_uk": trunc_normal(
                ks[3], lead + (cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_dim),
                cfg.kv_lora_rank ** -0.5, pd),
            "w_uv": trunc_normal(
                ks[4], lead + (cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim),
                cfg.kv_lora_rank ** -0.5, pd),
            "wo": trunc_normal(
                ks[5], lead + (cfg.n_heads * cfg.v_head_dim, d),
                (cfg.n_heads * cfg.v_head_dim) ** -0.5, pd),
        }
        return p
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": trunc_normal(ks[0], lead + (d, h * dh), s, pd),
        "wk": trunc_normal(ks[1], lead + (d, kv * dh), s, pd),
        "wv": trunc_normal(ks[2], lead + (d, kv * dh), s, pd),
        "wo": trunc_normal(ks[3], lead + (h * dh, d), (h * dh) ** -0.5, pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(lead + (h * dh,), pd)
        p["bk"] = jnp.zeros(lead + (kv * dh,), pd)
        p["bv"] = jnp.zeros(lead + (kv * dh,), pd)
    return p


# --- masks ------------------------------------------------------------------
def _attn_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(..., Sq, Sk) boolean allow-mask from broadcastable position vectors."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        m = m & (k <= q)
    if window is not None:
        m = m & (k > q - window)
    return m


# --- GQA full-sequence ------------------------------------------------------
def _project_qkv(p, x, cfg: ModelConfig):
    dt = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def chunked_mha(q, k, v, cfg: ModelConfig, chunk_q: int = 512,
                chunk_k: int = 1024):
    """Memory-efficient (online-softmax) attention in pure XLA — the
    dry-run/TPU-fallback twin of the Pallas flash kernel: q/kv are processed
    in blocks with running (m, l, acc) statistics, so the S^2 score matrix is
    never materialized in HBM. Causal + sliding-window masks applied per
    block. All-blocks are computed (a lax.scan cannot skip the masked upper
    triangle — the Pallas kernel does; the wasted FLOPs show up honestly in
    useful_ratio).

    q,k,v: (B,S,H,D) post-RoPE, KV already repeated to H. Returns (B,S,H*D).
    """
    b, s, h, d = q.shape
    cq = min(chunk_q, s)
    ck = min(chunk_k, s)
    pad_q = (-s) % cq
    pad_k = (-s) % ck
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // cq, k.shape[1] // ck
    dt = q.dtype
    scale = d ** -0.5
    qb = q.reshape(b, nq, cq, h, d)
    kb = jnp.moveaxis(k.reshape(b, nk, ck, h, d), 1, 0)  # (nk,b,ck,h,d)
    vb = jnp.moveaxis(v.reshape(b, nk, ck, h, d), 1, 0)
    causal = cfg.is_autoregressive
    window = cfg.sliding_window
    unroll_k = nk if cfg.unroll else 1
    unroll_q = nq if cfg.unroll else 1

    def q_block(_, inp):
        qc, iq = inp                      # (b,cq,h,d), scalar
        qc = _shard(cfg, qc, BATCH_AXES, None, "model", None)
        q_pos = iq * cq + jnp.arange(cq)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            kc, vc, ik = kv_in            # (b,ck,h,d), (b,ck,h,d), scalar
            sc = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32) * scale
            sc = _shard(cfg, sc, BATCH_AXES, "model", None, None)
            k_pos = ik * ck + jnp.arange(ck)
            mask = k_pos[None, :] < s
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            if window is not None:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            sc = jnp.where(mask, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhqk,bkhd->bhqd", p.astype(dt),
                                           vc).astype(jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((b, h, cq, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, h, cq, 1), jnp.float32),
                jnp.zeros((b, h, cq, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (kb, vb, jnp.arange(nk)), unroll=unroll_k)
        out = (acc / jnp.maximum(l, 1e-30)).astype(dt)  # (b,h,cq,d)
        return None, jnp.moveaxis(out, 1, 2)            # (b,cq,h,d)

    _, blocks = jax.lax.scan(q_block, None,
                             (jnp.moveaxis(qb, 1, 0), jnp.arange(nq)),
                             unroll=unroll_q)
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, nq * cq, h * d)
    return out[:, :s]


def _flash_mha(q, k, v, cfg: ModelConfig):
    """Pallas flash-attention twin of the full-seq einsum/chunked paths.

    q: (B,S,H,Dh), k/v: (B,S,KV,Dh) post-RoPE — the kernel repeats KV heads
    internally (GQA) and applies causal/sliding-window masks by absolute row
    index, which matches the reference `_attn_mask` because every full-seq
    call site passes positions == arange(S). Returns (B,S,H*Dh).
    """
    from repro.kernels.ops import flash_attention_op
    b, s, h, d = q.shape
    out = flash_attention_op(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=cfg.is_autoregressive, window=cfg.sliding_window)
    return jnp.moveaxis(out, 1, 2).reshape(b, s, h * d)


def _mha_core(q, k, v, positions, cfg: ModelConfig):
    """Head-parallel attention core: q,k,v all (B,S,H,Dh), H sharded over
    "model" in shard_activations mode (the classic TP layout — attention math
    is then fully local per head-shard; GQA KV heads are repeated to H, which
    XLA keeps sharded so the repeat is free per device)."""
    dt = q.dtype
    b, sq = q.shape[0], q.shape[1]
    q = _shard(cfg, q, BATCH_AXES, None, "model", None)
    k = _shard(cfg, k, BATCH_AXES, None, "model", None)
    v = _shard(cfg, v, BATCH_AXES, None, "model", None)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    scores = _shard(cfg, scores, BATCH_AXES, "model", None, None)
    mask = _attn_mask(positions, positions,
                      causal=cfg.is_autoregressive, window=cfg.sliding_window)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out.reshape(b, sq, -1)


def gqa_attention(p, x, positions, cfg: ModelConfig):
    """Full-sequence attention (training / prefill). x: (B,S,D)."""
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    g = cfg.n_heads // cfg.n_kv_heads
    if kernel_impl(cfg, "attention") == "kernel":
        out = _flash_mha(q, k, v, cfg)
        return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    if cfg.attn_impl == "chunked":
        out = chunked_mha(q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), cfg)
        return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    if cfg.shard_activations:
        # head-parallel core (repeat KV to H; stays sharded per device)
        out = _mha_core(q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
                        positions, cfg)
        return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    q = q.reshape(b, s, cfg.n_kv_heads, g, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    mask = _attn_mask(positions, positions,
                      causal=cfg.is_autoregressive, window=cfg.sliding_window)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))


# --- GQA decode (KV cache) ---------------------------------------------------
def init_kv_cache_shape(cfg: ModelConfig, batch: int, seq_len: int):
    """Per-layer cache shape (no allocation): (B, S_cache, KV, Dh)."""
    s_cache = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    if cfg.use_mla:
        return (batch, s_cache, cfg.kv_lora_rank + cfg.qk_rope_dim)
    return (batch, s_cache, cfg.n_kv_heads, cfg.head_dim)


def _write_entry(cache, entry, layer, slot):
    """Write the new token's ``entry`` (B, ...) into ``layer`` of a
    layer-stacked cache (L,B,Sc,...) at sequence slot ``slot``: a scalar,
    or (B,) with one slot per row, where a row whose slot is out of range is
    dropped by the scatter rather than corrupting a neighbour (callers clamp
    idle rows)."""
    entry = entry.astype(cache.dtype)
    if jnp.ndim(slot):
        rows = jnp.arange(entry.shape[0])
        return cache.at[layer, rows, slot].set(entry, mode="drop")
    entry = entry.reshape((1, entry.shape[0], 1) + entry.shape[1:])
    return jax.lax.dynamic_update_slice(
        cache, entry, (layer, 0, slot) + (0,) * (entry.ndim - 3))


def _layer_of(cache, layer):
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def gqa_decode(p, x, k_cache, v_cache, pos, layer, cfg: ModelConfig):
    """One-token decode of layer ``layer`` (int32 scalar). x: (B,1,D);
    caches: the whole layer stack (L,B,Sc,KV,Dh) that a decode scan carries;
    pos: scalar int32 current position, or an (B,) int32 vector giving each
    batch row its own position (continuous batching: every slot decodes at
    its own offset, masked independently). Returns (out, new_k_cache,
    new_v_cache). For SWA the cache is a ring buffer of width
    ``sliding_window``.

    The new row is written into the stack first and the layer's slice read
    back after, so a scan that carries donated caches updates them in place
    rather than copying the layer out.
    """
    b = x.shape[0]
    dt = x.dtype
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim > 0
    positions = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    s_cache = k_cache.shape[2]
    slot = pos % s_cache if cfg.sliding_window else pos
    k_cache = _write_entry(k_cache, k[:, 0], layer, slot)
    v_cache = _write_entry(v_cache, v[:, 0], layer, slot)
    k_cache = _shard(cfg, k_cache, None, BATCH_AXES, "model", None, None)
    v_cache = _shard(cfg, v_cache, None, BATCH_AXES, "model", None, None)
    kc, vc = _layer_of(k_cache, layer), _layer_of(v_cache, layer)
    # positions held in each cache slot, per batch row when pos is a vector
    idx = jnp.arange(s_cache)
    row_pos = pos[:, None] if per_row else pos  # (B,1) | scalar
    if cfg.sliding_window:
        # ring: slot i holds position p such that p % Sc == i and p <= pos;
        # slots for positions < 0 have never been written -> masked out.
        k_pos = row_pos - (row_pos % s_cache - idx) % s_cache
    else:
        k_pos = jnp.broadcast_to(idx, (b, s_cache)) if per_row else idx
    valid = (k_pos <= row_pos) & (k_pos >= 0)   # (B,Sc) | (Sc,)
    if not per_row:
        valid = jnp.broadcast_to(valid, (b, s_cache))
    g = cfg.n_heads // cfg.n_kv_heads
    qh = q.reshape(b, 1, cfg.n_kv_heads, g, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    # flash-decode layout: scores (B,KV,G,1,S) with the cache SEQ dim sharded
    # over "model"; softmax stats and the output are combined by tiny
    # all-reduces instead of gathering the cache.
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh, kc.astype(dt)).astype(jnp.float32) * scale
    scores = _shard(cfg, scores, BATCH_AXES, None, None, None, "model")
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, vc.astype(dt))
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    return out, k_cache, v_cache


def paged_gqa_decode(p, x, k_pool, v_pool, tables, pos, bids, offs,
                     cfg: ModelConfig, interpret: bool = False):
    """One-token decode against a block-paged KV pool (single layer), using
    the Pallas paged-attention kernel. x: (B,1,D); k_pool/v_pool:
    (NB,BS,KV,Dh) physical blocks; tables: (B,MAXB) int32 per-row block
    tables; pos: (B,) int32 position of the incoming token; bids/offs: (B,)
    int32 physical slot (block id, in-block offset) where this token's K/V
    must land (reserved by the block allocator — the kernel then sees
    ``context_lens = pos + 1`` valid positions). Returns
    (out, k_pool, v_pool).
    """
    from repro.kernels.paged_attention import paged_attention
    b = x.shape[0]
    dt = x.dtype
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k_pool = k_pool.at[bids, offs].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[bids, offs].set(v[:, 0].astype(v_pool.dtype))
    out = paged_attention(q[:, 0], k_pool, v_pool, tables, pos + 1,
                          interpret=interpret)         # (B,H,Dh)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(dt)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt)), k_pool, v_pool


# --- MLA ---------------------------------------------------------------------
def _mla_q(p, x, positions, cfg: ModelConfig):
    b, s, _ = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    q = q.reshape(b, s, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attention(p, x, positions, cfg: ModelConfig):
    """Full-sequence MLA (training / prefill). Decompressed formulation."""
    b, s, _ = x.shape
    dt = x.dtype
    q_nope, q_rope = _mla_q(p, x, positions, cfg)
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt))
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_krope"].astype(dt))
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,rope)
    k_nope = jnp.einsum("bsr,rh->bsh", c_kv, p["w_uk"].astype(dt)).reshape(
        b, s, cfg.n_heads, cfg.qk_nope_dim)
    v = jnp.einsum("bsr,rh->bsh", c_kv, p["w_uv"].astype(dt)).reshape(
        b, s, cfg.n_heads, cfg.v_head_dim)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    q_nope = _shard(cfg, q_nope, BATCH_AXES, None, "model", None)
    k_nope = _shard(cfg, k_nope, BATCH_AXES, None, "model", None)
    v = _shard(cfg, v, BATCH_AXES, None, "model", None)
    scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope[:, :, 0])
              ).astype(jnp.float32) * scale
    scores = _shard(cfg, scores, BATCH_AXES, "model", None, None)
    mask = _attn_mask(positions, positions, causal=True, window=None)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    out = out.reshape(b, s, cfg.n_heads * cfg.v_head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))


def mla_decode(p, x, c_cache, pos, layer, cfg: ModelConfig):
    """Absorbed-matrix MLA decode of layer ``layer`` over a compressed cache.
    ``pos`` is a scalar int32 or an (B,) per-row position vector (continuous
    batching).

    Cache layout: the layer stack (L, B, S, kv_lora_rank + qk_rope_dim) —
    c_kv ++ rope'd k_rope — written and read at ``layer`` and returned
    whole, as in :func:`gqa_decode`. The up-projections are absorbed into
    the query/output paths so decode cost is O(S * (r + rope)) per head,
    which is the MLA deployment trick.
    """
    b = x.shape[0]
    dt = x.dtype
    r = cfg.kv_lora_rank
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim > 0
    positions = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, positions, cfg)  # (B,1,H,*)
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt))
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_krope"].astype(dt))
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    entry = jnp.concatenate([c_kv, k_rope], axis=-1)
    c_cache = _write_entry(c_cache, entry[:, 0], layer, pos)
    c_cache = _shard(cfg, c_cache, None, BATCH_AXES, "model", None)
    cc = _layer_of(c_cache, layer)
    cache_c = cc[..., :r].astype(dt)      # (B,S,r)
    cache_rope = cc[..., r:].astype(dt)   # (B,S,rope)
    # absorb W_uk into q: (B,1,H,nope) @ (r, H*nope) -> (B,1,H,r)
    w_uk = p["w_uk"].astype(dt).reshape(r, cfg.n_heads, cfg.qk_nope_dim)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs, cache_c)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, cache_rope)).astype(jnp.float32) * scale
    scores = _shard(cfg, scores, BATCH_AXES, None, None, "model")
    valid = jnp.arange(cc.shape[1]) <= (pos[:, None] if per_row else pos)
    valid = jnp.broadcast_to(valid, (b, cc.shape[1]))
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = jnp.einsum("bhqs,bsr->bqhr", w, cache_c)  # (B,1,H,r)
    w_uv = p["w_uv"].astype(dt).reshape(r, cfg.n_heads, cfg.v_head_dim)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv)
    out = out.reshape(b, 1, cfg.n_heads * cfg.v_head_dim)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt)), c_cache


def attention(p, x, positions, cfg: ModelConfig):
    if cfg.use_mla:
        return mla_attention(p, x, positions, cfg)
    return gqa_attention(p, x, positions, cfg)


# --- prefill variants (single QKV computation, cache emitted) -----------------
def gqa_prefill(p, x, positions, cfg: ModelConfig):
    """Full-seq attention that also returns (k, v) for the cache. x: (B,S,D)."""
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    g = cfg.n_heads // cfg.n_kv_heads
    if kernel_impl(cfg, "attention") == "kernel":
        out = _flash_mha(q, k, v, cfg)
        out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
        if cfg.sliding_window:
            k, v = k[:, -cfg.sliding_window:], v[:, -cfg.sliding_window:]
        return out, k, v
    if cfg.attn_impl == "chunked" or cfg.shard_activations:
        if cfg.attn_impl == "chunked":
            out = chunked_mha(q, jnp.repeat(k, g, axis=2),
                              jnp.repeat(v, g, axis=2), cfg)
        else:
            out = _mha_core(q, jnp.repeat(k, g, axis=2),
                            jnp.repeat(v, g, axis=2), positions, cfg)
        out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
        if cfg.sliding_window:
            k, v = k[:, -cfg.sliding_window:], v[:, -cfg.sliding_window:]
        return out, k, v
    qh = q.reshape(b, s, cfg.n_kv_heads, g, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh, k).astype(jnp.float32) * scale
    mask = _attn_mask(positions, positions,
                      causal=cfg.is_autoregressive, window=cfg.sliding_window)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    if cfg.sliding_window:
        k, v = k[:, -cfg.sliding_window:], v[:, -cfg.sliding_window:]
    return out, k, v


def mla_prefill(p, x, positions, cfg: ModelConfig):
    """MLA attention returning the compressed cache entries (B,S,r+rope)."""
    b, s, _ = x.shape
    dt = x.dtype
    q_nope, q_rope = _mla_q(p, x, positions, cfg)
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt))
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_krope"].astype(dt))
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    k_nope = jnp.einsum("bsr,rh->bsh", c_kv, p["w_uk"].astype(dt)).reshape(
        b, s, cfg.n_heads, cfg.qk_nope_dim)
    v = jnp.einsum("bsr,rh->bsh", c_kv, p["w_uv"].astype(dt)).reshape(
        b, s, cfg.n_heads, cfg.v_head_dim)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    q_nope = _shard(cfg, q_nope, BATCH_AXES, None, "model", None)
    k_nope = _shard(cfg, k_nope, BATCH_AXES, None, "model", None)
    v = _shard(cfg, v, BATCH_AXES, None, "model", None)
    scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope)
              ).astype(jnp.float32) * scale
    scores = _shard(cfg, scores, BATCH_AXES, "model", None, None)
    mask = _attn_mask(positions, positions, causal=True, window=None)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v).reshape(b, s, cfg.n_heads * cfg.v_head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dt))
    cache = jnp.concatenate([c_kv, k_rope], axis=-1)
    return out, cache
