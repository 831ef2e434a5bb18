"""Paged decode attention as a Pallas TPU kernel.

One query token per sequence attends over K/V that live in fixed-size blocks
of a shared pool (``repro.serving.kvcache``), reachable only through the
sequence's block table. The table is passed as a *scalar-prefetch* operand
(:class:`PrefetchScalarGridSpec`), so the k/v BlockSpec index maps read
``tables[b, j]`` and the pipeline DMAs exactly the right physical block per
grid step — the gather costs no extra HBM traffic and the (B, S, KV, D)
dense view is never materialized.

Grid: (batch, block) executed row-major, so the innermost axis walks a
sequence's blocks in order and the online-softmax running stats (m, l, acc)
persist in VMEM scratch, exactly like the flash kernel. Each step DMAs one
whole ``(BS, KV, D)`` block — the trailing ``(KV, D)`` dims equal the pool's,
which is what the TPU's (8, 128) block-tiling rule accepts for any head
count and head dim — and loops over the KV heads inside the kernel, each
handling its whole G = H // KV query-head group (decode has a single query
position, so the group is the natural tile). Blocks fully past
``context_lens[b]`` are skipped via ``pl.when``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, block_size: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    n_kv = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[b]

    @pl.when(j * block_size < ctx)
    def _compute():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)        # (G, D)
            k = k_ref[0, :, h].astype(jnp.float32)     # (BS, D)
            v = v_ref[0, :, h].astype(jnp.float32)     # (BS, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32,
                                                            s.shape, 1)
            s = jnp.where(pos < ctx, s, NEG_INF)       # (G, BS)
            m_prev = m_scr[h]                          # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(p, v)
            m_scr[h] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                    scale: float | None = None, interpret: bool = False):
    """q: (B,H,D); k_pool/v_pool: (NB,BS,KV,D), H % KV == 0;
    block_tables: (B,MAXB) int32; context_lens: (B,) int32 — valid positions
    per sequence including the query token (rows with 0 produce zeros).
    Returns (B,H,D). Matches ``repro.kernels.ref.paged_attention_ref``.
    """
    b, h, d = q.shape
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    maxb = block_tables.shape[1]
    assert h % kv == 0, (h, kv)
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, kv, g, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[
            pl.BlockSpec((1, kv, g, d),
                         lambda b_, j, tables, lens: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bs, kv, d),
                         lambda b_, j, tables, lens: (tables[b_, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, kv, d),
                         lambda b_, j, tables, lens: (tables[b_, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kv, g, d),
                               lambda b_, j, tables, lens: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g, 1), jnp.float32),   # running max m
            pltpu.VMEM((kv, g, 1), jnp.float32),   # running denom l
            pltpu.VMEM((kv, g, d), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qg, k_pool, v_pool)
    return out.reshape(b, h, d)
