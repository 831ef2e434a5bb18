"""Flash attention (causal / bidirectional, GQA, optional sliding window)
as a Pallas TPU kernel.

TPU mapping (vs the CUDA original): the online-softmax tiling is expressed as
a 4D sequential grid (batch, kv_head, q_block, k_block) — the TPU grid
executes in row-major order, so the (m, l, acc) running statistics live in
VMEM scratch that persists across the innermost k_block axis; no atomics or
shared-memory reductions are needed.

One grid step serves a whole GQA group: the q block holds the G = H / KV
query heads that share a K/V head, stacked into G * block_q rows of one MXU
dot, so each K/V tile is fetched once per (group, q block, k block) rather
than once per query head. Tiles are chosen from the call's shapes (see
``default_tiles``): large enough that the per-step cost is small next to the
step's work, split evenly so padding stays small. Operands enter the MXU in
the dtype they arrive in, accumulating in float32; the softmax statistics
and the output accumulator are float32.

Causally (or window-) masked k blocks are skipped twice over: ``pl.when``
spends no FLOPs on them, and the K/V index map clamps the block index into
the q block's needed range, so a skipped step repeats the block already
resident in VMEM and the pipeline issues no DMA for it. Masks are built only
on tiles that straddle the diagonal, the window's edge or the padded tail.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

# Default tile targets: query rows a step (G * block_q) and keys a step.
TARGET_ROWS = 2048
TARGET_K = 512
Q_ALIGN = 16      # sublane tile of bf16 (f32's 8 divides it)
K_ALIGN = 128     # lane width: keys are the score tile's minor dimension


def _split(n: int, target: int, align: int) -> int:
    """Block size for ``n`` positions: as few blocks as ``target`` allows,
    each the same size rounded up to ``align``, so padding stays under one
    ``align`` a block."""
    nb = pl.cdiv(n, max(target, align))
    return pl.cdiv(pl.cdiv(n, nb), align) * align


def default_tiles(sq: int, sk: int, group: int) -> Tuple[int, int]:
    """(block_q, block_k) for a call with ``sq`` queries, ``sk`` keys and
    ``group`` query heads per K/V head."""
    return (_split(sq, TARGET_ROWS // group, Q_ALIGN),
            _split(sk, TARGET_K, K_ALIGN))


def kv_range(iq, *, block_q: int, block_k: int, nk: int, causal: bool,
             window: Optional[int]):
    """First and last k block that q block ``iq`` attends to (inclusive).
    Works on Python ints and on traced grid indices alike."""
    q_start = iq * block_q
    hi = nk - 1
    if causal:
        hi = jnp.minimum((q_start + block_q - 1) // block_k, hi)
    lo = 0
    if window is not None:
        lo = jnp.maximum(q_start - window + 1, 0) // block_k
        lo = jnp.minimum(lo, hi)
    return lo, hi


def kv_block(iq, ik, **kw):
    """K/V block fetched at grid step (iq, ik). A needed step gets block
    ``ik``; a skipped one repeats the block of the step before it: the
    q block's last needed block after the range, the previous q block's
    last one before it (the pipeline fetches only on a change of index)."""
    lo, hi = kv_range(iq, **kw)
    _, hi_prev = kv_range(iq - 1, **kw)
    return jnp.where(ik < lo, hi_prev, jnp.minimum(ik, hi))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, seq_k: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    group, _, d = acc_scr.shape

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    lo, hi = kv_range(iq, block_q=block_q, block_k=block_k, nk=nk,
                      causal=causal, window=window)
    needed = jnp.logical_and(ik >= lo, ik <= hi)

    def compute(masked: bool):
        q = q_ref[0].reshape(group * block_q, d)
        s = jax.lax.dot_general(q, k_ref[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s.reshape(group, block_q, block_k) * scale
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = k_pos < seq_k
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window is not None:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_scr[...]                            # (G, bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]
        pv = jax.lax.dot(p.reshape(group * block_q, block_k).astype(v.dtype),
                         v, preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv.reshape(group, block_q, d)
        m_scr[...] = m_new

    # A tile needs no mask when every key in it is valid, causally visible to
    # the block's first query and inside the window of its last one.
    clean = True
    if nk * block_k != seq_k:
        clean = k_start + block_k <= seq_k
    if causal:
        clean = jnp.logical_and(clean, k_start + block_k - 1 <= q_start)
    if window is not None:
        clean = jnp.logical_and(clean,
                                k_start > q_start + block_q - 1 - window)
    if clean is True:
        pl.when(needed)(lambda: compute(False))
    else:
        pl.when(jnp.logical_and(needed, clean))(lambda: compute(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(clean)))(
            lambda: compute(True))

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _vmem_bytes(group: int, block_q: int, block_k: int, d: int,
                itemsize: int) -> int:
    """VMEM the kernel asks for: double-buffered q, o, k and v blocks, the
    f32 scratch (m and l padded to a lane row each), and room for a few
    f32 score-sized temporaries."""
    rows = group * block_q
    blocks = 2 * itemsize * (2 * rows * d + 2 * block_k * d)
    scratch = 4 * rows * (2 * 128 + d)
    temps = 4 * 4 * rows * max(block_k, 128)
    return blocks + scratch + temps


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = False):
    """q: (B,H,Sq,D); k,v: (B,KV,Sk,D), H % KV == 0. Returns (B,H,Sq,D).

    ``block_q`` / ``block_k`` default to ``default_tiles`` of the shapes;
    given explicitly they are used as they are (capped at the length).
    Matches ``repro.kernels.ref.flash_attention_ref``.
    """
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = scale if scale is not None else d ** -0.5
    auto_q, auto_k = default_tiles(sq, sk, group)
    block_q = auto_q if block_q is None else min(block_q, sq)
    block_k = auto_k if block_k is None else min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k
    kv_index = functools.partial(kv_block, block_q=block_q, block_k=block_k,
                                 nk=nk, causal=causal, window=window)

    def kv_map(b_, g_, iq, ik):
        return b_, g_, kv_index(iq, ik), 0

    def q_map(b_, g_, iq, ik):
        return b_, g_, iq, 0

    vmem = _vmem_bytes(group, block_q, block_k, d, q.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_k=sk, nk=nk),
        grid=(b, kv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, group, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((group, block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((group, block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=max(vmem + (8 << 20), 32 << 20)),
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :sq]
    return out
