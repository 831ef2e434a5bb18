"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

TPU mapping: the GPU reference implementation splits SSD into four separate
kernels (intra-chunk, chunk-state, state-passing, output) joined through HBM.
On TPU we exploit the *sequential* grid: with grid (B, H, n_chunks) the chunk
axis is innermost, so the running inter-chunk state (P, N) lives in VMEM
scratch and is carried across chunk iterations — the whole SSD is ONE kernel
with a single HBM round-trip per chunk. The within-chunk quadratic term
(Q x Q) and the state products are MXU matmuls; Q=chunk is picked so the
(Q,Q) score tile and the (P,N) state fit VMEM comfortably (Q=128..256,
P,N <= 128).

Layout: the wrapper puts every operand head-major — x as (B,H,S,P), dt as
(B,H,S,1), B/C as (B,G,S,N) — so each grid step reads one ``(chunk, P)`` /
``(chunk, N)`` tile whose trailing dims are whole array dims, the form the
TPU's (8, 128) block-tiling rule accepts at any head dim. ``a`` is one f32
scalar per head, read from SMEM. The within-chunk cumulative sum is a
lower-triangular matmul (the TPU lowering has no cumsum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, fin_ref, state_scr,
                *, chunk: int):
    hh = pl.program_id(1)
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (Q, 1)
    b = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)           # (Q, N)

    da = dt * a_ref[hh]                           # (Q, 1)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cs = jax.lax.dot(tri.astype(jnp.float32), da,
                     precision=jax.lax.Precision.HIGHEST)  # (Q, 1) cumsum
    total = jnp.sum(da, axis=0, keepdims=True)    # (1, 1) == cs[-1]
    seg = cs - cs.T                               # (Q, Q)
    ell = jnp.exp(jnp.where(tri, seg, NEG_INF))   # lower-triangular decay
    xdt = x * dt                                  # (Q, P)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ()))) * ell  # (Q, Q)
    y = jax.lax.dot(scores, xdt)                  # (Q, P) within-chunk
    state = state_scr[...]                        # (P, N) entering state
    # off-chunk: y += exp(cs) * (C @ state^T)
    y = y + jnp.exp(cs) * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())))       # (Q,N)x(P,N)->(Q,P)
    # state update: state' = state * exp(sum da) + (xdt * decay)^T @ B
    decay = jnp.exp(total - cs)                   # (Q, 1)
    new_state = state * jnp.exp(total) + jax.lax.dot_general(
        xdt * decay, b, (((0,), (0,)), ((), ())))  # (P, N)
    state_scr[...] = new_state
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _fin():
        fin_ref[0, 0] = new_state.astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b_mat, c_mat, chunk: int = 128, interpret: bool = False):
    """SSD scan. x: (B,S,H,P); dt: (B,S,H) (>=0, already softplus'ed);
    a: (H,) (negative); b_mat/c_mat: (B,S,G,N) with H % G == 0.
    Returns (y (B,S,H,P) fp32, final_state (B,H,P,N) fp32).

    Matches ``repro.kernels.ref.ssd_ref``. S must be a multiple of ``chunk``
    (callers pad with dt=0, which is a state no-op).
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    assert s % chunk == 0 and h % g == 0, (s, chunk, h, g)
    nc = s // chunk
    rep = h // g
    row = lambda b, hh, c, a_: (b, hh, c, 0)       # noqa: E731
    y, fin = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, h, nc),
            in_specs=[
                pl.BlockSpec((1, 1, chunk, p), row),
                pl.BlockSpec((1, 1, chunk, 1), row),
                pl.BlockSpec((1, 1, chunk, n),
                             lambda b, hh, c, a_, r=rep: (b, hh // r, c, 0)),
                pl.BlockSpec((1, 1, chunk, n),
                             lambda b, hh, c, a_, r=rep: (b, hh // r, c, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, chunk, p), row),
                pl.BlockSpec((1, 1, p, n), lambda b, hh, c, a_: (b, hh, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(a.astype(jnp.float32),
      jnp.swapaxes(x, 1, 2),
      jnp.swapaxes(dt, 1, 2)[..., None],
      jnp.swapaxes(b_mat, 1, 2),
      jnp.swapaxes(c_mat, 1, 2))
    return jnp.swapaxes(y, 1, 2), fin
