"""Jit'd wrappers + integration helpers around the Pallas kernels.

``interpret`` defaults to True on CPU (this container) and False on TPU, so
the same call sites work in tests and on real hardware. The
``REPRO_PALLAS_INTERPRET`` environment variable overrides the backend probe
(``1``/``true`` forces interpret mode, ``0``/``false`` forces compiled
kernels) so CI and tests can pin the mode explicitly.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd import ssd

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


@functools.lru_cache(maxsize=None)
def _default_interpret() -> bool:
    """Memoized: the backend cannot change mid-process, and every kernel
    wrapper consults this at trace time. Tests that flip the env override
    must call ``_default_interpret.cache_clear()``."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        val = env.strip().lower()
        if val in _TRUE:
            return True
        if val in _FALSE:
            return False
        raise ValueError(
            f"REPRO_PALLAS_INTERPRET={env!r} is not a boolean; allowed "
            f"values: {_TRUE + _FALSE}")
    return jax.default_backend() != "tpu"


def default_interpret() -> bool:
    """Public accessor for the interpret-mode default — call sites outside
    ``repro.kernels`` use this; the underscore impl stays the lru_cache
    handle tests clear."""
    return _default_interpret()


def flash_attention_op(q, k, v, *, causal=True, window=None, scale=None,
                       interpret=None):
    return flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                           interpret=_default_interpret() if interpret is None else interpret)


def paged_attention_op(q, k_pool, v_pool, block_tables, context_lens, *,
                       scale=None, interpret=None):
    return paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                           scale=scale,
                           interpret=_default_interpret() if interpret is None else interpret)


def rmsnorm_op(x, w, eps=1e-5, interpret=None):
    return rmsnorm(x, w, eps=eps,
                   interpret=_default_interpret() if interpret is None else interpret)


def ssd_op(x, dt, a, b_mat, c_mat, chunk=128, interpret=None):
    return ssd(x, dt, a, b_mat, c_mat, chunk=chunk,
               interpret=_default_interpret() if interpret is None else interpret)


def moe_gmm_op(lhs, rhs, tile_expert, *, block_t: int = 128,
               block_f: int = 128, interpret=None):
    return moe_gmm(lhs, rhs, tile_expert, block_t=block_t, block_f=block_f,
                   interpret=_default_interpret() if interpret is None else interpret)


def pad_group_sizes(group_sizes, block_t: int):
    """Round each group size up to a multiple of block_t; returns
    (padded_sizes, padded_offsets). Padding rows must be zero-filled by the
    caller so they contribute nothing downstream."""
    padded = (group_sizes + block_t - 1) // block_t * block_t
    offs = jnp.concatenate([jnp.zeros(1, padded.dtype), jnp.cumsum(padded)])
    return padded, offs


def tile_experts_for_capacity(n_experts: int, capacity: int, block_t: int):
    """Tile->expert map for the capacity-padded (E*C, D) dispatch buffer."""
    assert capacity % block_t == 0, (capacity, block_t)
    per = capacity // block_t
    return jnp.repeat(jnp.arange(n_experts, dtype=jnp.int32), per)


def moe_gmm_capacity(buf, rhs, *, block_t: int = 128, block_f: int = 128,
                     interpret=None):
    """Expert matmul over the (E, C, D) capacity dispatch buffer -> (E, C, F)."""
    e, c, d = buf.shape
    block_t = min(block_t, c)
    assert c % block_t == 0, (c, block_t)
    te = tile_experts_for_capacity(e, c, block_t)
    out = moe_gmm(buf.reshape(e * c, d), rhs, te, block_t=block_t,
                  block_f=block_f,
                  interpret=_default_interpret() if interpret is None else interpret)
    return out.reshape(e, c, rhs.shape[2])
