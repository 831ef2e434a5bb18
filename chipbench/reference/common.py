"""Pieces shared by the plain references: weight generation from a seed,
float32 matrix products (optionally rounded through fp8 for the control),
RMSNorm and next-token scoring.

Nothing here imports the program. The weight tree is laid out the way the
program's ``init_params`` lays it out, so one generator feeds both the
program under test and the reference; the values are this benchmark's own.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def vocab_padded(m: Dict[str, Any]) -> int:
    return -(-m["vocab_size"] // 128) * 128


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits of it."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# Each leaf spec is (shape, draw) where draw(key, shape) -> float32 values.
def normal(std: float) -> Callable:
    return lambda k, s: jax.random.normal(k, s, F32) * std


def fan_in(n: int) -> Callable:
    return lambda k, s: jax.random.truncated_normal(k, -2.0, 2.0, s, F32) * n ** -0.5


def uniform(lo: float, hi: float) -> Callable:
    return lambda k, s: jax.random.uniform(k, s, F32, lo, hi)


def log_uniform(lo: float, hi: float) -> Callable:
    return lambda k, s: jnp.exp(jax.random.uniform(k, s, F32, math.log(lo), math.log(hi)))


def make_params(spec: Dict[str, Any], key, dtype) -> Dict[str, Any]:
    """Materialise a tree of (shape, draw) leaves; leaf i draws from
    ``fold_in(key, i)`` in the tree's flattening order."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and callable(x[1]))
    vals = [draw(jax.random.fold_in(key, i), shape).astype(dtype)
            for i, (shape, draw) in enumerate(leaves)]
    return jax.tree.unflatten(treedef, vals)


def fp8_round(x, axis: int):
    """Round ``x`` through float8_e4m3fn with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, quant=None):
    """x (..., K) @ w (K, N) in float32. ``quant="fp8"`` rounds the
    activation per row and the weight per output column through e4m3 first
    (fp8 weights and activations, the precision step below bfloat16)."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant == "fp8":
        x = fp8_round(x, -1)
        w = fp8_round(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}; allowed: None, 'fp8'")
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def rms_norm(x, w, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def head_weight(params, m):
    if m.get("tie_embeddings", False):
        return params["embed"]["tokens"].T
    return params["lm_head"]


def score_next(params, hidden, targets, m: Dict[str, Any], quant=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per position of ``hidden`` (S, D): the best logit, the logit of
    ``targets`` (S,) and the argmax, over the unpadded vocabulary."""
    w = head_weight(params, m)[:, :m["vocab_size"]]
    logits = mm(hidden, w, quant)
    best = jnp.max(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.clip(targets, 0)[:, None], axis=-1)[:, 0]
    return best, picked, jnp.argmax(logits, axis=-1).astype(jnp.int32)
