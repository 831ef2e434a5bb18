"""Plain reference of a dense grouped-query-attention decoder (Qwen2 style):
pre-RMSNorm blocks, QKV with bias, rotary embeddings (split-half), causal
softmax attention with key/value heads shared by groups of query heads,
SwiGLU MLP, final RMSNorm and a (tied) output head.

Full sequence, no cache, no batching, no kernels; everything in float32
at the highest matmul precision. ``quant="fp8"`` rounds every projection's
weight and input through fp8 (the benchmark's lower-precision control).

``prefill_flops`` and ``decode_flops`` count the FLOPs one model step needs
(``mfu.prefill`` and ``mfu.decode`` read them).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

import counts
from reference.common import (F32, HIGHEST, fan_in, head_flops, mm, normal, rms_norm, uniform,
                              vocab_padded)


# q and k are drawn at this multiple of the fan-in scale: scores of standard
# deviation about QK_SCALE**2, so heads attend to a few positions, as trained
# heads do, and a wrong cache entry moves the logits. At 1.0 a random head
# attends almost uniformly over a long prompt and a stale or misplaced cache
# row barely shows; at 2.0 the program's bf16 rounding of decode scores is
# amplified until its gap came within 3.6x of the fp8 control's (TPU v5e),
# where at 1.5 it stays 8.5x below.
QK_SCALE = 1.5


def init_spec(m: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes and draws, in the program's parameter layout."""
    if m["family"] != "dense" or m.get("act", "silu") != "silu":
        raise ValueError(f"gqa reference covers dense SwiGLU models, not {m['family']!r}")
    n, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    qd = m["n_heads"] * m["head_dim"]
    kd = m["n_kv_heads"] * m["head_dim"]
    norm = ((n, d), uniform(0.8, 1.2))
    qk = lambda k, s: QK_SCALE * fan_in(d)(k, s)  # noqa: E731
    attn = {"wq": ((n, d, qd), qk), "wk": ((n, d, kd), qk),
            "wv": ((n, d, kd), fan_in(d)), "wo": ((n, qd, d), fan_in(qd))}
    if m.get("qkv_bias", False):
        attn.update(bq=((n, qd), normal(0.05)), bk=((n, kd), normal(0.05)),
                    bv=((n, kd), normal(0.05)))
    spec = {
        "embed": {"tokens": ((vocab_padded(m), d), normal(0.02))},
        "stack": {"dense": {
            "ln1": {"w": norm}, "attn": attn, "ln2": {"w": norm},
            "mlp": {"w_up": ((n, d, f), fan_in(d)), "w_down": ((n, f, d), fan_in(f)),
                    "w_gate": ((n, d, f), fan_in(d))}}},
        "final_norm": {"w": ((d,), uniform(0.8, 1.2))},
    }
    if not m.get("tie_embeddings", False):
        spec["lm_head"] = ((d, vocab_padded(m)), fan_in(d))
    return spec


def _rope(x, theta: float):
    """x: (S, H, Dh), positions 0..S-1; rotate the two halves."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def embed(params, tokens, m: Dict[str, Any]):
    """tokens (S,) -> the first layer's input (S, D), in float32."""
    return params["embed"]["tokens"][tokens].astype(F32)


def layers(stack, x, m: Dict[str, Any], quant=None):
    """The layers of ``stack`` (``params["stack"]`` or a run of its layers,
    each leaf's leading axis) over x (S, D)."""
    eps = m.get("norm_eps", 1e-5)
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        a = lp["attn"]
        y = rms_norm(x, lp["ln1"]["w"], eps)
        q, k, v = mm(y, a["wq"], quant), mm(y, a["wk"], quant), mm(y, a["wv"], quant)
        if "bq" in a:
            q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), v + a["bv"].astype(F32)
        q = _rope(q.reshape(s, h, dh), m["rope_theta"])
        k = _rope(k.reshape(s, kv, dh), m["rope_theta"])
        v = v.reshape(s, kv, dh)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * dh ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST).reshape(s, h * dh)
        x = x + mm(o, a["wo"], quant)
        y = rms_norm(x, lp["ln2"]["w"], eps)
        p = lp["mlp"]
        g = jax.nn.silu(mm(y, p["w_gate"], quant)) * mm(y, p["w_up"], quant)
        return x + mm(g, p["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, stack["dense"])
    return x


def final(params, x, m: Dict[str, Any]):
    """The last layer's output -> final-normed hidden states (S, D)."""
    return rms_norm(x, params["final_norm"]["w"], m.get("norm_eps", 1e-5))


def _matmul_per_token(m):
    d, f = m["d_model"], m["d_ff"]
    qd = m["n_heads"] * m["head_dim"]
    kd = m["n_kv_heads"] * m["head_dim"]
    return m["n_layers"] * 2 * (d * qd + 2 * d * kd + qd * d + 3 * d * f)


def prefill_flops(m: Dict[str, Any], length: int) -> float:
    """One prompt of ``length`` through the model; logits at the last position."""
    return (length * _matmul_per_token(m)
            + m["n_layers"] * counts.flash_flops(m, length) + head_flops(m))


def decode_flops(m: Dict[str, Any], context: int) -> float:
    """One token of one sequence whose cache holds ``context`` positions,
    the new one included."""
    attn = m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * context
    return _matmul_per_token(m) + attn + head_flops(m)
