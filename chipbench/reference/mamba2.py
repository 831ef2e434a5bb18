"""Plain reference of a Mamba2 language model (arXiv:2405.21060): pre-RMSNorm
blocks of in-projection to [z, xBC, dt], a causal depthwise convolution with
SiLU, the selective state-space recurrence run one position at a time,
the D skip, a gated RMSNorm and the out-projection; final RMSNorm and a
(tied) output head.

The recurrence is the definition, not the chunked (SSD) form:
h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T,  y_t = h_t C_t + D x_t.
Everything in float32 at the highest matmul precision; ``quant="fp8"``
rounds every projection's weight and input through fp8 (the control).

``prefill_flops`` and ``decode_flops`` count the FLOPs one model step needs
(``mfu.prefill`` and ``mfu.decode`` read them).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

import counts
from reference.common import (F32, fan_in, head_flops, log_uniform, mm, normal, rms_norm,
                              uniform, vocab_padded)


def _sizes(m):
    di = m["ssm_expand"] * m["d_model"]
    gn = m["ssm_ngroups"] * m["ssm_state"]
    return di, gn, di // m["ssm_headdim"], di + 2 * gn


def _dt_bias(k, s):
    # softplus^-1 of a step drawn log-uniformly in [1e-3, 1e-1]
    dt = log_uniform(1e-3, 1e-1)(k, s)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_spec(m: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes and draws, in the program's parameter layout."""
    if m["family"] != "ssm":
        raise ValueError(f"mamba2 reference covers ssm models, not {m['family']!r}")
    n, d, w = m["n_layers"], m["d_model"], m["d_conv"]
    di, gn, h, cd = _sizes(m)
    spec = {
        "embed": {"tokens": ((vocab_padded(m), d), normal(0.02))},
        "stack": {"ssm": {
            "ln": {"w": ((n, d), uniform(0.8, 1.2))},
            "mixer": {
                "in_proj": ((n, d, 2 * di + 2 * gn + h), fan_in(d)),
                "conv_w": ((n, cd, w), fan_in(w)),
                "conv_b": ((n, cd), normal(0.05)),
                "A_log": ((n, h), lambda k, s: jnp.log(uniform(1.0, 16.0)(k, s))),
                "dt_bias": ((n, h), _dt_bias),
                "D_skip": ((n, h), uniform(0.5, 1.5)),
                "norm_w": ((n, di), uniform(0.8, 1.2)),
                "out_proj": ((n, di, d), fan_in(di)),
            }}},
        "final_norm": {"w": ((d,), uniform(0.8, 1.2))},
    }
    if not m.get("tie_embeddings", False):
        spec["lm_head"] = ((d, vocab_padded(m)), fan_in(d))
    return spec


def embed(params, tokens, m: Dict[str, Any]):
    """tokens (S,) -> the first layer's input (S, D), in float32."""
    return params["embed"]["tokens"][tokens].astype(F32)


def layers(stack, x, m: Dict[str, Any], quant=None):
    """The layers of ``stack`` (``params["stack"]`` or a run of its layers,
    each leaf's leading axis) over x (S, D)."""
    eps = m.get("norm_eps", 1e-5)
    di, gn, h, cd = _sizes(m)
    p_, n_, g_, w_ = m["ssm_headdim"], m["ssm_state"], m["ssm_ngroups"], m["d_conv"]
    s = x.shape[0]

    def layer(x, lp):
        mp = {k: v.astype(F32) for k, v in lp["mixer"].items()}
        zxbcdt = mm(rms_norm(x, lp["ln"]["w"], eps), lp["mixer"]["in_proj"], quant)
        z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cd], zxbcdt[:, di + cd:]
        # causal depthwise conv: weight column j meets input t - (w - 1) + j
        xp = jnp.pad(xbc, ((w_ - 1, 0), (0, 0)))
        conv = sum(xp[j:j + s] * mp["conv_w"][:, j] for j in range(w_)) + mp["conv_b"]
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :di].reshape(s, h, p_)
        b = jnp.repeat(xbc[:, di:di + gn].reshape(s, g_, n_), h // g_, axis=1)
        c = jnp.repeat(xbc[:, di + gn:].reshape(s, g_, n_), h // g_, axis=1)
        dt = jax.nn.softplus(dt + mp["dt_bias"])            # (S, H)
        a = -jnp.exp(mp["A_log"])                            # (H,)

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp
            state = (state * jnp.exp(dt_t * a)[:, None, None]
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return state, jnp.sum(state * c_t[:, None, :], axis=-1)

        _, y = jax.lax.scan(step, jnp.zeros((h, p_, n_), F32), (xs, b, c, dt))
        y = (y + xs * mp["D_skip"][None, :, None]).reshape(s, di)
        y = rms_norm(y * jax.nn.silu(z), mp["norm_w"], eps)
        return x + mm(y, lp["mixer"]["out_proj"], quant), None

    x, _ = jax.lax.scan(layer, x, stack["ssm"])
    return x


def final(params, x, m: Dict[str, Any]):
    """The last layer's output -> final-normed hidden states (S, D)."""
    return rms_norm(x, params["final_norm"]["w"], m.get("norm_eps", 1e-5))


def _per_token(m):
    d, w = m["d_model"], m["d_conv"]
    di, gn, h, cd = _sizes(m)
    return m["n_layers"] * 2 * (d * (2 * di + 2 * gn + h) + cd * w + di * d)


def prefill_flops(m: Dict[str, Any], length: int) -> float:
    """One prompt of ``length`` through the model; logits at the last position."""
    return (length * _per_token(m)
            + m["n_layers"] * counts.ssd_flops(m, length) + head_flops(m))


def decode_flops(m: Dict[str, Any], context: int) -> float:
    """One token of one sequence; the recurrent state's work does not grow
    with ``context``."""
    h = _sizes(m)[2]
    # state decay, the dt x B^T update and the C contraction
    return (_per_token(m) + m["n_layers"] * 5 * h * m["ssm_headdim"] * m["ssm_state"]
            + head_flops(m))
