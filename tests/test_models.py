"""Model behaviour tests: decode==forward consistency, MoE impl equivalence,
SSD chunked==recurrent, MLA absorption, SWA ring cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import decode_step, forward, init_cache, init_params, prefill
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models.frontends import make_batch

pytestmark = pytest.mark.slow  # JAX tier: excluded from the fast core-sim run

S, EXTRA, B = 64, 4, 2


def _graft(full, pre):
    """Embed a prefill cache (seq dim S) into a zeroed full cache (S+EXTRA)."""
    def g(z, c):
        if z.shape == c.shape:
            return c.astype(z.dtype)
        ax = [i for i, (a, b) in enumerate(zip(z.shape, c.shape)) if a != b]
        assert len(ax) == 1, (z.shape, c.shape)
        pad = [(0, 0)] * z.ndim
        pad[ax[0]] = (0, z.shape[ax[0]] - c.shape[ax[0]])
        return jnp.pad(c.astype(z.dtype), pad)
    return jax.tree.map(g, full, pre)


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if a != "hubert-xlarge"])
def test_decode_matches_forward(arch):
    """prefill(S) + decode(EXTRA) must reproduce full-forward logits."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    batch = make_batch(rng, cfg, batch=B, seq_len=S + EXTRA, with_labels=False)
    logits_full, _ = forward(params, batch, cfg)
    f = cfg.frontend_seq if cfg.frontend == "vision" else 0
    if f:
        pre = {"tokens": batch["tokens"][:, :S - f], "vision_embeds": batch["vision_embeds"]}
        toks = batch["tokens"][:, S - f:]
    else:
        pre = {"tokens": batch["tokens"][:, :S]}
        toks = batch["tokens"][:, S:]
    _, cache = prefill(params, pre, cfg)
    cache = _graft(init_cache(cfg, B, S + EXTRA), cache)
    for i in range(EXTRA):
        pos = S + i
        lg, cache = decode_step(params, toks[:, i:i + 1], cache, jnp.int32(pos), cfg)
        np.testing.assert_allclose(lg, logits_full[:, pos - f], atol=2e-4, rtol=2e-3)


def test_moe_impls_agree():
    """dense / scatter / ragged dispatch agree when nothing is dropped."""
    cfg = dataclasses.replace(get_config("mixtral-8x22b", smoke=True),
                              dtype="float32", capacity_factor=8.0)
    rng = jax.random.PRNGKey(3)
    p = M.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model), jnp.float32)
    outs = {}
    for impl in ("dense", "scatter", "ragged"):
        y, aux = M.apply_moe(p, x, cfg, impl=impl)
        outs[impl] = y
        assert jnp.all(jnp.isfinite(y))
    np.testing.assert_allclose(outs["dense"], outs["scatter"], atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(outs["dense"], outs["ragged"], atol=1e-5, rtol=1e-4)


def test_moe_scatter_drops_at_low_capacity():
    """With capacity_factor << 1 the scatter impl must drop (not corrupt)."""
    cfg = dataclasses.replace(get_config("mixtral-8x22b", smoke=True),
                              dtype="float32", capacity_factor=0.05)
    rng = jax.random.PRNGKey(3)
    p = M.init_moe(rng, cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 64, cfg.d_model), jnp.float32)
    y, _ = M.apply_moe(p, x, cfg, impl="scatter")
    assert jnp.all(jnp.isfinite(y))


def test_ssd_chunked_equals_stepwise():
    """Chunked SSD == naive per-step recurrence."""
    bsz, s, h, pdim, g, n, chunk = 2, 32, 4, 8, 2, 8, 8
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (bsz, s, h, pdim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b_mat = jax.random.normal(ks[3], (bsz, s, g, n))
    c_mat = jax.random.normal(ks[4], (bsz, s, g, n))
    y_chunk, final_chunk = SSM.ssd_chunked(x, dt, a, b_mat, c_mat, chunk)
    state = jnp.zeros((bsz, h, pdim, n))
    ys = []
    for t in range(s):
        y_t, state = SSM.ssd_decode_step(state, x[:, t], dt[:, t], a,
                                         b_mat[:, t], c_mat[:, t])
        ys.append(y_t)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_chunk, y_step, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(final_chunk, state, atol=1e-4, rtol=1e-3)


def test_ssd_initial_state_threading():
    """ssd(x, S) == ssd(x[:S/2]) then ssd(x[S/2:], initial_state)."""
    bsz, s, h, pdim, g, n, chunk = 1, 64, 2, 4, 1, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (bsz, s, h, pdim))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b_mat = jax.random.normal(ks[3], (bsz, s, g, n))
    c_mat = jax.random.normal(ks[4], (bsz, s, g, n))
    y_all, f_all = SSM.ssd_chunked(x, dt, a, b_mat, c_mat, chunk)
    half = s // 2
    y1, f1 = SSM.ssd_chunked(x[:, :half], dt[:, :half], a, b_mat[:, :half], c_mat[:, :half], chunk)
    y2, f2 = SSM.ssd_chunked(x[:, half:], dt[:, half:], a, b_mat[:, half:], c_mat[:, half:],
                             chunk, initial_state=f1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_all, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(f2, f_all, atol=1e-4, rtol=1e-3)


def test_mla_decode_absorption():
    """Absorbed-matrix MLA decode == decompressed full attention, per step."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b", smoke=True), dtype="float32")
    p = A.init_attention(jax.random.PRNGKey(1), cfg, None)
    bsz, s = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(2), (bsz, s, cfg.d_model), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (bsz, s))
    out_full = A.mla_attention(p, x, positions, cfg)
    cache = jnp.zeros((1, bsz, s, cfg.kv_lora_rank + cfg.qk_rope_dim), jnp.float32)
    for t in range(s):
        out_t, cache = A.mla_decode(p, x[:, t:t + 1], cache, jnp.int32(t),
                                    jnp.int32(0), cfg)
        np.testing.assert_allclose(out_t, out_full[:, t:t + 1], atol=1e-5, rtol=1e-4)


def test_swa_ring_cache_wraps():
    """Mixtral-style ring cache must equal full attention restricted to the
    window, even after the ring wraps several times."""
    cfg = dataclasses.replace(get_config("mixtral-8x22b", smoke=True), dtype="float32")
    w = cfg.sliding_window
    p = A.init_attention(jax.random.PRNGKey(5), cfg, None)
    bsz, s = 1, 3 * w + 5
    x = jax.random.normal(jax.random.PRNGKey(6), (bsz, s, cfg.d_model), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (bsz, s))
    out_full = A.gqa_attention(p, x, positions, cfg)
    kc = jnp.zeros((1, bsz, w, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    vc = jnp.zeros_like(kc)
    for t in range(s):
        out_t, kc, vc = A.gqa_decode(p, x[:, t:t + 1], kc, vc, jnp.int32(t),
                                     jnp.int32(0), cfg)
        np.testing.assert_allclose(out_t, out_full[:, t:t + 1], atol=1e-5, rtol=1e-4)


def test_encoder_only_is_bidirectional():
    """hubert: flipping a late frame must change logits of an early frame."""
    cfg = dataclasses.replace(get_config("hubert-xlarge", smoke=True), dtype="float32")
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    batch = make_batch(rng, cfg, batch=1, seq_len=32, with_labels=False)
    lg1, _ = forward(params, batch, cfg)
    frames2 = batch["frames"].at[:, -1].set(batch["frames"][:, -1] + 1.0)
    lg2, _ = forward(params, {"frames": frames2}, cfg)
    assert float(jnp.max(jnp.abs(lg1[:, 0] - lg2[:, 0]))) > 1e-6


def test_causal_lm_is_causal():
    """Dense LM: perturbing a late token must NOT change earlier logits."""
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True), dtype="float32")
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    toks = jax.random.randint(rng, (1, 32), 0, cfg.vocab_size)
    lg1, _ = forward(params, {"tokens": toks}, cfg)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab_size)
    lg2, _ = forward(params, {"tokens": toks2}, cfg)
    np.testing.assert_allclose(lg1[:, :-1], lg2[:, :-1], atol=1e-5)
