"""Per-kernel validation: sweep shapes/dtypes and assert_allclose against the
ref.py pure-jnp oracles (interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (default_tiles, flash_attention,
                                           kv_block, kv_range)
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ops import moe_gmm_capacity, tile_experts_for_capacity
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd import ssd

pytestmark = pytest.mark.slow  # JAX tier: excluded from the fast core-sim run

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=5e-5, rtol=5e-4)


# --- flash attention ---------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,sq,sk,d,causal,window", [
    (2, 4, 2, 256, 256, 64, True, None),      # GQA causal
    (1, 4, 4, 128, 128, 128, False, None),    # MHA bidirectional (hubert)
    (1, 8, 2, 384, 384, 64, True, 128),       # sliding window (mixtral)
    (2, 2, 1, 100, 100, 32, True, None),      # non-multiple seq (padding path)
    (1, 16, 8, 128, 128, 128, True, None),    # internlm2-like head geometry
    (1, 2, 2, 512, 512, 80, True, None),      # zamba2 head_dim=80
    (1, 16, 2, 1000, 1000, 128, True, None),  # qwen2.5-3b, no tile multiple
    (1, 16, 2, 1537, 1537, 128, True, None),  # qwen2.5-3b, odd length
    (1, 16, 2, 1000, 1000, 128, True, 300),   # G=8 with a sliding window
])
def test_flash_attention_matches_ref(b, h, kv, sq, sk, d, causal, window, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, sk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out.astype(np.float32), exp.astype(np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [1, 64, 128, 256, 384])
def test_flash_attention_sliding_window_edges(window):
    """Window extremes: 1 (self only), block-boundary, == seq (full causal),
    > seq (degenerates to full causal)."""
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, exp, atol=5e-5, rtol=5e-4)
    if window >= 256:   # window covering the whole sequence == plain causal
        full = ref.flash_attention_ref(q, k, v, causal=True, window=None)
        np.testing.assert_allclose(out, full, atol=5e-5, rtol=5e-4)


def test_flash_attention_block_shape_invariance():
    """Same math regardless of block tiling choice."""
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256),
                           (None, None)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("h,kv,s,causal,window", [
    (16, 2, 1000, True, None),     # qwen2.5-3b prefill, no tile multiple
    (16, 2, 777, True, 300),       # G=8 with a sliding window
    (4, 4, 300, False, None),      # bidirectional, padded keys
])
def test_flash_attention_default_tiles_match_small_tiles(h, kv, s, causal,
                                                         window):
    """The shape-chosen tiles compute what explicit small tiles do."""
    assert default_tiles(s, s, h // kv) != (64, 128)
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, h, s, 32))
    k = jax.random.normal(ks[1], (1, kv, s, 32))
    v = jax.random.normal(ks[2], (1, kv, s, 32))
    small = flash_attention(q, k, v, causal=causal, window=window,
                            block_q=64, block_k=128, interpret=True)
    auto = flash_attention(q, k, v, causal=causal, window=window,
                           interpret=True)
    np.testing.assert_allclose(auto, small, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("sq,bq,bk,causal,window", [
    (1000, 256, 512, True, None),
    (1537, 224, 512, True, None),
    (2048, 128, 256, True, 300),
    (2048, 256, 128, True, 1000),
    (1024, 64, 128, False, 200),
])
def test_flash_attention_skipped_steps_fetch_nothing(sq, bq, bk, causal,
                                                     window):
    """Walk the grid in its order: a step that computes gets its own k
    block, and a skipped step repeats the block index of the step before
    it, so the pipeline issues no DMA for it."""
    nq, nk = -(-sq // bq), -(-sq // bk)
    kw = dict(block_q=bq, block_k=bk, nk=nk, causal=causal, window=window)
    prev, skipped = None, 0
    for iq in range(nq):
        lo, hi = (int(x) for x in kv_range(iq, **kw))
        for ik in range(nk):
            idx = int(kv_block(iq, ik, **kw))
            if lo <= ik <= hi:
                assert idx == ik, (iq, ik, idx)
            else:
                skipped += 1
                assert idx == prev, (iq, ik, idx, prev)
            prev = idx
    assert skipped > 0


# --- rmsnorm -------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64, 256), (1, 7, 512), (128, 128), (3, 100, 80)])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(RNG, shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], jnp.float32)
    out = rmsnorm(x, w, interpret=True)
    exp = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(out.astype(np.float32), exp.astype(np.float32), **_tol(dtype))


# --- ssd -------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 64, 4, 16, 2, 8, 16),
    (1, 128, 8, 64, 1, 32, 32),     # mamba2-like (headdim 64, state big)
    (2, 96, 2, 8, 2, 16, 32),
    (1, 256, 4, 64, 1, 64, 128),    # zamba2-like
])
def test_ssd_matches_ref(b, s, h, p, g, n, chunk, dtype):
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, g, n), dtype)
    cm = jax.random.normal(ks[4], (b, s, g, n), dtype)
    y, fin = ssd(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr, finr = ref.ssd_ref(x, dt, a, bm, cm)
    tol = dict(atol=3e-1, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(y, yr, **tol)
    np.testing.assert_allclose(fin, finr, **tol)


def test_ssd_chunk_invariance():
    """Output must not depend on the chunk size."""
    ks = jax.random.split(RNG, 5)
    b, s, h, p, g, n = 1, 128, 2, 16, 1, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    outs = [ssd(x, dt, a, bm, cm, chunk=c, interpret=True)[0] for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-4, rtol=1e-3)


# --- moe gmm ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d,f,e,bt,bf", [
    (256, 64, 96, 4, 32, 32),
    (512, 128, 128, 8, 64, 128),
    (128, 32, 200, 2, 64, 128),   # F padding path
])
def test_moe_gmm_matches_ref(t, d, f, e, bt, bf, dtype):
    # group sizes: multiples of bt summing to t (kernel contract)
    base = t // bt
    sizes = [bt] * e
    rem = base - e
    sizes[0] += rem * bt // 2 * 0  # keep simple: distribute remainder below
    per = [1] * e
    for i in range(rem):
        per[i % e] += 1
    gs = jnp.array([p * bt for p in per], jnp.int32)
    assert int(gs.sum()) == t
    lhs = jax.random.normal(RNG, (t, d), dtype)
    rhs = jax.random.normal(jax.random.PRNGKey(2), (e, d, f), dtype)
    te = jnp.repeat(jnp.arange(e, dtype=jnp.int32), gs // bt, total_repeat_length=t // bt)
    out = moe_gmm(lhs, rhs, te, block_t=bt, block_f=bf, interpret=True)
    exp = ref.moe_gmm_ref(lhs, rhs, gs)
    np.testing.assert_allclose(out.astype(np.float32), exp.astype(np.float32), **_tol(dtype))


def test_moe_gmm_capacity_buffer():
    """(E,C,D) capacity-buffer wrapper: every expert multiplies its own slab."""
    e, c, d, f = 4, 64, 32, 48
    buf = jax.random.normal(RNG, (e, c, d))
    rhs = jax.random.normal(jax.random.PRNGKey(3), (e, d, f))
    out = moe_gmm_capacity(buf, rhs, block_t=32, block_f=16, interpret=True)
    exp = jnp.einsum("ecd,edf->ecf", buf, rhs)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


def test_tile_experts_map():
    te = tile_experts_for_capacity(3, 128, 64)
    np.testing.assert_array_equal(te, jnp.array([0, 0, 1, 1, 2, 2], jnp.int32))


# --- paged decode attention ----------------------------------------------------
def _paged_case(b, h, kv, d, bs, maxb, lens, dtype):
    """Pool + distinct non-null block tables + ragged context lengths."""
    nb = b * maxb + 1   # block 0 plays the null block: never referenced
    ks = jax.random.split(RNG, 4)
    k_pool = jax.random.normal(ks[0], (nb, bs, kv, d), dtype)
    v_pool = jax.random.normal(ks[1], (nb, bs, kv, d), dtype)
    q = jax.random.normal(ks[2], (b, h, d), dtype)
    perm = jax.random.permutation(ks[3], nb - 1)[:b * maxb] + 1
    tables = perm.reshape(b, maxb).astype(jnp.int32)
    return q, k_pool, v_pool, tables, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,d,bs,maxb,lens", [
    (4, 4, 1, 16, 16, 4, [1, 16, 17, 64]),   # qwen-smoke GQA; block edges
    (2, 4, 4, 32, 8, 3, [5, 24]),            # MHA; full table
    (3, 8, 2, 64, 16, 2, [2, 31, 32]),       # GQA group 4
    (2, 6, 3, 32, 4, 5, [3, 13]),            # odd heads, tiny blocks
])
def test_paged_attention_matches_ref(b, h, kv, d, bs, maxb, lens, dtype):
    from repro.kernels.paged_attention import paged_attention
    q, k_pool, v_pool, tables, lens = _paged_case(b, h, kv, d, bs, maxb,
                                                  lens, dtype)
    out = paged_attention(q, k_pool, v_pool, tables, lens, interpret=True)
    exp = ref.paged_attention_ref(q, k_pool, v_pool, tables, lens)
    np.testing.assert_allclose(out.astype(np.float32), exp.astype(np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("b,h,kv,d,bs,maxb,lens", [
    (4, 4, 1, 16, 16, 4, [1, 16, 17, 64]),
    (2, 4, 4, 32, 8, 3, [5, 24]),
])
def test_paged_attention_matches_dense_flash_ref(b, h, kv, d, bs, maxb, lens):
    """Tri-parity: gathering each row's blocks back into a dense K/V slice
    and running the dense oracle (bidirectional: the whole context is valid
    for a decode query) must agree with the paged kernel."""
    from repro.kernels.paged_attention import paged_attention
    q, k_pool, v_pool, tables, lens = _paged_case(b, h, kv, d, bs, maxb,
                                                  lens, jnp.float32)
    out = paged_attention(q, k_pool, v_pool, tables, lens, interpret=True)
    for i in range(b):
        n = int(lens[i])
        ki = k_pool[tables[i]].reshape(maxb * bs, kv, d)[:n]
        vi = v_pool[tables[i]].reshape(maxb * bs, kv, d)[:n]
        exp = ref.flash_attention_ref(q[i][None, :, None],
                                      ki.transpose(1, 0, 2)[None],
                                      vi.transpose(1, 0, 2)[None],
                                      causal=False)
        np.testing.assert_allclose(out[i][None, :, None], exp,
                                   atol=5e-5, rtol=5e-4)


def test_paged_attention_null_rows_finite():
    """Rows whose table is all padding (inactive batch slots attend to one
    masked position) must produce finite output, not NaN."""
    from repro.kernels.paged_attention import paged_attention
    q, k_pool, v_pool, tables, lens = _paged_case(2, 4, 2, 16, 8, 2, [1, 9],
                                                  jnp.float32)
    out = paged_attention(q, k_pool, v_pool,
                          jnp.zeros_like(tables), jnp.ones_like(lens),
                          interpret=True)
    assert np.isfinite(np.asarray(out)).all()


def test_paged_attention_op_wrapper_defaults():
    from repro.kernels.ops import paged_attention_op
    q, k_pool, v_pool, tables, lens = _paged_case(2, 4, 2, 16, 8, 2, [1, 9],
                                                  jnp.float32)
    out = paged_attention_op(q, k_pool, v_pool, tables, lens)
    exp = ref.paged_attention_ref(q, k_pool, v_pool, tables, lens)
    np.testing.assert_allclose(out, exp, atol=5e-5, rtol=5e-4)


def test_moe_gmm_op_wrapper_defaults():
    """The ops-layer wrapper picks interpret mode from the backend default."""
    from repro.kernels.ops import moe_gmm_op
    t, d, f, e, bt = 64, 16, 24, 2, 32
    lhs = jax.random.normal(RNG, (t, d))
    rhs = jax.random.normal(jax.random.PRNGKey(2), (e, d, f))
    te = jnp.array([0, 1], jnp.int32)
    out = moe_gmm_op(lhs, rhs, te, block_t=bt, block_f=8)
    exp = ref.moe_gmm_ref(lhs, rhs, jnp.array([bt, bt], jnp.int32))
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


def test_moe_gmm_ragged_groups_via_padding():
    """The dropless dispatch recipe: RAGGED group sizes (not multiples of
    block_t) padded with pad_group_sizes, rows scattered to padded offsets,
    per-tile experts from searchsorted — gathered output must equal the
    ragged-oracle per-group matmul."""
    from repro.kernels.ops import pad_group_sizes
    t, d, f, e, bt = 90, 16, 24, 3, 16
    gs = jnp.array([37, 0, 53], jnp.int32)        # ragged + an EMPTY group
    assert int(gs.sum()) == t
    lhs = jax.random.normal(RNG, (t, d))
    rhs = jax.random.normal(jax.random.PRNGKey(2), (e, d, f))
    padded, offs = pad_group_sizes(gs, bt)
    t_pad = int(padded.sum())
    raw_offs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                jnp.cumsum(gs)]).astype(jnp.int32)
    row_e = jnp.repeat(jnp.arange(e, dtype=jnp.int32), gs,
                       total_repeat_length=t)
    dest = jnp.arange(t, dtype=jnp.int32) + (offs[:-1] - raw_offs[:-1])[row_e]
    buf = jnp.zeros((t_pad, d)).at[dest].set(lhs)
    tile_starts = jnp.arange(t_pad // bt, dtype=jnp.int32) * bt
    te = jnp.clip(jnp.searchsorted(offs, tile_starts, side="right") - 1,
                  0, e - 1).astype(jnp.int32)
    out = moe_gmm(buf, rhs, te, block_t=bt, block_f=8, interpret=True)[dest]
    exp = ref.moe_gmm_ref(lhs, rhs, gs)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


# --- dispatch policy plumbing ------------------------------------------------
def test_interpret_env_override(monkeypatch):
    """REPRO_PALLAS_INTERPRET forces interpret mode on/off; junk values name
    the allowed spellings; the default is memoized per process."""
    from repro.kernels import ops
    ops._default_interpret.cache_clear()
    try:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert ops._default_interpret() is True
        ops._default_interpret.cache_clear()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "off")
        assert ops._default_interpret() is False
        ops._default_interpret.cache_clear()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
        with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
            ops._default_interpret()
        ops._default_interpret.cache_clear()
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
        assert ops._default_interpret() == (jax.default_backend() != "tpu")
        # memoized: a later env change without cache_clear is not observed
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
        assert ops._default_interpret() == (jax.default_backend() != "tpu")
    finally:
        ops._default_interpret.cache_clear()
