"""Compile the main-path kernels and the qwen2.5-3b serving programs for a
described TPU v5e, at published widths, without a chip.

Interpret-mode parity (tests/test_kernels.py) cannot refuse a block shape the
chip's tiling rules forbid or a kernel that overruns fast memory; the TPU
compiler can, and it is installed even where no chip is attached. Nothing
runs here: each test lowers shapes for one device of a ``v5e:2x2`` topology
and asserts the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture: only one process at a time may
load the TPU library, so describing it while the module is imported would
make parallel test workers collect different tests. The persistent
compilation cache stays off in this file (a compile for a described chip
cannot be read back).
"""
import dataclasses
import functools

import pytest

pytestmark = pytest.mark.slow

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Model code picks interpret mode from the backend, which is the CPU
    here; steer it to the compiled kernels for the test's duration."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    ops._default_interpret.cache_clear()
    yield
    ops._default_interpret.cache_clear()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash(s, length=256):
    from repro.kernels.flash_attention import flash_attention
    bf = jnp.bfloat16    # qwen2.5-3b prefill: 16 query / 2 KV heads of 128
    return (lambda q, k, v: flash_attention(q, k, v, causal=True),
            s((1, 16, length, 128), bf), s((1, 2, length, 128), bf),
            s((1, 2, length, 128), bf))


def _flash_1024(s):
    return _flash(s, 1024)


def _flash_2048(s):
    return _flash(s, 2048)


def _rmsnorm(s):
    from repro.kernels.rmsnorm import rmsnorm
    return (lambda x, w: rmsnorm(x, w),            # qwen2.5-3b d_model
            s((1, 200, 2048), jnp.bfloat16), s((2048,), jnp.bfloat16))


def _moe_gmm(s):
    from repro.kernels.moe_gmm import moe_gmm
    bf = jnp.bfloat16    # deepseek-v2-lite: 64 experts, d 2048, expert ff 1408
    return (lambda lhs, rhs, te: moe_gmm(lhs, rhs, te),
            s((1024, 2048), bf), s((64, 2048, 1408), bf),
            s((8,), jnp.int32))


def _paged(s):
    from repro.kernels.paged_attention import paged_attention
    bf = jnp.bfloat16    # qwen2.5-3b decode, 4 slots, 16-token blocks
    return (lambda q, k, v, t, n: paged_attention(q, k, v, t, n),
            s((4, 16, 128), bf), s((64, 16, 2, 128), bf),
            s((64, 16, 2, 128), bf), s((4, 16), jnp.int32),
            s((4,), jnp.int32))


def _ssd(s):
    from repro.kernels.ssd import ssd
    bf = jnp.bfloat16    # mamba2-2.7b: 80 heads x 64, state 128, 1 group
    return (lambda x, dt, a, b, c: ssd(x, dt, a, b, c, chunk=256),
            s((1, 512, 80, 64), bf), s((1, 512, 80), bf),
            s((80,), jnp.float32), s((1, 512, 1, 128), bf),
            s((1, 512, 1, 128), bf))


@pytest.mark.parametrize("case", [_flash, _rmsnorm, _moe_gmm, _paged, _ssd,
                                  _flash_1024, _flash_2048],
                         ids=["flash", "rmsnorm", "moe_gmm", "paged", "ssd",
                              "flash_1024", "flash_2048"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, *args = case(functools.partial(_sds, one_chip))
    assert "tpu_custom_call" in _compile_text(fn, *args)


@pytest.fixture(scope="module")
def qwen_full(one_chip):
    """qwen2.5-3b FULL with bf16 parameter storage and the "auto" kernel
    policy, as parameter shapes placed on the described chip."""
    from repro.configs import get_config
    from repro.configs.base import with_kernel_impls
    from repro.models import model as M
    cfg = with_kernel_impls(
        dataclasses.replace(get_config("qwen2.5-3b"), param_dtype="bfloat16"),
        "auto")
    shapes = jax.eval_shape(functools.partial(M.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda l: _sds(one_chip, l.shape, l.dtype), shapes)
    return cfg, params


def test_qwen_full_prefill_compiles_with_kernels(one_chip, qwen_full,
                                                 compiled_kernels):
    from repro.models import model as M
    cfg, params = qwen_full
    batch = {"tokens": _sds(one_chip, (1, 200), jnp.int32)}
    text = _compile_text(functools.partial(M.prefill, cfg=cfg), params, batch)
    assert "tpu_custom_call" in text


def test_qwen_full_decode_step_compiles_with_kernels(one_chip, qwen_full,
                                                     compiled_kernels):
    from repro.models import model as M
    cfg, params = qwen_full
    cache = jax.tree.map(lambda l: _sds(one_chip, l.shape, l.dtype),
                         M.cache_spec(cfg, 4, 256))
    text = _compile_text(functools.partial(M.decode_step, cfg=cfg), params,
                         _sds(one_chip, (4, 1), jnp.int32), cache,
                         _sds(one_chip, (4,), jnp.int32))
    assert "tpu_custom_call" in text


def test_qwen_full_decode_step_updates_cache_in_place(one_chip, qwen_full,
                                                      compiled_kernels):
    """At a 64-slot, 768-position wave the donated cache is aliased whole to
    the output, and the program holds no second copy of any of it, not even
    one layer's K slice: each layer writes its row into the stacked cache
    its scan carries, rather than into a fresh stacked result."""
    from repro.models import model as M
    cfg, params = qwen_full
    slots, max_seq = 64, 768
    spec = M.cache_spec(cfg, slots, max_seq)
    cache = jax.tree.map(lambda l: _sds(one_chip, l.shape, l.dtype), spec)
    mem = jax.jit(functools.partial(M.decode_step, cfg=cfg),
                  donate_argnums=(2,)).lower(
        params, _sds(one_chip, (slots, 1), jnp.int32), cache,
        _sds(one_chip, (slots,), jnp.int32)).compile().memory_analysis()
    cache_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(spec))
    layer_k_bytes = slots * max_seq * cfg.n_kv_heads * cfg.head_dim * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < layer_k_bytes
