"""Substrate tests: optimizer, data pipeline determinism/elasticity,
checkpoint (incl. elastic restore semantics), gradient compression,
sharding rules, serving engine."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config
from repro.data.pipeline import DataPipeline, synth_sequence_rows
from repro.distributed.compression import dequantize, ef_compress, quantize
from repro.distributed.sharding import batch_spec, param_specs
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.serving.batching import GenRequest
from repro.serving.engine import ContinuousEngine
from repro.training.optimizer import (OptimizerConfig, adamw_update,
                                      init_opt_state, schedule)
from repro.training.train_step import make_train_step

pytestmark = pytest.mark.slow  # JAX tier: excluded from the fast core-sim run


# --- optimizer -----------------------------------------------------------------
def test_adamw_reduces_quadratic_loss():
    cfg = OptimizerConfig(lr=0.1, warmup_steps=1, total_steps=200,
                          weight_decay=0.0, grad_clip=100.0)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(grads, opt, params, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(schedule(cfg, jnp.int32(s))) for s in (1, 10, 55, 100)]
    assert lrs[0] < lrs[1]            # warmup
    assert lrs[1] >= lrs[2] >= lrs[3]  # cosine decay
    assert abs(lrs[3] - 0.1) < 0.02


def test_grad_accumulation_matches_full_batch():
    cfg = get_config("internlm2-1.8b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptimizerConfig(lr=1e-3)
    pipe = DataPipeline(cfg, 8, 32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
    p1, _, m1 = make_train_step(cfg, opt_cfg, n_microbatches=1)(
        params, init_opt_state(params), batch)
    p4, _, m4 = make_train_step(cfg, opt_cfg, n_microbatches=4)(
        params, init_opt_state(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


# --- data pipeline ---------------------------------------------------------------
def test_pipeline_deterministic_and_topology_invariant():
    cfg = get_config("internlm2-1.8b", smoke=True)
    full = DataPipeline(cfg, global_batch=8, seq_len=32, seed=1)
    b_full = full.next_batch()
    shards = []
    for rank in range(4):
        p = DataPipeline(cfg, global_batch=8, seq_len=32, seed=1,
                         dp_rank=rank, dp_size=4)
        shards.append(p.next_batch())
    merged = np.concatenate([s["tokens"] for s in shards])
    np.testing.assert_array_equal(merged, b_full["tokens"])


def test_pipeline_resume_from_state():
    cfg = get_config("internlm2-1.8b", smoke=True)
    p1 = DataPipeline(cfg, 4, 16, seed=2)
    batches = [p1.next_batch() for _ in range(5)]
    state = p1.state_dict()
    p2 = DataPipeline(cfg, 4, 16, seed=2)
    p2.load_state_dict({"step": 3})
    np.testing.assert_array_equal(p2.next_batch()["tokens"], batches[3]["tokens"])


def test_synth_data_is_learnable_markov():
    rows = synth_sequence_rows(0, np.arange(64), 128, 64, p_markov=0.8)
    nxt = (rows[:, :-1] * 31 + 7) % 64
    frac = float(np.mean(nxt == rows[:, 1:]))
    assert 0.7 < frac < 0.9  # ~p_markov


# --- checkpoint ----------------------------------------------------------------------
def test_checkpoint_roundtrip_and_latest():
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(tree, d, step=10)
        ckpt.save(jax.tree.map(lambda x: x * 2, tree), d, step=20)
        assert ckpt.latest_step(d) == 20
        template = jax.eval_shape(lambda: tree)
        restored, man = ckpt.restore(template, d)
        assert man["step"] == 20
        np.testing.assert_array_equal(restored["a"], np.asarray(tree["a"]) * 2)


def test_checkpoint_ignores_uncommitted():
    import os
    tree = {"a": jnp.ones(3)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(tree, d, step=1)
        os.makedirs(os.path.join(d, "step_00000002"))  # partial write, no marker
        assert ckpt.latest_step(d) == 1


def test_checkpoint_async():
    tree = {"a": jnp.ones((128, 128))}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(tree, d, step=5, async_save=True)
        ckpt.wait_for_saves()
        assert ckpt.latest_step(d) == 5


# --- compression -----------------------------------------------------------------------
def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,))
    q, s = quantize(x)
    err = float(jnp.max(jnp.abs(dequantize(q, s) - x)))
    assert err <= float(s) / 2 + 1e-6


def test_error_feedback_telescopes():
    """Sum of dequantized outputs + final residual == sum of inputs (EF-SGD)."""
    rng = jax.random.PRNGKey(1)
    err = jnp.zeros(256)
    total_in = jnp.zeros(256)
    total_out = jnp.zeros(256)
    for i in range(20):
        rng, k = jax.random.split(rng)
        g = jax.random.normal(k, (256,)) * (1 + i % 3)
        total_in = total_in + g
        q, s, err = ef_compress(g, err)
        total_out = total_out + dequantize(q, s)
    np.testing.assert_allclose(total_out + err, total_in, atol=1e-3)


# --- sharding rules ------------------------------------------------------------------------
def test_param_specs_cover_all_leaves():
    for arch in ("internlm2-1.8b", "mixtral-8x22b", "deepseek-v2-lite-16b",
                 "mamba2-2.7b", "zamba2-2.7b", "hubert-xlarge"):
        cfg = get_config(arch, smoke=True)
        params = jax.eval_shape(lambda c=cfg: init_params(jax.random.PRNGKey(0), c))
        mesh = make_mesh((1, 1), ("data", "model"))
        specs = param_specs(params, cfg, mesh)
        assert jax.tree.structure(specs) == jax.tree.structure(params)


def test_batch_spec_divisibility():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert batch_spec(1, mesh)[0] is None  # nothing to shard on a 1x1 mesh


# --- serving -----------------------------------------------------------------------------------
def test_serving_engine_greedy_deterministic():
    """The same prompt served twice (the second time into a slot its first
    run left dirty) gives the same in-vocab tokens."""
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(cfg, params, n_slots=1, max_seq=48)
    outs = []
    for rid in range(2):
        eng.add(GenRequest(id=rid, prompt=[3] * 8, max_new=8))
        (done,) = eng.run()
        outs.append(done.generated)
    assert outs[0] == outs[1]
    assert len(outs[0]) == 8
    assert all(0 <= t < cfg.vocab_size for t in outs[0])
