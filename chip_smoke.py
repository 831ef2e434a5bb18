"""Bring-up check on a TPU: serve full-width qwen2.5-3b through the platform.

Default (one chip):
  1. Builds qwen2.5-3b at its published widths (36 layers, d_model 2048,
     16/2 heads, d_ff 11008, vocab 151936) with bfloat16 parameters, made
     on the device from a seed, and the ``"auto"`` kernel policy (flash
     attention and rmsnorm Pallas kernels).
  2. Checks the prefill of one fixed prompt: the kernel policy's logits
     against the reference policy's on the same parameters, and that the
     compiled prefill holds a ``tpu_custom_call`` (the kernels really ran).
  3. Serves a short harvest scenario through
     ``Platform.build(ScenarioConfig(...), executor=BatchedServingExecutor(
     ContinuousEngine(...)))``, the path ``examples/harvest_serving.py``
     takes, and requires every request the executor served to end in
     ``success`` with all its tokens.

``--four-chips`` runs only the elastic phase: an ``ElasticReplica`` serves
the same model tensor-parallel over four chips, shrinks to two mid-stream
(``migrate`` hand-off), and its streams are checked against the one-chip
model.

Run from the repository root: ``python chip_smoke.py [--four-chips]``.
It refuses to run without a TPU or with interpreted Pallas kernels. Every
failed phase raises; on success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2.5-3b"
N_SLOTS = 4
PROMPT_LEN = 200
N_NEW = 16
MAX_SEQ = 256
MIN_SERVED = 8
SEED = 0
SCENARIO_MINUTES = 5.0   # simulated; about 150 arrivals at 0.5 qps
SCENARIO_QPS = 0.5
# Prefill logits, relative L2 over the vocabulary. Both policies run bf16
# activations but round at different points (the flash kernel keeps the
# softmax weights in f32 through the PV product, the reference rounds them
# to bf16; the rmsnorm kernel scales in f32). Measured on a TPU v5e with this
# prompt and seed: each policy lies 1.3e-2 to 1.4e-2 from an f32-activation
# reference (matmul precision "highest") and 1.6e-2 from the other. A wrong
# mask, head mapping or scale is an O(1) error. So the policies must agree
# within 4e-2, and the kernel policy may be at most 1.5x as far from the f32
# reference as the reference policy is.
LOGIT_RTOL = 4e-2
F32_ERR_RATIO = 1.5
# Elastic phase, teacher-forced: every token the shrunk replica emitted must
# be within this many logits of the best token of the one-chip model fed the
# same prefix. Logits of this random-init model have a standard deviation
# near 1 (unit-RMS final norm, lm_head scaled by d_model**-0.5); tensor
# parallelism changes the order of bf16 sums, which moves logits by a few
# hundredths and can flip a near tie, after which greedy streams differ
# without being wrong. A broken hand-off emits tokens several logits down.
GAP_TOL = 0.25


def require_chip():
    """Exit non-zero unless JAX runs on a TPU with compiled kernels."""
    import jax

    from repro.kernels.ops import default_interpret
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform {platform!r}, not "
                         f"'tpu'; this check runs only on a TPU")
    if default_interpret():
        raise SystemExit("chip_smoke: Pallas kernels would run in interpret "
                         "mode (REPRO_PALLAS_INTERPRET is set); this check "
                         "needs them compiled")


def full_config():
    """qwen2.5-3b at published widths with bf16 parameter storage."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), param_dtype="bfloat16")


def init_on_device(cfg, seed: int):
    """Random weights made by one jitted program on the default device."""
    import jax

    from repro.models import init_params
    fn = jax.jit(functools.partial(init_params, cfg=cfg))
    return jax.block_until_ready(fn(jax.random.PRNGKey(seed)))


def tree_bytes(tree) -> int:
    import jax
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(tree)))


def check_prefill(cfg, kcfg, params):
    """Kernel-policy prefill logits vs the reference policy's, same params,
    each also measured against an f32-activation reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    from repro.platform.executors import prompt_for_fn
    prompt = prompt_for_fn("chip-smoke", cfg.vocab_size, PROMPT_LEN)
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    t0 = time.perf_counter()
    kernel = jax.jit(functools.partial(M.prefill, cfg=kcfg)).lower(
        params, batch).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in kernel.as_text():
        raise AssertionError("compiled kernel-policy prefill holds no "
                             "tpu_custom_call: the Pallas kernels did not run")
    reference = jax.jit(functools.partial(M.prefill, cfg=cfg))
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(functools.partial(
            M.prefill, cfg=dataclasses.replace(cfg, dtype="float32"))).lower(
                params, batch).compile()
    v = cfg.vocab_size

    def logits(fn):
        out = np.asarray(fn(params, batch)[0], np.float32)[:, :v]
        if not np.isfinite(out).all():
            raise AssertionError("prefill logits are not finite")
        return out

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    got, want, f32 = logits(kernel), logits(reference), logits(exact)
    err, err_kernel, err_ref = rel(got, want), rel(got, f32), rel(want, f32)
    print(f"prefill check: {PROMPT_LEN}-token prompt, kernel vs reference "
          f"logits rel L2 {err!r} (tolerance {LOGIT_RTOL}); vs the f32 "
          f"reference: kernel {err_kernel!r}, reference {err_ref!r} "
          f"(ratio tolerance {F32_ERR_RATIO}); argmax "
          f"{int(got.argmax())} / {int(want.argmax())} / "
          f"{int(f32.argmax())}; kernel prefill compile {compile_s!r} s; "
          f"tpu_custom_call present", flush=True)
    if not err <= LOGIT_RTOL:
        raise AssertionError(f"kernel prefill logits differ from the "
                             f"reference: rel L2 {err} > {LOGIT_RTOL}")
    if not err_kernel <= F32_ERR_RATIO * err_ref:
        raise AssertionError(f"kernel prefill is further from the f32 "
                             f"reference ({err_kernel}) than {F32_ERR_RATIO}x "
                             f"the reference policy ({err_ref})")


def serve_harvest(kcfg, params):
    """Serve a short harvest scenario through the batched-serving executor;
    every request the executor decoded must succeed with all its tokens."""
    from repro.platform import (BatchedServingExecutor, Platform,
                                ScenarioConfig, SchedulingSection,
                                TraceSection, WorkloadSection)
    from repro.platform.executors import prompt_for_fn
    from repro.serving.batching import GenRequest
    from repro.serving.engine import ContinuousEngine

    class RecordingExecutor(BatchedServingExecutor):
        """Keeps every batch's tokens; ``last_results`` holds the last."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.served = {}

        def run_batch(self, reqs):
            out = super().run_batch(reqs)
            self.served.update(self.last_results)
            return out

    engine = ContinuousEngine(kcfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ)
    executor = RecordingExecutor(engine, prompt_len=PROMPT_LEN, n_new=N_NEW)
    # The executor charges measured wall time to virtual time, so compile
    # every shape the scenario uses first: the prefill, the graft of a
    # prefilled row into the slot batch, and the full-batch decode step.
    t0 = time.perf_counter()
    engine.serve([GenRequest(id=-1 - i, max_new=N_NEW,
                             prompt=prompt_for_fn(f"warm-{i}", kcfg.vocab_size,
                                                  PROMPT_LEN))
                  for i in range(N_SLOTS + 1)])
    engine.batcher.finished.clear()
    warm_s = time.perf_counter() - t0
    print(f"engine warm-up (compiles prefill, graft, {N_SLOTS}-slot decode): "
          f"{warm_s!r} s", flush=True)

    sc = ScenarioConfig(
        name="chip_smoke", duration=SCENARIO_MINUTES * 60.0, seed=SEED,
        trace=TraceSection(seed=4),
        workload=WorkloadSection(qps=SCENARIO_QPS, n_functions=10),
        scheduling=SchedulingSection(model="fib"))
    res = Platform.build(sc, executor=executor).run()
    by_id = {r.id: r for r in res.requests}
    served = executor.served
    bad = [(rid, getattr(by_id.get(rid), "outcome", None), len(toks))
           for rid, toks in served.items()
           if getattr(by_id.get(rid), "outcome", None) != "success"
           or len(toks) != N_NEW]
    n_tokens = sum(len(t) for t in served.values())
    print(f"harvest scenario: {len(res.requests)} requests arrived in "
          f"{SCENARIO_MINUTES} simulated minutes; executor served "
          f"{len(served)} requests, {n_tokens} tokens ({N_NEW} each, "
          f"{PROMPT_LEN}-token prompts); {engine.n_decode_steps} decode waves",
          flush=True)
    if bad:
        raise AssertionError(f"served requests that did not succeed with "
                             f"{N_NEW} tokens (id, outcome, n_tokens): "
                             f"{bad[:10]}")
    if len(served) < MIN_SERVED:
        raise AssertionError(f"executor served {len(served)} requests; the "
                             f"check needs at least {MIN_SERVED}")


def teacher_forced_gaps(forward, params, prompt, stream, vocab_size):
    """Per emitted token: the one-chip model's best logit minus the logit
    of that token, given the same prefix."""
    import jax.numpy as jnp
    import numpy as np

    tokens = jnp.asarray([list(prompt) + list(stream[:-1])], jnp.int32)
    logits = forward(params, {"tokens": tokens})[0]
    steps = np.asarray(logits[0, len(prompt) - 1:], np.float32)
    steps = steps[:, :vocab_size]
    picked = steps[np.arange(len(stream)), np.asarray(stream)]
    return steps.max(axis=-1) - picked


def elastic_shrink(cfg, params, devices):
    """An ElasticReplica over 4 devices shrinks to 2 mid-stream (migrate
    hand-off); its streams are checked against the one-chip model."""
    import jax
    import numpy as np

    from repro.distributed.elastic_serving import ElasticReplica
    from repro.models import model as M
    from repro.platform.executors import prompt_for_fn
    from repro.serving.batching import GenRequest

    prompts = [prompt_for_fn(f"elastic-{i}", cfg.vocab_size, PROMPT_LEN)
               for i in range(N_SLOTS)]

    def requests():
        return [GenRequest(id=i, prompt=p, max_new=N_NEW)
                for i, p in enumerate(prompts)]

    def assert_mesh_size(rep, n):
        # serving_mesh clamps to the devices it is given: a mesh smaller
        # than the gang would pass every other check
        if rep.mesh_size != n:
            raise AssertionError(f"replica mesh spans {rep.mesh_size} "
                                 f"devices, expected {n}")

    one = ElasticReplica(cfg, params, 1, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                         devices=devices[:1])
    assert_mesh_size(one, 1)
    for r in requests():
        one.add(r)
    golden = {r.id: list(r.generated) for r in one.run()}
    del one

    rep = ElasticReplica(cfg, params, 4, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                         kv_mode="migrate", devices=devices[:4])
    assert_mesh_size(rep, 4)
    for r in requests():
        rep.add(r)
    for _ in range(N_NEW // 2):
        rep.step()
    rec = rep.shrink(2)
    assert_mesh_size(rep, 2)
    got = {r.id: list(r.generated) for r in rep.run()}
    if sorted(got) != sorted(golden) or any(len(t) != N_NEW
                                            for t in got.values()):
        raise AssertionError(f"shrunk replica finished {sorted(got)} with "
                             f"lengths {[len(t) for t in got.values()]}")

    forward = jax.jit(functools.partial(M.forward, cfg=cfg))
    gaps = {rid: teacher_forced_gaps(forward, params, prompts[rid], got[rid],
                                     cfg.vocab_size) for rid in got}
    floor = {rid: teacher_forced_gaps(forward, params, prompts[rid],
                                      golden[rid], cfg.vocab_size)
             for rid in golden}
    worst = max(float(g.max()) for g in gaps.values())
    worst_floor = max(float(g.max()) for g in floor.values())
    same = sum(int(np.sum(np.asarray(got[i]) == np.asarray(golden[i])))
               for i in got)
    print(f"elastic 4->2: {rec.n_requests_live} live requests carried; "
          f"{rec.bytes_moved} bytes moved ({rec.param_bytes} params, "
          f"{rec.kv_bytes} KV); resize {rec.wall_s!r} s", flush=True)
    print(f"elastic check: {same}/{N_SLOTS * N_NEW} tokens equal to the "
          f"one-chip streams; worst teacher-forced gap {worst!r} logits "
          f"(one-chip decode's own: {worst_floor!r}; tolerance {GAP_TOL})",
          flush=True)
    if not (worst <= GAP_TOL and worst_floor <= GAP_TOL):
        raise AssertionError(f"elastic streams leave the one-chip model's "
                             f"best tokens: worst gap {worst} (one-chip "
                             f"decode {worst_floor}) > {GAP_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the elastic 4->2 phase on four chips")
    args = ap.parse_args(argv)

    require_chip()
    import jax

    from repro.configs.base import with_kernel_impls
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devices)}; compile cache {cache_dir}", flush=True)
    if args.four_chips and len(devices) < 4:
        raise SystemExit(f"chip_smoke --four-chips: JAX found "
                         f"{len(devices)} devices, needs 4")

    cfg = full_config()
    t0 = time.perf_counter()
    params = init_on_device(cfg, SEED)
    print(f"params: {ARCH} at published widths ({cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, vocab {cfg.vocab_size}), "
          f"{tree_bytes(params)} bytes in {cfg.param_dtype}; init "
          f"(compile + run) {time.perf_counter() - t0!r} s", flush=True)

    if args.four_chips:
        elastic_shrink(cfg, params, devices)
    else:
        kcfg = with_kernel_impls(cfg, "auto")
        print(f"kernel policy: {dict(kcfg.kernel_impls)}", flush=True)
        check_prefill(cfg, kcfg, params)
        serve_harvest(kcfg, params)

    stats = dev.memory_stats() or {}
    print(f"device 0 peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
