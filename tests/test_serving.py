"""Continuous-batching serving runtime tests.

Fast tier (no JAX): SlotBatcher invariants — fill/refill conservation, EOS
early-free, drain partials — plus the stable prompt-seed contract across
PYTHONHASHSEED values. Slow tier (JAX): per-slot position-vector decode,
batched==unbatched temperature-0 token equality checked against a cache-free
forward, in-place slot state, PRNG key hygiene, drain/resume, and the
batched executor behind the platform seam.
"""
import dataclasses
import subprocess
import sys

import pytest

from repro.serving.batching import GenRequest, SlotBatcher


# --- SlotBatcher (fast tier) --------------------------------------------------
def _mk(i, max_new=4, eos_id=None, generated=None):
    return GenRequest(id=i, prompt=[1, 2, 3], max_new=max_new, eos_id=eos_id,
                      generated=list(generated or []))


def test_slot_batcher_conservation():
    """No request lost or duplicated across add/step/drain."""
    b = SlotBatcher(2)
    for i in range(5):
        b.add(_mk(i, max_new=i % 3 + 1))
    for _ in range(6):
        b.step(lambda r: 7)
    drained = b.drain()
    ids = sorted(r.id for r in b.finished) + sorted(r.id for r in drained)
    assert sorted(ids) == list(range(5))
    assert all(r.done for r in b.finished)
    assert not any(r.done for r in drained)


def test_slot_batcher_eos_frees_slot_early():
    b = SlotBatcher(1)
    b.add(_mk(0, max_new=100))
    b.add(_mk(1, max_new=2))          # waits behind request 0
    b.step(lambda r: 9, eos_id=9)     # batcher-wide stop token
    assert b.finished[0].id == 0 and len(b.finished[0].generated) == 1
    assert b.slots[0] is not None and b.slots[0].id == 1  # refilled same step


def test_slot_batcher_per_request_eos_overrides_default():
    b = SlotBatcher(2)
    b.add(_mk(0, max_new=10, eos_id=5))
    b.add(_mk(1, max_new=10))
    b.step(lambda r: 5, eos_id=None)  # only request 0 stops on 5
    assert [r.id for r in b.finished] == [0]
    assert b.slots[1] is not None and b.slots[1].id == 1


def test_slot_batcher_drain_keeps_partials_and_waiting():
    b = SlotBatcher(1)
    b.add(_mk(0, max_new=10))
    b.add(_mk(1, max_new=10))
    b.step(lambda r: 3)
    b.step(lambda r: 4)
    out = b.drain()
    assert {r.id for r in out} == {0, 1}
    in_slot = next(r for r in out if r.id == 0)
    assert in_slot.generated == [3, 4] and in_slot.remaining == 8
    assert b.slots == [None] and not b.waiting
    assert b.drain() == []


def test_gen_request_remaining_counts_resumed_partial():
    r = _mk(0, max_new=6, generated=[1, 2])
    assert r.remaining == 4
    assert _mk(1, max_new=2, generated=[1, 2, 3]).remaining == 0


def test_slot_batcher_property_conservation():
    """Property fuzz: arbitrary interleavings of add/step/drain conserve the
    request multiset (hypothesis-optional; deterministic fallback above)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(["add", "step", "drain"]),
                              st.integers(1, 5)), min_size=1, max_size=40))
    def run(ops):
        b = SlotBatcher(3)
        n_added = 0
        drained_ids = []
        for op, arg in ops:
            if op == "add":
                b.add(_mk(n_added, max_new=arg))
                n_added += 1
            elif op == "step":
                b.step(lambda r: arg, eos_id=1)
            else:
                drained_ids += [r.id for r in b.drain()]
        live = [r.id for r in b.slots if r is not None] + \
               [r.id for r in b.waiting]
        ids = sorted([r.id for r in b.finished] + drained_ids + live)
        assert ids == list(range(n_added))

    run()


# --- stable prompt seeds (fast tier) -----------------------------------------
def test_prompt_seed_stable_across_hashseed():
    """The executor prompt must NOT depend on Python's randomized string hash
    (the old ``abs(hash(req.fn))`` seed): two processes with different
    PYTHONHASHSEED values must derive the same prompt."""
    import os
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("from repro.platform.executors import prompt_for_fn;"
            "print(prompt_for_fn('fib-07', 128, 8))")
    outs = []
    for seed in ("0", "424242"):
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True,
                           env={**os.environ, "PYTHONHASHSEED": seed,
                                "PYTHONPATH": src})
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip())
    assert outs[0] == outs[1] and outs[0]


# --- JAX tier -----------------------------------------------------------------
jaxtier = pytest.mark.slow


@pytest.fixture(scope="module")
def qwen_setup():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@jaxtier
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v2-lite-16b",
                                  "mixtral-8x22b"])
def test_vector_pos_decode_matches_scalar(arch):
    """decode_step with a per-row position VECTOR must equal the scalar-pos
    path when all rows share the position (GQA, MLA, and SWA ring caches)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import decode_step, init_cache, init_params, prefill
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, s, extra = 2, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s + extra), 0,
                              cfg.vocab_size)
    _, cache = prefill(params, {"tokens": toks[:, :s]}, cfg)
    full = init_cache(cfg, b, s + extra)
    grow = lambda z, c: c.astype(z.dtype) if z.shape == c.shape else jnp.pad(
        c.astype(z.dtype), [(0, zi - ci) for zi, ci in zip(z.shape, c.shape)])
    c_sc = jax.tree.map(grow, full, cache)
    c_vec = c_sc
    for i in range(extra):
        pos = s + i
        lg_sc, c_sc = decode_step(params, toks[:, s + i:s + i + 1], c_sc,
                                  jnp.int32(pos), cfg)
        lg_vec, c_vec = decode_step(params, toks[:, s + i:s + i + 1], c_vec,
                                    jnp.full((b,), pos, jnp.int32), cfg)
        np.testing.assert_allclose(lg_vec, lg_sc, atol=1e-5, rtol=1e-5)
    jax.tree.map(lambda a, c: np.testing.assert_allclose(a, c, atol=1e-6),
                 c_vec, c_sc)


# widest gap allowed between a cache-free forward's best logit and the
# served token's logit, at float32 (every family reads 0.0 on the CPU)
FORWARD_LOGIT_TOL = 1e-4


@jaxtier
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mixtral-8x22b",
                                  "deepseek-v2-lite-16b", "mamba2-2.7b",
                                  "zamba2-2.7b"])
def test_continuous_matches_unbatched_and_forward(arch):
    """Streams served by 3 slots at staggered offsets equal the same requests
    served one at a time by a 1-slot engine, and every served token is the
    argmax of a cache-free ``forward`` over prompt + generated so far (the
    oracle shares neither the cache nor the decode step with the engine)."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import forward, init_params
    from repro.serving.engine import ContinuousEngine
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (6, 11, 8, 7, 9)]
    one = ContinuousEngine(cfg, params, n_slots=1, max_seq=48)
    ref = []
    for i, p in enumerate(prompts):
        one.add(GenRequest(id=i, prompt=p, max_new=8))
        (done,) = one.run()
        ref.append(done.generated)
    cont = ContinuousEngine(cfg, params, n_slots=3, max_seq=48)
    for i, p in enumerate(prompts):
        cont.add(GenRequest(id=i, prompt=p, max_new=8))
    got = {r.id: r.generated for r in cont.run()}
    assert [got[i] for i in range(len(prompts))] == ref
    assert cont.occupancy <= 1.0

    fwd = jax.jit(lambda toks: forward(params, {"tokens": toks}, cfg)[0])
    for p, gen in zip(prompts, ref):
        seq = np.asarray([p + gen], np.int32)
        logits = np.asarray(fwd(seq))[0, len(p) - 1:-1, :cfg.vocab_size]
        served = logits[np.arange(len(gen)), gen]
        gap = float(np.max(logits.max(axis=-1) - served))
        assert gap <= FORWARD_LOGIT_TOL, (arch, gap)


@jaxtier
def test_continuous_sampling_key_hygiene(qwen_setup):
    """Sampled serving splits the root key before the FIRST admission token
    (the root key is only ever split, never consumed), and the same seed
    reproduces the stream while another seed does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import ContinuousEngine, _pick
    cfg, params = qwen_setup
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size,
                                               size=8).tolist()

    def serve(seed):
        eng = ContinuousEngine(cfg, params, n_slots=1, max_seq=32,
                               temperature=1.0, seed=seed)
        eng.add(GenRequest(id=0, prompt=prompt, max_new=4))
        (done,) = eng.run()
        return eng, done.generated

    eng, got = serve(7)
    logits, _ = eng._prefill(params, {"tokens": jnp.asarray([prompt],
                                                            jnp.int32)})
    _, sub = jax.random.split(jax.random.PRNGKey(7))
    first = int(np.asarray(_pick(logits, cfg.vocab_size, 1.0, sub))[0, 0])
    assert got[0] == first
    assert len(got) == 4
    assert serve(7)[1] == got
    assert serve(8)[1] != got


@jaxtier
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mixtral-8x22b",
                                  "deepseek-v2-lite-16b", "mamba2-2.7b",
                                  "zamba2-2.7b"])
def test_kernel_impls_token_identity_per_arch(arch):
    """Every zoo family (GQA, MoE+SWA, MLA+MoE, SSM, hybrid) serves with
    kernel_impls="auto" through the ContinuousEngine emitting temperature-0
    tokens bit-identical to the reference einsum/scan leg at float32."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import supported_kernel_sites, with_kernel_impls
    from repro.models import init_params
    from repro.serving.engine import ContinuousEngine
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    assert supported_kernel_sites(cfg)   # every zoo arch has a kernel leg
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (6, 10, 8, 7)]
    outs = {}
    for leg, leg_cfg in (("reference", cfg),
                         ("kernel", with_kernel_impls(cfg, "auto"))):
        eng = ContinuousEngine(leg_cfg, params, n_slots=2, max_seq=48)
        for i, p in enumerate(prompts):
            eng.add(GenRequest(id=i, prompt=p, max_new=6))
        got = {r.id: r.generated for r in eng.run()}
        outs[leg] = [got[i] for i in range(len(prompts))]
    assert outs["kernel"] == outs["reference"]


@jaxtier
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "internlm2-1.8b",
                                  "mixtral-8x22b", "deepseek-v2-lite-16b",
                                  "mamba2-2.7b", "zamba2-2.7b"])
def test_decode_and_graft_update_slot_state_in_place(arch):
    """Both programs that rewrite the slot state take it donated, for every
    cache family (GQA, SWA ring + MoE, MLA, SSM state + conv, hybrid): a
    tree read from ``engine.cache`` is deleted by the next admission's graft
    and by the next decode wave, with no buffer refused for donation."""
    import warnings

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import ContinuousEngine
    cfg = get_config(arch, smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=32)
    prompt = np.random.default_rng(7).integers(0, cfg.vocab_size, 6).tolist()
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers")
        for i, rewrite in enumerate((
                lambda: eng.add(GenRequest(id=0, prompt=prompt, max_new=4)),
                eng.step,
                lambda: eng.add(GenRequest(id=1, prompt=prompt, max_new=4)),
                eng.step)):
            held = jax.tree.leaves(eng.cache)
            rewrite()
            assert all(leaf.is_deleted() for leaf in held), i
            assert not any(leaf.is_deleted()
                           for leaf in jax.tree.leaves(eng.cache)), i
    got = {r.id: r.generated for r in eng.run()}
    assert got[0] == got[1]   # the same prompt, admitted a wave apart


@jaxtier
@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "mamba2-2.7b",
                                  "zamba2-2.7b"])
def test_drain_resume_nontransformer_state(arch):
    """The slot-state protocol generalizes drain/resume beyond dense K/V:
    MLA latents, SSM recurrent+conv state, and the hybrid union all resume a
    preempted stream token-identically (resumed state is re-prefilled, so
    any stale slot row from the previous occupant must be fully grafted
    over). float32: resume re-prefills prompt+partial in ONE pass, and MLA's
    absorbed-decode math / the SSM chunk boundaries round differently from
    incremental decode at bf16 — f32 is the bit-identity regime."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import ContinuousEngine
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (8, 11)]
    ref_eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=48)
    for i, p in enumerate(prompts):
        ref_eng.add(GenRequest(id=i, prompt=p, max_new=10))
    ref = {r.id: r.generated for r in ref_eng.run()}

    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=48)
    for i, p in enumerate(prompts):
        eng.add(GenRequest(id=i, prompt=p, max_new=10))
    eng.step()
    eng.step()
    partials = eng.drain()
    assert all(0 < len(r.generated) < 10 for r in partials)
    for r in partials:
        eng.add(r)
    got = {r.id: r.generated for r in eng.run()}
    assert got == ref


@jaxtier
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "deepseek-v2-lite-16b",
                                  "mamba2-2.7b", "zamba2-2.7b"])
def test_slot_state_single_batch_axis(arch):
    """find_batch_axes identifies exactly one batch axis per decode-state
    leaf for every cache family (dense K/V, MLA latents, SSM state+conv,
    hybrid union)."""
    import jax
    from repro.configs import get_config
    from repro.models import model as model_mod
    from repro.serving.slot_state import find_batch_axes
    cfg = get_config(arch, smoke=True)
    axes = find_batch_axes(cfg, 32)
    spec = model_mod.cache_spec(cfg, 3, 32)
    for ax, leaf in zip(jax.tree.leaves(axes), jax.tree.leaves(spec)):
        assert leaf.shape[ax] == 3   # the axis found really is batch


@jaxtier
def test_continuous_eos_frees_slot_early(qwen_setup):
    """A slot whose greedy stream hits eos_id frees before max_new and is
    refilled without stopping the loop."""
    import numpy as np
    from repro.serving.engine import ContinuousEngine
    cfg, params = qwen_setup
    probe = ContinuousEngine(cfg, params, n_slots=1, max_seq=48)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, size=8).tolist()
    probe.add(GenRequest(id=0, prompt=prompt, max_new=8))
    full = probe.run()[0].generated
    # stop on the first token that does not occur earlier in the stream, so
    # the run must end exactly at its first occurrence (index >= 1)
    stop = next(i for i in range(1, len(full)) if full[i] not in full[:i])
    eng = ContinuousEngine(cfg, params, n_slots=1, max_seq=48,
                           eos_id=full[stop])
    eng.add(GenRequest(id=0, prompt=prompt, max_new=8))
    eng.add(GenRequest(id=1, prompt=prompt, max_new=8))  # waits for the slot
    done = eng.run()
    first = next(r for r in done if r.id == 0)
    assert first.generated == full[:stop + 1]  # stopped AT the eos token
    assert len(first.generated) < 8            # ...before max_new
    assert len(done) == 2                     # the freed slot served req 1


@jaxtier
def test_continuous_drain_resume_matches_uninterrupted(qwen_setup):
    """drain() mid-decode returns partial ``generated``; resuming the partial
    reproduces the uninterrupted temperature-0 stream (the resubmit path)."""
    import numpy as np
    from repro.serving.engine import ContinuousEngine
    cfg, params = qwen_setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (8, 10)]
    ref_eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=48)
    for i, p in enumerate(prompts):
        ref_eng.add(GenRequest(id=i, prompt=p, max_new=10))
    ref = {r.id: r.generated for r in ref_eng.run()}

    eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=48)
    for i, p in enumerate(prompts):
        eng.add(GenRequest(id=i, prompt=p, max_new=10))
    eng.step()
    eng.step()
    partials = eng.drain()
    assert {r.id for r in partials} == {0, 1}
    assert all(0 < len(r.generated) < 10 for r in partials)
    assert not eng.batcher.active()
    for r in partials:     # preempted decode resumes, does not restart
        eng.add(r)
    got = {r.id: r.generated for r in eng.run()}
    assert got == ref


@jaxtier
def test_batched_executor_behind_platform_seam(qwen_setup):
    """The ``batched-serving`` registry key aggregates an invoker's pull into
    one continuous batch and charges real wall seconds per request."""
    from repro.platform import (BatchedServingExecutor, Platform,
                                ScenarioConfig, SchedulingSection,
                                TraceSection, WorkloadSection)
    from repro.serving.engine import ContinuousEngine
    cfg, params = qwen_setup
    executor = BatchedServingExecutor(
        ContinuousEngine(cfg, params, n_slots=4, max_seq=48),
        prompt_len=12, n_new=4)
    sc = ScenarioConfig(name="t", duration=600.0, seed=0,
                        trace=TraceSection(seed=4),
                        workload=WorkloadSection(qps=0.5, n_functions=4),
                        scheduling=SchedulingSection(model="fib"))
    rt = Platform.build(sc, executor=executor)
    res = rt.run()
    done = [r for r in res.requests if r.outcome == "success"]
    assert done, "no request succeeded through the batched executor"
    assert all(r.response_time is None or r.response_time >= 0
               for r in res.requests)
    assert executor.engine.n_emitted >= len(done) * 4


@jaxtier
def test_batched_executor_resume_after_drain(qwen_setup):
    """Executor drain() parks partial generations; a resubmitted request
    resumes them and completes with the uninterrupted token stream."""
    from repro.platform.executors import (BatchedServingExecutor,
                                          prompt_for_fn)
    from repro.serving.engine import ContinuousEngine

    @dataclasses.dataclass
    class Req:
        id: int
        fn: str

    cfg, params = qwen_setup
    executor = BatchedServingExecutor(
        ContinuousEngine(cfg, params, n_slots=2, max_seq=48),
        prompt_len=10, n_new=8)
    prompt = prompt_for_fn("fn-a", cfg.vocab_size, 10)
    ref_eng = ContinuousEngine(cfg, params, n_slots=2, max_seq=48)
    ref_eng.add(GenRequest(id=0, prompt=prompt, max_new=8))
    (ref_req,) = ref_eng.run()
    ref = ref_req.generated

    # interrupt a decode mid-flight (SIGTERM), park the partial (4 tokens
    # decoded — a whole resume bucket, so all of them survive)
    executor.engine.add(GenRequest(id=77, prompt=prompt, max_new=8))
    for _ in range(3):
        executor.engine.step()
    assert executor.drain() == 1
    assert len(executor._partials[77]) == 4
    # resubmit: the same request id resumes instead of restarting
    times = executor.run_batch([Req(id=77, fn="fn-a")])
    assert len(times) == 1 and times[0] > 0
    assert executor.last_results[77] == ref
    assert not executor._partials


@jaxtier
def test_batched_executor_note_preempt_resumes_prefix(qwen_setup):
    """The invoker's preemption hook (virtual time) parks a prefix of the
    decoded stream proportional to the elapsed fraction; the requeued
    request decodes only the remainder and lands on the same tokens."""
    from repro.platform.executors import BatchedServingExecutor
    from repro.serving.engine import ContinuousEngine

    @dataclasses.dataclass
    class Req:
        id: int
        fn: str

    cfg, params = qwen_setup
    executor = BatchedServingExecutor(
        ContinuousEngine(cfg, params, n_slots=2, max_seq=48),
        prompt_len=10, n_new=8)
    req = Req(id=5, fn="fn-b")
    executor.run_batch([req])
    ref = executor.last_results[5]
    assert len(ref) == 8

    # unknown request / zero progress with nothing banked park nothing
    executor.note_preempt(Req(id=99, fn="x"), 1.0, 2.0)
    executor.note_preempt(req, 0.0, 10.0)
    assert 99 not in executor._partials and 5 not in executor._partials

    executor.note_preempt(req, elapsed=5.0, total=10.0)  # ran half its time
    assert executor._partials[5] == ref[:4]
    steps0 = executor.engine.n_emitted
    executor.run_batch([req])                            # the resubmit
    assert executor.last_results[5] == ref               # same stream
    assert executor.engine.n_emitted - steps0 == 4       # only the remainder
    assert 5 not in executor._partials                   # consumed on resume

    # re-preemption keeps banked progress: the 4 resumed-from tokens survive
    # even when the second invocation dies with ~no elapsed time
    executor.note_preempt(req, 0.01, 10.0)
    assert executor._partials[5] == ref[:4]
