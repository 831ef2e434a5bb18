"""The comparison that decides ``correct`` fails its control and every fault
a served cell can have, and passes a sound run: a whole run through
``harness.run`` at smoke size on the CPU, with the chip look skipped and the
timed path broken underneath."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import harness
import spec
from conftest import DATA

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELLS = ["smoke-gqa", "smoke-mamba"]


def _bench():
    bench = spec.benchmark()
    bench["workloads"] = [{"name": n, "config": n, "traffic": "smoke", "chips": 1, "why": "test"}
                          for n in CELLS]
    return bench


def _run(name, seed, hook=None):
    return harness.run(name, seed, 1.5, False, time.perf_counter(), DEVICE, engine_hook=hook,
                       bench=_bench(), root=DATA)


def stale_state(engine):
    """A decode step that returns its state unchanged: a copy taken before
    the call, since the call consumes the state it is given (donation)."""
    dec = engine._decode

    def decode(p, tok, cache, pos):
        before = jax.tree.map(jnp.copy, cache)
        return dec(p, tok, cache, pos)[0], before
    engine._decode = decode


def half_batch(engine):
    """Half of the slot rows left out: each even row (slot 0, the one filled
    first, among them) takes the logits of the odd row beside it."""
    dec = engine._decode

    def decode(p, tok, cache, pos):
        logits, new = dec(p, tok, cache, pos)
        n = logits.shape[0]
        return logits[np.minimum(np.arange(n) | 1, n - 1)], new
    engine._decode = decode


def altered_token(engine):
    """A token altered where it is produced."""
    pick = engine._pick_row
    engine._pick_row = lambda logits: (pick(logits) + 1) % engine.cfg.vocab_size


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(name, 2**31 + 77)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [stale_state, half_batch, altered_token])
@pytest.mark.parametrize("name", CELLS)
def test_faults_are_not_correct(name, fault):
    res = _run(name, 2**31 + 78, fault)
    assert not res["correct"], (fault.__name__, res["check"])


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_fails_the_limit(name):
    """The reference in fp8 in the program's place reads above the limit on
    every seed tried."""
    cell = spec.cell(name, DATA)
    limit = cell["check"][check.GAP]
    for seed in (3, 4, 5):
        w, _, served, _ = harness.serve(cell, seed, 1.5, None, time.perf_counter())
        pairs = [served[i] for i in check.sample(served, cell["check"]["requests"], seed)]
        ref = harness.reference(cell, seed)
        assert check.widest_gap(ref, pairs, cell["max_seq"], control=True) > limit
        assert check.widest_gap(ref, pairs, cell["max_seq"]) <= limit


def test_sample_takes_the_longest_and_is_seeded():
    served = [([1] * n, [2] * 3) for n in (5, 9, 2, 7, 4, 6)]
    a = check.sample(served, 3, 11)
    assert a[0] == 1 and len(a) == 3 and a == check.sample(served, 3, 11)
    assert sorted(check.sample(served, 10, 11)) == list(range(6))
    assert check.sample([], 3, 1) == []
    # requests carried across a gang's resize come after the draw, once each
    extra = [i for i in range(6) if i not in a][:2]
    assert check.sample(served, 3, 11, extra + a) == a + extra


@pytest.fixture(scope="module", params=CELLS)
def served_sample(request):
    """A cell, its weights and a seeded sample of what a run served."""
    cell = spec.cell(request.param, DATA)
    seed = 2**31 + 79
    _, _, served, _ = harness.serve(cell, seed, 1.5, None, time.perf_counter())
    pairs = [served[i] for i in check.sample(served, cell["check"]["requests"], seed)]
    return cell, harness.make_params(cell, seed), pairs


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("block", ["every", 1])
def test_blocked_reference_reads_the_whole_models_gaps(served_sample, block, control):
    """On the CPU bit for bit, in one block of every layer or in blocks of
    one layer, against the whole model as one program (weights on one
    device run as that)."""
    cell, params, pairs = served_sample
    cs = cell["config_spec"]
    ref_module = spec.reference(cs["reference"])
    whole = check.Reference(ref_module, cs["model"], params)
    ref = check.Reference(ref_module, cs["model"], params,
                          cs["model"]["n_layers"] if block == "every" else block)
    assert whole.block is None
    for prompt, tokens in pairs:
        assert np.array_equal(ref.gaps(prompt, tokens, cell["max_seq"], control),
                              whole.gaps(prompt, tokens, cell["max_seq"], control))


def test_block_size_divides_the_layers_and_fits_the_budget():
    stack = {"w": jnp.zeros((12, 256, 256), jnp.bfloat16)}      # 256 KiB a layer in float32
    assert check.block_layers(stack, 12, 2**20) == 4
    assert check.block_layers(stack, 12, 5 * 2**18) == 4        # 5 fit; 4 divides 12
    assert check.block_layers(stack, 12, 1) == 1
    assert check.block_layers(stack, 12, 2**40) == 12
