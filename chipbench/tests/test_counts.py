"""Operation and byte counts against hand counts and pinned values, and the
peaks table."""
import json

import pytest

import counts
import spec
from conftest import DATA
from reference import gqa, mamba2

GQA = {"family": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
       "head_dim": 4, "d_ff": 12, "vocab_size": 10}
SSM = {"family": "ssm", "n_layers": 2, "d_model": 4, "ssm_expand": 2, "ssm_headdim": 4,
       "ssm_state": 3, "ssm_ngroups": 1, "ssm_chunk": 2, "d_conv": 4, "vocab_size": 10}
PEAK = counts.peaks("TPU v5 lite")


def test_flash_counts_by_hand():
    # L=3: 6 causal pairs; per pair QK^T and PV are 2*4 FLOPs each, 2 heads
    assert counts.flash_flops(GQA, 3) == 6 * 2 * (2 * 4 + 2 * 4)
    # q and o: 3*2*4 each, k and v: 3*1*4 each, 2 bytes apiece
    assert counts.flash_bytes(GQA, 3) == 2 * (2 * 24 + 2 * 12)


def test_ssd_counts_by_hand():
    # H = 8/4 = 2 heads, P=4, N=3, chunks of 2 over L=3: sizes 2 and 1
    per_head = (2 * 3 * (3 + 4) + 4 * 2 * 4 * 3) + (2 * 1 * (3 + 4) + 4 * 1 * 4 * 3)
    assert counts.ssd_flops(SSM, 3) == 2 * per_head
    reads = 3 * (2 * 4 * 2 + 2 * 4 + 2 * 1 * 3 * 2)
    writes = 3 * 2 * 4 * 4 + 2 * 4 * 3 * 4
    assert counts.ssd_bytes(SSM, 3) == reads + writes


def test_step_counts_by_hand():
    # dense: per layer q,o 8x8, k,v 8x4, MLP 3 of 8x12; head 8x10
    per_tok = 2 * 2 * (64 + 2 * 32 + 64 + 3 * 96)
    assert gqa.prefill_flops(GQA, 3) == 3 * per_tok + 2 * counts.flash_flops(GQA, 3) + 160
    assert gqa.decode_flops(GQA, 5) == per_tok + 2 * 4 * 2 * 4 * 5 + 160
    # ssm: d_inner 8, in_proj 4x(16+6+2), conv 14x4, out_proj 8x4
    ssm_tok = 2 * 2 * (4 * 24 + 14 * 4 + 8 * 4)
    assert mamba2.prefill_flops(SSM, 3) == 3 * ssm_tok + 2 * counts.ssd_flops(SSM, 3) + 80
    assert mamba2.decode_flops(SSM, 9) == ssm_tok + 2 * 5 * 2 * 4 * 3 + 80


# Each configuration's step counts at the prompt lengths and decode contexts
# the cells span, pinned to the value and the type: the same reduction has to
# give mfu.prefill and mfu.decode bit for bit.
STEP_GOLDEN = {
    "qwen2.5-3b": {
        "prefill": {1: 6171688960.0, 64: 356375855104.0, 512: 2880473399296.0,
                    1024: 5837633880064.0, 2048: 11983883075584.0},
        "decode": {2: 6171983872, 640: 6360137728, 2112: 6794248192}},
    "mamba2-2.7b": {
        "prefill": {1: 5573189632.0, 64: 344429436928.0, 512: 2850270224384.0,
                    1024: 5700283015168.0, 2048: 11400308596736.0},
        "decode": {2: 5613166592, 640: 5613166592, 2112: 5613166592}},
    "smoke-gqa": {
        "prefill": {1: 131584.0, 64: 8421376.0, 512: 125976576.0, 1024: 386154496.0,
                    2048: 1309163520.0},
        "decode": {2: 132096, 640: 458752, 2112: 1212416}},
    "smoke-mamba": {
        "prefill": {1: 144896.0, 64: 9256960.0, 512: 73940992.0, 1024: 147865600.0,
                    2048: 295714816.0},
        "decode": {2: 147968, 640: 147968, 2112: 147968}},
}


@pytest.mark.parametrize("step", ["prefill", "decode"])
@pytest.mark.parametrize("config", sorted(STEP_GOLDEN))
def test_step_counts_keep_their_pinned_values(config, step):
    path = spec.HERE / "configs" / f"{config}.json"
    if not path.exists():
        path = DATA / "configs" / f"{config}.json"
    cs = json.loads(path.read_text())
    count = getattr(spec.reference(cs["reference"]), f"{step}_flops")
    for n, want in STEP_GOLDEN[config][step].items():
        got = count(cs["model"], n)
        assert (got, type(got)) == (want, type(want)), (config, step, n)


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(KeyError, match="TPU v9"):
        counts.peaks("TPU v9")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"X": {"bf16_flops": 1, "hbm_bytes_per_s": 1}}))
    assert counts.peaks("X", p)["bf16_flops"] == 1
    with pytest.raises(KeyError):
        counts.peaks("TPU v5 lite", p)


@pytest.mark.parametrize("length", [1, 17, 256, 1024, 2048])
def test_shares_cannot_pass_one_at_or_above_the_bound(length):
    """A time at or above the roofline bound gives a share of at most 1."""
    for flops, nbytes in ((counts.flash_flops(GQA, length), counts.flash_bytes(GQA, length)),
                          (counts.ssd_flops(SSM, length), counts.ssd_bytes(SSM, length))):
        bound = counts.roofline_s(flops, nbytes, PEAK)
        for t in (bound, 1.5 * bound):
            assert bound / t <= 1.0
    for fl in (gqa.prefill_flops(GQA, length), mamba2.decode_flops(SSM, length)):
        t = fl / PEAK["bf16_flops"]
        assert fl / (t * PEAK["bf16_flops"]) <= 1.0 + 1e-12
