"""Per-layer metric readers on made-up reductions: nothing to read gives
nothing, and no roofline or mfu share passes 100% for a time at or above
what the counts allow."""
import json
import shutil
import sys

import pytest

import counts
import harness
import programs
import spec
import trace_reduce
from conftest import BENCH, DATA

QWEN = spec.cell("qwen2.5-3b.doc-faas")
MAMBA = spec.cell("mamba2-2.7b.chat-burst")
PEAK = counts.peaks("TPU v5 lite")
TOKENS = [(1024, 0), (1024, 1), (1024, 2), (700, 0), (700, 5)]


class _Window:
    trace = (0.0, 1.0)
    counters_open = {"n_decode_steps": 10, "n_slot_steps": 40}
    counters_close = {"n_decode_steps": 30, "n_slot_steps": 200}
    compiles = 0

    def __init__(self, tokens):
        self.recs = []
        for p, j in tokens:
            call = type("Call", (), {"prompt": [0] * p})()
            stamps = [2.0] * j + [0.5]      # only index j falls inside the trace
            self.recs.append(type("Rec", (), {"call": call, "stamps": stamps})())


def _reduction(module_s, op_s, busy=2.0, window=4.0, module_n=None):
    return trace_reduce.Reduction(window_s=window, busy_s=busy, n_devices=1,
                                  module_s=module_s, module_n=module_n or {}, op_s=op_s,
                                  gaps=[])


def _ctx(cell, red, tokens=TOKENS):
    return harness.Context(cell, cell["config_spec"]["model"], _Window(tokens), red, PEAK)


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_traced_tokens_reads_index_of_each_stamp_in_the_span():
    assert sorted(_ctx(QWEN, None).traced_tokens()) == sorted(TOKENS)


@pytest.mark.parametrize("cell,kernel,flops,nbytes", [
    (QWEN, "flash_attention", counts.flash_flops, counts.flash_bytes),
    (MAMBA, "ssd", counts.ssd_flops, counts.ssd_bytes)])
def test_roofline_at_the_bound_reads_100(cell, kernel, flops, nbytes):
    m = cell["config_spec"]["model"]
    bound = sum(m["n_layers"] * counts.roofline_s(flops(m, p), nbytes(m, p), PEAK)
                for p, j in TOKENS if j == 0)
    name = f"{kernel}_roofline"
    for t, want in ((bound, 100.0), (2 * bound, 50.0)):
        red = _reduction({}, {f"%{kernel}.3": t})
        assert _read(name, _ctx(cell, red)) == pytest.approx(want)
    assert _read(name, _ctx(cell, _reduction({}, {}))) is None
    assert _read(name, _ctx(cell, _reduction({}, {f"%{kernel}": 1.0}), [])) is None
    assert _read(name, _ctx(cell, _reduction({}, {f"%{kernel}_other.1": 1.0}))) is None


@pytest.mark.parametrize("cell", [QWEN, MAMBA])
def test_mfu_at_peak_reads_100(cell):
    m = cell["config_spec"]["model"]
    ref = spec.reference(cell["config_spec"]["reference"])
    pf = sum(ref.prefill_flops(m, p) for p, j in TOKENS if j == 0)
    df = sum(ref.decode_flops(m, p + j) for p, j in TOKENS if j >= 1)
    red = _reduction({"jit__unknown(1)": pf / PEAK["bf16_flops"],
                      "jit__unknown(2)": 4 * df / PEAK["bf16_flops"]}, {},
                     module_n={"jit__unknown(1)": 2, "jit__unknown(2)": 40})
    assert _read("mfu.prefill", _ctx(cell, red)) == pytest.approx(100.0)
    assert _read("mfu.decode", _ctx(cell, red)) == pytest.approx(25.0)
    assert _read("mfu.prefill", _ctx(cell, _reduction({}, {}))) is None


TOY_STEPS = """
def init_spec(m):
    return {}


def embed(params, tokens, m):
    raise NotImplementedError


def layers(stack, x, m, quant=None):
    raise NotImplementedError


def final(params, x, m):
    raise NotImplementedError


def prefill_flops(m, length):
    return 1000.0 * length
"""
TOY_DECODE = """

def decode_flops(m, context):
    return 10.0 * context
"""


@pytest.fixture
def toy_family(tmp_path, monkeypatch):
    """A configuration of a family no committed configuration has ("moe"),
    added as new files: its configuration, a cell, and its reference in a
    directory the ``reference`` package searches. Returns a function that
    writes the reference and loads the cell."""
    import reference
    root = tmp_path / "bench"
    shutil.copytree(DATA, root)
    (root / "reference").mkdir()
    monkeypatch.setattr(reference, "__path__", [*reference.__path__, str(root / "reference")])
    added = []

    def add(module, source):
        (root / "reference" / f"{module}.py").write_text(source)
        name = module.replace("_", "-")
        (root / "configs" / f"{name}.json").write_text(json.dumps(
            {"name": name, "reference": module, "kernels": "auto",
             "model": {"family": "moe", "n_layers": 2, "vocab_size": 128}}))
        (root / "workloads" / f"{name}.json").write_text(json.dumps(
            {"config": name, "traffic": "smoke", "why": "a new family", "n_slots": 2,
             "max_seq": 64, "rate": 2.0, "drain_cap_s": 10,
             "check": {"requests": 2, "min_tokens": 2, "max_logit_gap": 0.1}}))
        added.append(f"reference.{module}")
        return spec.cell(name, root)

    yield add
    for mod in added:
        sys.modules.pop(mod, None)


def test_mfu_reads_a_new_familys_counts_from_its_reference(toy_family):
    cell = toy_family("toy_moe", TOY_STEPS + TOY_DECODE)
    red = _reduction({"jit__unknown(1)": 2e-6, "jit__unknown(2)": 5e-6}, {},
                     module_n={"jit__unknown(1)": 2, "jit__unknown(2)": 40})
    peak = PEAK["bf16_flops"]
    # prompts 1024 and 700; decoded tokens over 1025, 1026 and 705 positions
    assert _read("mfu.prefill", _ctx(cell, red)) == pytest.approx(
        100.0 * 1000.0 * (1024 + 700) / (2e-6 * peak))
    assert _read("mfu.decode", _ctx(cell, red)) == pytest.approx(
        100.0 * 10.0 * (1025 + 1026 + 705) / (5e-6 * peak))


def test_a_reference_without_its_step_counts_is_an_error(toy_family):
    cell = toy_family("toy_moe_uncounted", TOY_STEPS)
    red = _reduction({"jit__unknown(1)": 2e-6, "jit__unknown(2)": 5e-6}, {},
                     module_n={"jit__unknown(1)": 2, "jit__unknown(2)": 40})
    for name in ("mfu.prefill", "mfu.decode"):
        with pytest.raises(AttributeError, match="decode_flops"):
            _read(name, _ctx(cell, red))


def test_engine_and_device_shares():
    red = _reduction({"jit__unknown(7)": 0.5, "jit__lambda(8)": 0.25,
                      "jit__unknown(9)": 1.2, "jit__argmax(1)": 0.05}, {}, busy=2.0, window=4.0,
                     module_n={"jit__unknown(7)": 3, "jit__lambda(8)": 3,
                               "jit__unknown(9)": 30, "jit__argmax(1)": 30})
    assert programs.classify(red.module_n) == {
        "prefill": ["jit__unknown(7)"], "graft": ["jit__lambda(8)"],
        "decode": ["jit__unknown(9)"]}
    ctx = _ctx(QWEN, red)
    assert _read("prefill_device_share", ctx) == pytest.approx(37.5)
    assert _read("device_idle_share", ctx) == pytest.approx(50.0)
    assert _read("compiles_in_window", ctx) == 0.0
    # 160 slot-steps over 20 waves of 16 slots
    assert _read("slot_occupancy", ctx) == pytest.approx(50.0)


def test_every_reader_has_a_file():
    for m in spec.benchmark()["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
