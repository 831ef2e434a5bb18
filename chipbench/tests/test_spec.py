"""Cells, configurations, mixes and metrics are files found by name; the
committed BENCHMARK.json agrees with them."""
import json
import shutil

import pytest

import gen
import harness
import spec
from conftest import BENCH, DATA


def test_benchmark_entries_match_their_files():
    bench = spec.benchmark()
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    for c in bench["configs"]:
        cfg = json.loads((spec.CHECKOUT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert spec.reference(cfg["reference"]).init_spec(cfg["model"])
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["why"]) == \
            (w["config"], w["traffic"], w["why"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert spec.end_to_end_for(w["name"], bench)
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        for cell_name in m.get("workloads", []):
            assert cell_name in {w["name"] for w in bench["workloads"]}


def test_added_files_are_found_by_name_with_no_edit(tmp_path):
    """A new configuration, mix, cell and metric are new files only."""
    root = tmp_path / "bench"
    shutil.copytree(DATA, root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "configs" / "smoke-gqa.json").read_text())
    cfg["name"] = "dummy-cfg"
    cfg["model"]["n_layers"] = 1
    (root / "configs" / "dummy-cfg.json").write_text(json.dumps(cfg))
    (root / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"arrivals": "onoff", "burst": {"period_s": 2, "on_s": 1, "factor": 2},
         "functions": 2, "zipf_s": 1.0,
         "prompt_len": {"median": 16, "sigma": 0.1, "min": 8, "max": 20},
         "output_len": {"min": 2, "max": 3}}))
    (root / "workloads" / "dummy-cell.json").write_text(json.dumps(
        {"config": "dummy-cfg", "traffic": "dummy-mix", "why": "dummy", "n_slots": 2,
         "max_seq": 32, "rate": 2.0, "drain_cap_s": 10,
         "check": {"requests": 2, "min_tokens": 2, "max_logit_gap": 0.03}}))
    (root / "metrics").mkdir(exist_ok=True)
    (root / "metrics" / "dummy.metric.py").write_text(
        "def read(ctx):\n    return float(ctx.window.counters_close['n_emitted'])\n")
    after = {p: p.read_bytes() for p in root.rglob("*") if p.is_file() and p in before}
    assert after == before

    cell = spec.cell("dummy-cell", root)
    assert cell["config_spec"]["name"] == "dummy-cfg"
    t = gen.Traffic(cell["traffic_spec"], cell["rate"], 128, 1)
    assert len(t.open_schedule(4.0)) == 8
    assert spec.metric_reader("dummy.metric", root)(
        type("C", (), {"window": type("W", (), {"counters_close": {"n_emitted": 3}})})) == 3.0
    bench = {"per_layer": [{"name": "dummy.metric", "unit": "count",
                            "workloads": ["dummy-cell"]},
                           {"name": "slot_occupancy", "unit": "%", "workloads": ["other"]}]}
    assert [m["name"] for m in spec.per_layer_for("dummy-cell", bench)] == ["dummy.metric"]
    assert harness.model_config(cell).n_layers == 1


def test_missing_metric_reader_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric", tmp_path)


def test_committed_cells_have_a_checked_limit():
    for w in spec.benchmark()["workloads"]:
        chk = spec.cell(w["name"])["check"]
        assert 0 < chk["max_logit_gap"] < 1.0
        assert chk["requests"] >= 2 and chk["min_tokens"] >= 100
    assert (BENCH / "peaks.json").exists()
