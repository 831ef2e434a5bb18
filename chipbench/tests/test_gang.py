"""A gang cell: served tensor-parallel over four host devices, shrunk on
schedule inside the window, and checked with its resize pause held to the
event's grace. The run itself is ``gang_rehearsal.py``, a process of its
own; the refusals and the pause limit are checked here."""
import json
import os
import shutil
import subprocess
import sys
import time
import types

import pytest

import check
import gen
import harness
import spec
from conftest import DATA, HERE

CELL = "smoke-gang.gang-shrink"


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cache = tmp_path_factory.mktemp("gang") / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(HERE / "gang_rehearsal.py"), str(cache)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_gang_serves_shrinks_on_schedule_and_is_correct(rehearsal):
    res = rehearsal["sound"]
    cell = spec.cell(CELL, DATA)
    (event,) = res["window"]["events"]
    (planned,) = cell["events"]
    assert event["at_s"] == planned["at_s"]
    assert 0 <= event["fired_s"] - planned["at_s"] < 0.5
    assert event["members"] == [4, 2] and event["devices"] == [0, 1]
    assert event["bytes_moved"] > 0 and event["wire_bytes"] > 0
    assert 0 < event["pause_s"] < planned["grace_s"]
    assert event["fired_s"] < event["settled_s"] < res["window"]["seconds"] - harness.TRACE_S
    assert res["correct"], res["check"]
    assert res["check"][harness.PAUSE] == {"value": event["pause_s"], "limit": planned["grace_s"]}
    assert list(res["check"]) == [check.GAP, "tokens_compared", harness.PAUSE]
    # the new engine lowers the programs set-up built again, from the cache
    assert res["window"]["compiles"] == 0
    assert 0 < event["reloads"] <= res["window"]["cache_loads"] and event["reload_s"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0


def test_compiles_count_cache_answers_and_reloads_go_to_the_event(rehearsal):
    """[compiles, cache loads, reloads]: a program built again counts as a
    compile until an event takes it; one set-up never built always counts,
    also where the persistent cache answers it."""
    c = rehearsal["compile_counts"]
    assert c["before_event"] == [1, 1]
    assert c["after_event"] == [1, 2, 1]
    assert c["new_shape"] == [2, 2, 1]
    assert c["cached_shape"] == [3, 3, 1]


def test_gang_weights_are_the_one_device_tree_spread_over_the_mesh(rehearsal):
    w = rehearsal["weights"]
    assert w["identical"]
    assert len(w["held_bytes"]) == 4 and max(w["held_bytes"]) < w["tree_bytes"] / 2


def test_a_lost_kv_handoff_is_not_correct(rehearsal):
    """The requests carried across the event are always compared, so a
    resize that loses their state fails the check."""
    res = rehearsal["lost_handoff"]
    assert res["window"]["events"][0]["live"] > 0
    assert not res["correct"]
    assert res["check"][check.GAP]["value"] > res["check"][check.GAP]["limit"]


def _write_cell(tmp_path, changes):
    """The gang cell with ``changes`` (None drops a key), as cell ``bad``."""
    root = tmp_path / "bench"
    shutil.copytree(DATA, root)
    cell = json.loads((root / "workloads" / f"{CELL}.json").read_text())
    for key, value in changes.items():
        if value is None:
            del cell[key]
        else:
            cell[key] = value
    (root / "workloads" / "bad.json").write_text(json.dumps(cell))
    return root


def _event(at_s, members, grace_s=30):
    return {"at_s": at_s, "members": members, "grace_s": grace_s}


@pytest.mark.parametrize("changes,reason", [
    ({"events": [_event(3.2, 2)]}, "traced span"),
    ({"events": [_event(2.5, 2)]}, "within 1.0 s of the traced span"),
    ({"events": [_event(1.0, 2), _event(0.5, 1)]}, "time order"),
    ({"events": [_event(1.0, 0)]}, "resizes 4 members to 0"),
    ({"events": [_event(1.0, 4)]}, "resizes 4 members to 4"),
    ({"events": [_event(1.0, 2, 0)]}, "grace"),
    ({"gang": {"members": 8, "kv_mode": "migrate"}}, "8 members on 4 chips"),
    ({"gang": {"members": 4, "kv_mode": "teleport"}}, "kv_mode"),
    ({"gang": None}, "events need a gang"),
])
def test_a_gang_cell_breaking_the_rules_is_refused_before_setup(tmp_path, monkeypatch,
                                                                changes, reason):
    root = _write_cell(tmp_path, changes)

    def no_setup(*args, **kwargs):
        raise AssertionError("set-up started")
    monkeypatch.setattr(harness, "serve", no_setup)
    bench = {"workloads": [{"name": "bad", "chips": 4}], "end_to_end": [], "per_layer": []}
    with pytest.raises(spec.CellError, match=reason):
        harness.run("bad", 1, 4.0, False, time.perf_counter(), {}, bench=bench, root=root)


def test_the_pause_is_held_to_the_grace():
    cell = spec.cell(CELL, DATA, 4)
    w = harness.Window(recs=[], t_open=0.0, t_close=4.0, t_end=4.0, counters_open={},
                       counters_close={}, compiles=0, queue_open=0, queue_close=0)
    numbers = harness.correctness(cell, 1, [], w)
    assert numbers[harness.PAUSE] == {"value": float("inf"), "limit": 30}   # never fired
    ok = {check.GAP: {"value": 0.0, "limit": 0.02}, "tokens_compared": {"value": 200, "limit": 100}}
    assert harness.passed({**ok, harness.PAUSE: {"value": 29.9, "limit": 30}})
    assert not harness.passed({**ok, harness.PAUSE: {"value": 30.1, "limit": 30}})
    assert not harness.passed({**ok, harness.PAUSE: {"value": 1.0, "limit": 30},
                               harness.PAUSE + ".1": {"value": 31.0, "limit": 30}})


class _StalledGang:
    """A gang whose resize leaves no request to serve: no engine call
    follows the event, so it never settles."""

    n_members, mesh, params = 4, None, None

    def __init__(self):
        self.engine = types.SimpleNamespace(
            batcher=types.SimpleNamespace(active=lambda: False, waiting=[]), device_state=None,
            n_decode_steps=0, n_slot_steps=0, n_emitted=0, prefill_tokens=0)

    def resize(self, n):
        self.n_members = n
        return types.SimpleNamespace(bytes_moved=0, wire_bytes=0, n_requests_live=0)


def test_an_event_not_over_when_the_traced_span_opens_fails_the_run(tmp_path):
    """The first engine call after an event runs programs of its own; one
    still to come when the profiler starts would be read as a prefill."""
    cell = dict(spec.cell(CELL, DATA, 4), events=[_event(0.1, 2)])
    traffic = gen.Traffic(cell["traffic_spec"], 1e-6, 64, 1)
    with harness._CompileCounter() as compiles:
        with pytest.raises(spec.CellError, match="not over when the traced span opened"):
            harness.drive(_StalledGang(), traffic, cell, 1.2, compiles, tmp_path)
