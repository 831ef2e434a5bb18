"""Tests for the composable platform layer (repro.platform): registry and
scenario round-trips, protocol conformance of every bundled component, the
router seam, and the bit-for-bit regression pinning the ``hash`` router to
the pre-refactor Controller behaviour on fixed seeds."""
import hashlib
import json
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Controller, Invoker, Request, Simulator
from repro.platform import (AdmissionPolicy, Executor, HarvestConfig,
                            HarvestRuntime, HashRouter, LeastLoadedRouter,
                            LocalityRouter, Platform, Router, Scaler,
                            ScenarioConfig, SchedulingSection, SimExecutor,
                            WorkloadSection, WorkloadSource, available,
                            register, resolve)

HOUR = 3600.0


# --- registry -----------------------------------------------------------------
def test_registry_resolves_bundled_components():
    assert {"hash", "least-loaded", "locality",
            "deadline-aware"} <= set(available("router"))
    assert {"static", "adaptive"} <= set(available("scaler"))
    assert {"none", "slo"} <= set(available("admission"))
    assert {"uniform", "suite"} <= set(available("workload"))
    assert {"sim", "batched-serving"} <= set(available("executor"))
    assert {"default", "burst"} <= set(available("suite"))
    assert {"none", "retry"} <= set(available("reliability"))
    assert resolve("router", "hash") is HashRouter


def test_registry_unknown_name_lists_available():
    with pytest.raises(KeyError, match="least-loaded"):
        resolve("router", "does-not-exist")
    with pytest.raises(KeyError, match="unknown component kind"):
        resolve("nonsense", "hash")


def test_registry_rejects_duplicate_registration():
    with pytest.raises(KeyError, match="duplicate"):
        register("router", "hash")(LeastLoadedRouter)


# --- scenario config ----------------------------------------------------------
@pytest.mark.parametrize("preset", ["fib_day", "var_day",
                                    "multi_tenant_steady",
                                    "multi_tenant_burst",
                                    "preemption_storm", "churn_day"])
def test_scenario_round_trips_through_dict_and_json(preset):
    cfg = getattr(ScenarioConfig, preset)()
    assert ScenarioConfig.from_dict(cfg.to_dict()) == cfg
    assert ScenarioConfig.from_json(cfg.to_json()) == cfg


def test_scenario_round_trips_with_overrides(tmp_path):
    cfg = ScenarioConfig.multi_tenant_burst(duration=2 * HOUR)
    cfg.platform.router = "locality"
    cfg.scheduling.scaler_params = {"base_per_length": 6}
    cfg.trace.params = {"slack_hi": 2.0}
    path = tmp_path / "scenario.json"
    path.write_text(cfg.to_json())
    cfg2 = ScenarioConfig.from_file(str(path))
    assert cfg2 == cfg
    assert json.loads(cfg.to_json())["platform"]["router"] == "locality"


# --- protocol conformance ------------------------------------------------------
def test_bundled_routers_conform_to_protocol():
    for name in available("router"):
        router = resolve("router", name)()
        assert isinstance(router, Router), name


def test_bundled_components_conform_to_protocols():
    sc = ScenarioConfig(duration=600.0, workload=WorkloadSection(qps=0.5))
    p = Platform.build(sc)
    assert isinstance(p.router, Router)
    assert isinstance(p.scaler, Scaler)          # JobManager
    assert isinstance(p.workload, WorkloadSource)
    assert isinstance(p.executor, Executor)
    sc = ScenarioConfig.multi_tenant_burst(duration=600.0, scaler="adaptive")
    p = Platform.build(sc)
    assert isinstance(p.scaler, Scaler)          # AdaptiveJobManager
    assert isinstance(p.admission, AdmissionPolicy)
    assert isinstance(p.workload, WorkloadSource)


def test_scaler_start_is_idempotent():
    sc = ScenarioConfig(duration=600.0, workload=WorkloadSection(qps=0.0))
    p = Platform.build(sc)
    n_events = len(p.sim._heap)
    p.scaler.start()                # Platform already started it
    assert len(p.sim._heap) == n_events


# --- routers -------------------------------------------------------------------
def _fleet(n=4):
    sim = Simulator()
    ctrl = Controller(sim)
    rng = np.random.default_rng(0)
    invs = [Invoker(sim, ctrl, node=i, sched_end=4000.0, rng=rng)
            for i in range(n)]
    sim.run_until(60.0)             # p95 warm-up is 26.5 s; all healthy now
    assert ctrl.healthy_count() == n
    return sim, ctrl, invs


def test_hash_router_matches_openwhisk_reference():
    """The seam default must reproduce the pre-refactor inline algorithm:
    sha1 home invoker + overload stepping over the sorted healthy ids."""
    sim, ctrl, invs = _fleet(5)
    assert isinstance(ctrl.router, HashRouter)

    def reference(fn):
        order = ctrl.healthy_order
        start = int.from_bytes(hashlib.sha1(fn.encode()).digest()[:4],
                               "big") % len(order)
        for step in range(len(order)):
            cand = order[(start + step) % len(order)]
            if len(ctrl.topics[cand]) < ctrl.queue_depth_soft_limit:
                return cand
        return order[start]

    for i in range(300):
        fn = f"fn-{i:03d}"
        req = Request(fn=fn, exec_time=0.01, arrival=sim.now)
        assert ctrl.router.route(req, ctrl) == reference(fn), fn


def test_hash_router_steps_past_overloaded_home():
    sim, ctrl, invs = _fleet(2)
    req = Request(fn="f", exec_time=0.01, arrival=sim.now)
    home = ctrl.router.route(req, ctrl)
    other = next(i for i in ctrl.healthy_order if i != home)
    for _ in range(ctrl.queue_depth_soft_limit):
        ctrl.topics[home].push(Request(fn="x", exec_time=1.0, arrival=sim.now))
    assert ctrl.router.route(req, ctrl) == other


def test_least_loaded_router_picks_min_backlog():
    sim, ctrl, invs = _fleet(3)
    router = LeastLoadedRouter()
    a, b, c = ctrl.healthy_order
    for _ in range(3):
        ctrl.topics[a].push(Request(fn="x", exec_time=1.0, arrival=sim.now))
    ctrl.topics[b].push(Request(fn="y", exec_time=1.0, arrival=sim.now))
    req = Request(fn="f", exec_time=0.01, arrival=sim.now)
    assert router.route(req, ctrl) == c


def test_locality_router_sticks_and_rehomes():
    sim, ctrl, invs = _fleet(3)
    router = LocalityRouter()
    req = Request(fn="hot", exec_time=0.01, arrival=sim.now)
    first = router.route(req, ctrl)
    # other functions pile load elsewhere; "hot" stays put (warm containers)
    for i in ctrl.healthy_order:
        if i != first:
            ctrl.topics[i].push(Request(fn="x", exec_time=1.0,
                                        arrival=sim.now))
    assert router.route(req, ctrl) == first
    # losing the affinity target re-homes the function
    inv = ctrl.invokers[first]
    ctrl.deregister(inv)
    router.on_deregister(inv)       # controller calls this when injected
    assert "hot" not in router.affinity
    second = router.route(req, ctrl)
    assert second != first and second in ctrl.healthy_order


def test_router_seam_is_injected_end_to_end():
    """A custom router injected via the registry actually controls placement."""

    @register("router", "_test-first-healthy")
    class FirstHealthyRouter(HashRouter):
        def route(self, req, ctrl):
            return ctrl.healthy_order[0] if ctrl.healthy_order else None

    sc = ScenarioConfig(duration=1200.0,
                        workload=WorkloadSection(qps=2.0),
                        scheduling=SchedulingSection(model="fib"))
    sc.platform.router = "_test-first-healthy"
    p = Platform.build(sc)
    assert isinstance(p.controller.router, FirstHealthyRouter)
    res = p.run()
    assert all(r.outcome is not None for r in res.requests)


def test_admission_released_when_router_refuses_placement():
    """A router may return None after admission admitted the request; the
    503 must give back the in-flight slot or the function's concurrency cap
    leaks shut permanently."""
    from repro.faas import AdmissionController, default_slos

    class NoneRouter(HashRouter):
        def route(self, req, ctrl):
            return None

    sim = Simulator()
    adm = AdmissionController(default_slos())
    ctrl = Controller(sim, admission=adm, router=NoneRouter())
    Invoker(sim, ctrl, node=0, sched_end=4000.0,
            rng=np.random.default_rng(0))
    sim.run_until(60.0)
    reqs = [Request(fn="hot", exec_time=0.01, arrival=sim.now,
                    slo_class="latency") for _ in range(10)]
    for r in reqs:
        assert ctrl.submit(r) is False
        assert r.reject_reason == "no_invoker"
    assert adm.inflight("hot") == 0
    assert adm.inflight_total() == 0


# --- regression: hash router pins the pre-refactor behaviour -------------------
def test_hash_run_reproduces_pre_refactor_numbers_bit_for_bit():
    """Golden values for the quickstart scenario: seed 0, 1 h, 5 QPS, fib,
    hash routing. Exact float equality on every reported share.

    Originally captured from the pre-seam ``HarvestRuntime`` (commit
    f98a1af). Re-pinned once for the tie-order RNG decoupling: every
    event-time draw moved to a stable identity key (schedule-time request
    attributes, per-invoker spawn streams, jittered proactive drains), which
    re-seeds the day's randomness while leaving the mechanisms untouched.
    The tie-order fuzz (test_tie_order.py) proves these numbers no longer
    depend on event insertion order at equal timestamps."""
    sc = ScenarioConfig(duration=3600.0, seed=0,
                        workload=WorkloadSection(qps=5.0),
                        scheduling=SchedulingSection(model="fib"))
    res = Platform.build(sc).run()
    assert res.n_submitted == 17999
    assert res.outcome_counts == {"success": 8672, "503": 9327}
    assert res.slurm_coverage == 0.7176793559830099
    assert res.sim_upper_bound == 0.5765852603243591
    assert res.response_p50 == 0.5900000000001455
    assert res.response_p95 == 0.5900000000001455
    assert res.invoked_share == 0.48180454469692763
    assert res.success_share == 1.0
    assert res.n_jobs_started == 12
    assert res.n_evicted == 8
    assert float(np.mean(res.worker_samples["healthy"])) == 0.7340720221606648


def test_hash_multi_tenant_run_reproduces_pre_refactor_numbers():
    """Same pin for the platform-layer path (burst suite + SLO admission +
    static supply, 1 h): scenario construction, admission, and per-request
    RNG draws all interleave exactly as before the seam refactor.

    p95 was re-pinned once, for the PR-4 warm-container LRU fix (last-use now
    stamped at completion, in-flight functions exempt from eviction): the
    recency change shifts a handful of warm/cold decisions, moving p95 from
    0.8669291062664568 while every other number stays bit-identical.

    Re-pinned again for the tie-order RNG decoupling (see the quickstart
    golden above): suite attribute draws moved to schedule time and SlurmSim
    seeds its identity-keyed draw streams at construction, which shifts the
    shared stream (n_submitted moves from 61346) without touching the
    arrival or admission mechanisms."""
    sc = ScenarioConfig.multi_tenant_burst(duration=3600.0, scaler="static")
    res = Platform.build(sc).run()
    assert res.n_submitted == 61340
    assert res.outcome_counts == {"success": 34249, "503": 27091}
    assert res.slurm_coverage == 0.82375880636139
    assert res.n_throttled == 26747
    assert res.response_p95 == 0.870131095641609


def test_facade_matches_platform_build():
    """HarvestRuntime(cfg, ...) is a pure façade: same numbers as the
    scenario path, and the legacy attribute surface still works."""
    cfg = HarvestConfig(model="fib", duration=3600.0, qps=5.0, seed=0)
    rt = HarvestRuntime(cfg)
    assert rt.sim is rt.platform.sim
    assert rt.controller is rt.platform.controller
    res = rt.run()
    assert res.n_submitted == 17999
    assert res.slurm_coverage == 0.7176793559830099


# --- satellite fixes -----------------------------------------------------------
def test_submit_treats_zero_as_explicit_value():
    sc = ScenarioConfig(duration=60.0, workload=WorkloadSection(qps=0.0))
    p = Platform.build(sc, windows=[])
    p.sim.at(1.0, p.submit, "zero-exec", 0.0, 0.0)
    p.sim.at(2.0, p.submit, "defaulted")
    p.run()
    by_fn = {r.fn: r for r in p.requests}
    assert by_fn["zero-exec"].exec_time == 0.0
    assert by_fn["zero-exec"].timeout == 0.0
    assert by_fn["defaulted"].exec_time == sc.workload.exec_time
    assert by_fn["defaulted"].timeout == sc.workload.timeout


def test_percentiles_are_nan_when_nothing_succeeded():
    # no windows in the first 10 min -> every request 503s
    sc = ScenarioConfig(duration=600.0, workload=WorkloadSection(qps=1.0))
    p = Platform.build(sc, windows=[])
    res = p.run()
    assert res.outcome_counts.get("503", 0) == res.n_submitted > 0
    assert np.isnan(res.response_p50) and np.isnan(res.response_p95)
    assert np.isnan(res.success_share)
    assert "n/a" in res.summary()   # formatting stays printable


def test_executor_seam_sim_executor_is_default():
    sc = ScenarioConfig(duration=60.0, workload=WorkloadSection(qps=0.0))
    p = Platform.build(sc, windows=[])
    assert isinstance(p.executor, SimExecutor)
    r = Request(fn="f", exec_time=0.125, arrival=0.0)
    assert p.executor(r) == 0.125


# --- tooling -------------------------------------------------------------------
def test_import_layering_lint_passes():
    proc = subprocess.run([sys.executable, "tools/lint_imports.py"],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_bench_driver_list_and_unknown_only():
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    proc = subprocess.run([sys.executable, "-m", "benchmarks.run", "--list"],
                          capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    names = proc.stdout.split()
    assert "routing" in names and "multitenant" in names
    assert "reliability" in names
    proc = subprocess.run([sys.executable, "-m", "benchmarks.run",
                           "--only", "definitely-not-a-bench"],
                          capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode != 0
    assert "definitely-not-a-bench" in proc.stderr
