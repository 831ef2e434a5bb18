"""The paper's scenario end-to-end WITH REAL JAX EXECUTION: a cluster's idle
windows host pilot-job invokers that serve actual model inference (bounded
decode on a reduced qwen2.5 config). Virtual time advances by the measured
wall-clock of each request's real decode.

This is HPC-Whisk as a serving system: dynamic registration, fast-lane
hand-off on preemption, Alg. 1 commercial fallback — with the FaaS function
being a bounded decode. Concurrent in-flight requests on an invoker are
aggregated onto one ContinuousEngine (continuous batching: per-slot decode
positions, one batched decode per token wave) via the ``batched-serving``
executor.

Run: PYTHONPATH=src python examples/harvest_serving.py [--minutes 20]
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core import CommercialBackend, FaaSWrapper
from repro.models import init_params
from repro.platform import (BatchedServingExecutor, Platform, ScenarioConfig,
                            SchedulingSection, TraceSection, WorkloadSection)
from repro.serving.engine import ContinuousEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=20.0)
    ap.add_argument("--qps", type=float, default=0.5)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()
    duration = args.minutes * 60.0

    print("loading model (the invoker warm-up the paper measures)...")
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    executor = BatchedServingExecutor(
        ContinuousEngine(cfg, params, n_slots=args.slots, max_seq=64),
        prompt_len=16, n_new=8)

    sc = ScenarioConfig(
        name="harvest_serving", duration=duration, seed=0,
        trace=TraceSection(seed=4),
        workload=WorkloadSection(qps=args.qps, n_functions=10),
        scheduling=SchedulingSection(model="fib"))
    # same construction path as sim-only runs; only the executor seam differs
    rt = Platform.build(sc, executor=executor)

    # Alg. 1 wrapper in front of the controller
    commercial = CommercialBackend(rt.sim, overhead=0.35, slowdown=1.176)
    wrapper = FaaSWrapper(rt.sim, rt.controller, commercial)

    res = rt.run()
    done = [r for r in res.requests if r.outcome == "success"]
    rts = [r.response_time for r in done if r.response_time is not None]
    print(f"\n{args.minutes:.0f} simulated minutes, {len(res.requests)} requests")
    print(f"  coverage          : {res.slurm_coverage:.1%} "
          f"(clairvoyant {res.sim_upper_bound:.1%})")
    print(f"  invoked / success : {res.invoked_share:.1%} / {res.success_share:.1%}")
    print(f"  pilots / evictions: {res.n_jobs_started} / {res.n_evicted}")
    if rts:
        print(f"  response p50      : {np.percentile(rts, 50):.3f}s "
              f"(REAL decode wall-time inside virtual time)")
    eng = executor.engine
    print(f"  batched decode    : {eng.n_decode_steps} waves, "
          f"occupancy {eng.occupancy:.0%}" if eng.n_decode_steps else
          "  batched decode    : (no batched waves)")
    print(f"  executed tokens   : ~{len(done) * 8} real greedy-decoded tokens")


if __name__ == "__main__":
    main()
