"""End-to-end behaviour tests for the whole system: the paper's harvest layer
driving REAL JAX inference, training with failure/restart, and the
benchmark-level claims (reduced durations)."""
import dataclasses
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.train import TrainConfig, train
from repro.models import init_params
from repro.platform import (BatchedServingExecutor, Platform, ScenarioConfig,
                            SchedulingSection, TraceSection, WorkloadSection)
from repro.serving.engine import ContinuousEngine

pytestmark = pytest.mark.slow  # JAX tier: excluded from the fast core-sim run

HOUR = 3600.0


def test_harvest_executes_real_jax_inference():
    """Invokers run actual model decodes; measured wall time advances the
    virtual clock; everything accepted completes."""
    cfg = get_config("qwen2.5-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ContinuousEngine(cfg, params, n_slots=4, max_seq=48)
    sc = ScenarioConfig(duration=900.0, seed=0, trace=TraceSection(seed=4),
                        workload=WorkloadSection(qps=0.2, n_functions=4),
                        scheduling=SchedulingSection(model="fib"))
    rt = Platform.build(sc, executor=BatchedServingExecutor(
        engine, prompt_len=8, n_new=4))
    res = rt.run()
    done = [r for r in res.requests if r.outcome == "success"]
    assert len(done) >= 1
    # real execution time must be visible in the response times
    rts = [r.response_time for r in done]
    assert min(rts) > 0.0


def test_train_failure_restart_continues_loss_curve():
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(arch="internlm2-1.8b", smoke=True, steps=12,
                         global_batch=4, seq_len=32, ckpt_dir=d,
                         ckpt_every=6, log_every=3, lr=2e-3)
        _, _, h1 = train(dataclasses.replace(tc, steps=6))
        _, _, h2 = train(tc)  # resumes from step 6
        assert h2[0][0] > 6  # continued, not restarted
        assert h2[-1][1] < h1[0][1] + 0.5  # loss did not blow up


def test_fib_day_headline_numbers():
    """Reduced (3h) version of Table II: coverage close to the clairvoyant
    bound, high invoked share."""
    sc = ScenarioConfig.fib_day(3 * HOUR, qps=2.0)
    sc.workload.non_interruptible_share = 0.0
    res = Platform.build(sc).run()
    assert res.slurm_coverage > 0.75
    assert res.slurm_coverage > 0.85 * res.sim_upper_bound
    assert res.invoked_share > 0.9


def test_examples_run():
    """quickstart must execute cleanly (the other examples are long-running)."""
    proc = subprocess.run(
        [sys.executable, "examples/quickstart.py"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "coverage=" in proc.stdout
