"""Quickstart: the three layers of the framework in ~60 seconds on CPU.

1. model zoo      — instantiate any assigned arch (reduced config), run a
                    train step and a decode step
2. harvest layer  — the paper's contribution: simulate 1 hour of an HPC
                    cluster harvesting idle nodes into FaaS capacity
3. dry-run        — what launch/dryrun.py does per cell (shown on a 1-device
                    mesh here; the real thing uses 256/512 placeholder devices)

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import DataPipeline
from repro.platform import Platform, ScenarioConfig, SchedulingSection, \
    WorkloadSection
from repro.models import init_params
from repro.serving.batching import GenRequest
from repro.serving.engine import ContinuousEngine
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train_step import make_train_step

print("== 1. model zoo ==")
print("assigned architectures:", ", ".join(ARCH_IDS))
cfg = get_config("qwen2.5-3b", smoke=True)
params = init_params(jax.random.PRNGKey(0), cfg)
pipe = DataPipeline(cfg, global_batch=4, seq_len=64, seed=0)
step = jax.jit(make_train_step(cfg, OptimizerConfig(lr=1e-3)))
opt = init_opt_state(params)
batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
params, opt, metrics = step(params, opt, batch)
print(f"train step: loss={float(metrics['loss']):.3f}")

engine = ContinuousEngine(cfg, params, n_slots=1, max_seq=48)
engine.add(GenRequest(id=0, prompt=[1] * 8, max_new=8))
(done,) = engine.run()
print(f"decode: generated tokens {done.generated}")

print("\n== 2. harvest layer (the paper) ==")
sc = ScenarioConfig(name="quickstart", duration=3600.0, seed=0,
                    workload=WorkloadSection(qps=5.0),
                    scheduling=SchedulingSection(model="fib"))
res = Platform.build(sc).run()
print(f"1h of cluster time: coverage={res.slurm_coverage:.1%} "
      f"(clairvoyant bound {res.sim_upper_bound:.1%}), "
      f"invoked={res.invoked_share:.1%}, pilots started={res.n_jobs_started}, "
      f"evicted={res.n_evicted}")

print("\n== 3. dry-run (1-device demo) ==")
from repro.launch.dryrun import input_specs
from repro.configs import SHAPES_BY_NAME
specs = input_specs(cfg, SHAPES_BY_NAME["train_4k"])
print("train_4k input specs:",
      {k: (v.shape, str(v.dtype)) for k, v in specs.items()})
print("full dry-run: PYTHONPATH=src python -m repro.launch.dryrun --all")
