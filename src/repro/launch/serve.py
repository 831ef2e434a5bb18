"""Serving driver: stand up a continuous-batching engine for a (reduced)
arch and serve generate requests — the FaaS function an HPC-Whisk invoker
hosts. The FULL-config serve_step is exercised by launch/dryrun.py (decode
cells). ``--sequential`` falls back to the run-to-completion baseline for
comparison; SIGTERM drains partial generations (the invoker hand-off path).
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.batching import GenRequest
from repro.serving.engine import ContinuousEngine, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token: finished slots free early")
    ap.add_argument("--sequential", action="store_true",
                    help="run-to-completion baseline instead of continuous batching")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_seq = args.prompt_len + args.new_tokens + 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len).tolist()
               for _ in range(args.requests)]

    t0 = time.time()
    if args.sequential:
        engine = ServingEngine(cfg, params, max_seq=max_seq)
        done = []
        for i, p in enumerate(prompts):
            out = engine.generate(np.asarray([p], np.int32), args.new_tokens)
            done.append(GenRequest(id=i, prompt=p, max_new=args.new_tokens,
                                   generated=out[0].tolist(), done=True))
        n_tok = sum(len(r.generated) for r in done)
        occ = 1.0
    else:
        engine = ContinuousEngine(cfg, params, n_slots=args.batch_slots,
                                  max_seq=max_seq, eos_id=args.eos_id)
        # SIGTERM = invoker preemption: hand partials back for resubmit()
        signal.signal(signal.SIGTERM, lambda *_: (_drain_and_exit(engine)))
        for i, p in enumerate(prompts):
            engine.add(GenRequest(id=i, prompt=p, max_new=args.new_tokens))
        done = engine.run()
        n_tok = sum(len(r.generated) for r in done)
        occ = engine.occupancy
    dt = time.time() - t0
    mode = "sequential" if args.sequential else \
        f"continuous x{args.batch_slots} slots (occupancy {occ:.0%})"
    print(f"served {len(done)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s on {jax.devices()[0].platform}, reduced "
          f"config, {mode})")


def _drain_and_exit(engine: ContinuousEngine):
    partials = engine.drain()
    print(f"SIGTERM: drained {len(partials)} in-flight requests "
          f"({sum(len(p.generated) for p in partials)} partial tokens kept "
          f"for resubmit)")
    raise SystemExit(143)


if __name__ == "__main__":
    main()
