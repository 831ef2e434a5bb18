import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the very first lines, before ANY jax-touching import: jax locks the
#   device count on first init. The 512 placeholder host devices exist ONLY in
#   this entrypoint; tests and benches see 1 device.

_DOC = """Multi-pod dry-run: lower + compile EVERY runnable (architecture x input
shape) cell on the single-pod (16,16) and multi-pod (2,16,16) production
meshes, print memory_analysis()/cost_analysis(), and derive the roofline
terms (launch/roofline.py).

FLOP/byte/collective accounting: XLA's cost model counts a lax.scan body once,
so per-cell we also compile two reduced-depth UNROLLED twins (depth 1 and 2
segment units) and extrapolate linearly in depth — exact for depth-linear
stacks. The FULL scanned compile is still performed as the fits/shards proof.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
      --shape train_4k --mesh single --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse  # noqa: E402  (XLA_FLAGS must precede all imports)
import dataclasses
import functools
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES_BY_NAME, get_config
from repro.configs.base import ModelConfig, ShapeConfig, cell_is_runnable
from repro.distributed.sharding import (cache_shardings, input_shardings,
                                        param_shardings)
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train_step import make_train_step


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (weak-type-correct, shardable, no device allocation)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        batch: Dict[str, Any] = {}
        if cfg.frontend == "audio":
            batch["frames"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        elif cfg.frontend == "vision":
            batch["vision_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_seq, cfg.d_model), jnp.bfloat16)
            batch["tokens"] = jax.ShapeDtypeStruct((b, s - cfg.frontend_seq), i32)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        if shape.kind == "train":
            if cfg.frontend == "audio":
                batch["labels"] = jax.ShapeDtypeStruct((b, s), i32)
            elif cfg.frontend == "vision":
                batch["labels"] = jax.ShapeDtypeStruct((b, s - cfg.frontend_seq), i32)
            else:
                batch["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        return batch
    # decode: one new token against an s-token cache
    return {
        "token": jax.ShapeDtypeStruct((b, 1), i32),
        "cache": M.cache_spec(cfg, b, s),
        "pos": jax.ShapeDtypeStruct((), i32),
    }


def _params_shape(cfg: ModelConfig):
    return jax.eval_shape(functools.partial(M.init_params, cfg=cfg),
                          jax.random.PRNGKey(0))


def _build(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Returns (fn, args, in_shardings, donate) for the cell."""
    params = _params_shape(cfg)
    p_sh = param_shardings(params, cfg, mesh)
    if shape.kind == "train":
        opt = jax.eval_shape(init_opt_state, params)
        opt_sh = {"m": p_sh, "v": p_sh, "step": NamedSharding(mesh, P())}
        batch = input_specs(cfg, shape)
        b_sh = input_shardings(batch, mesh)
        fn = make_train_step(cfg, OptimizerConfig())
        return fn, (params, opt, batch), (p_sh, opt_sh, b_sh), (0, 1)
    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        b_sh = input_shardings(batch, mesh)
        fn = functools.partial(M.prefill, cfg=cfg)
        return fn, (params, batch), (p_sh, b_sh), ()
    specs = input_specs(cfg, shape)
    cache_sh = cache_shardings(specs["cache"], cfg, mesh, shape.global_batch,
                               seq_shard=cfg.shard_activations)
    tok_sh = input_shardings({"t": specs["token"]}, mesh)["t"]
    fn = functools.partial(M.decode_step, cfg=cfg)
    args = (params, specs["token"], specs["cache"], specs["pos"])
    shardings = (p_sh, tok_sh, cache_sh, NamedSharding(mesh, P()))
    return fn, args, shardings, (2,)


def _compile_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    fn, args, shardings, donate = _build(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def _metrics(compiled) -> Tuple[float, float, Dict[str, int], Dict[str, float]]:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    coll = RL.collective_bytes(compiled.as_text())
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {
            "argument_bytes_per_dev": int(ma.argument_size_in_bytes),
            "output_bytes_per_dev": int(ma.output_size_in_bytes),
            "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
            "alias_bytes_per_dev": int(ma.alias_size_in_bytes),
        }
    return flops, nbytes, coll, mem


def _probe_depths(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(depth_a, depth_b, units_a, n_units) in n_layers terms. Probes use 2
    and 3 segment units (depth-1 modules tempt XLA into different embed/head
    partitioning choices, breaking linearity); extrapolation:
    total = f_a + (n_units - units_a) * (f_b - f_a)."""
    if cfg.family == "hybrid":
        return (2 * cfg.attn_every, 3 * cfg.attn_every, 2,
                cfg.n_layers // cfg.attn_every)
    if cfg.family == "moe" and cfg.first_dense_layers:
        fd = cfg.first_dense_layers
        return fd + 2, fd + 3, 2, cfg.n_layers - fd
    return 2, 3, 2, cfg.n_layers


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             remat: Optional[str] = None, probes: bool = True,
             moe_impl: Optional[str] = None,
             shard_activations: bool = False,
             param_dtype: Optional[str] = None,
             ssm_chunk: Optional[int] = None) -> Dict[str, Any]:
    shape = SHAPES_BY_NAME[shape_name]
    cfg = get_config(arch)
    overrides: Dict[str, Any] = {}
    if shape.kind == "train":
        overrides["remat"] = remat if remat is not None else "dots_saveable"
    elif remat is not None:
        overrides["remat"] = remat
    if moe_impl is not None:
        overrides["moe_impl"] = moe_impl
    if shard_activations:
        overrides["shard_activations"] = True
    if param_dtype is not None:
        overrides["param_dtype"] = param_dtype
    if ssm_chunk is not None and cfg.ssm_state:
        overrides["ssm_chunk"] = ssm_chunk
    cfg = dataclasses.replace(cfg, **overrides)
    ok, why = cell_is_runnable(cfg, shape)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "overrides": overrides}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    chips = mesh.devices.size
    try:
        t0 = time.time()
        _, compiled = _compile_cell(cfg, shape, mesh)
        rec["compile_s"] = round(time.time() - t0, 1)
        flops_full, bytes_full, coll_full, mem = _metrics(compiled)
        rec["memory_analysis"] = mem
        rec["scan_body_once"] = {"flops": flops_full, "bytes": bytes_full,
                                 "coll": coll_full}
        if probes:
            d1, d2, units_a, n_units = _probe_depths(cfg)
            probe_metrics = []
            for d in (d1, d2):
                pcfg = dataclasses.replace(cfg, n_layers=d, unroll=True)
                t1 = time.time()
                _, pc = _compile_cell(pcfg, shape, mesh)
                f, by, co, _ = _metrics(pc)
                probe_metrics.append((f, by, co, round(time.time() - t1, 1)))
            (f1, b1, c1, t_1), (f2, b2, c2, t_2) = probe_metrics
            extra = n_units - units_a
            df, db = f2 - f1, b2 - b1
            dcoll = {k: c2.get(k, 0) - c1.get(k, 0)
                     for k in set(c1) | set(c2)}
            flops = f1 + extra * df
            nbytes = b1 + extra * db
            coll = {k: max(int(c1.get(k, 0) + extra * dcoll.get(k, 0)), 0)
                    for k in set(c1) | set(c2)}
            rec["probe_compile_s"] = [t_1, t_2]
        else:
            flops, nbytes, coll = flops_full, bytes_full, coll_full
        terms = RL.build_terms(flops, nbytes, coll, chips, cfg, shape)
        rec["roofline"] = terms.row()
        rec["status"] = "ok"
    except Exception as e:  # a failure here is a bug in our sharding config
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--shard-activations", action="store_true")
    ap.add_argument("--param-dtype", default=None,
                    help="override param dtype (e.g. bfloat16 for serving)")
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = args.arch or list(ARCH_IDS)
    shapes = args.shape or list(SHAPES_BY_NAME)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], json.dumps(r.get("overrides", {}), sort_keys=True))
            for r in existing}

    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_name = "multi" if multi else "single"
        for arch in archs:
            for shape_name in shapes:
                key_overrides: Dict[str, Any] = {}
                if SHAPES_BY_NAME[shape_name].kind == "train":
                    key_overrides["remat"] = args.remat or "dots_saveable"
                elif args.remat:
                    key_overrides["remat"] = args.remat
                if args.moe_impl:
                    key_overrides["moe_impl"] = args.moe_impl
                if args.shard_activations:
                    key_overrides["shard_activations"] = True
                if args.param_dtype:
                    key_overrides["param_dtype"] = args.param_dtype
                if args.ssm_chunk:
                    key_overrides["ssm_chunk"] = args.ssm_chunk
                key = (arch, shape_name, mesh_name,
                       json.dumps(key_overrides, sort_keys=True))
                if key in done:
                    continue
                print(f"=== {arch} x {shape_name} x {mesh_name} ===", flush=True)
                rec = run_cell(arch, shape_name, mesh, mesh_name,
                               remat=args.remat, probes=not args.no_probes,
                               moe_impl=args.moe_impl,
                               shard_activations=args.shard_activations,
                               param_dtype=args.param_dtype,
                               ssm_chunk=args.ssm_chunk)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" frac={r['roofline_fraction']:.3f}"
                             f" compile={rec.get('compile_s')}s")
                elif status == "error":
                    extra = " " + rec["error"][:200]
                else:
                    extra = " " + rec["reason"]
                print(f"  -> {status}{extra}", flush=True)
                existing.append(rec)
                with open(args.out, "w") as f:
                    json.dump(existing, f, indent=1)
    print(f"wrote {args.out} ({len(existing)} records)")


if __name__ == "__main__":
    main()
