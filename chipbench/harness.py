"""One run of one cell: set up, drive the traffic for the window, drain,
check what was served against the reference, and reduce it all to the
result line.

The window drives ``ContinuousEngine.add`` (admission: batch-1 prefill and
the graft into a slot row) and ``ContinuousEngine.step`` (one batched decode
over every slot, then refills) from one host loop. Every engine call ends on
the host (``step`` and admission read their tokens back), so the host clock
after a call is when its tokens reached the caller.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import check
import counts
import gen
import spec

CACHE_DIR = spec.CHECKOUT / ".jax_cache"
TRACE_ROOT = spec.HERE / ".traces"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Traffic runs this long before the window opens (counted in set-up), so the
# window starts with the slots already filling rather than from an empty engine.
RAMP_S = 4.0
# The traced span is the window's last TRACE_S seconds: one whole burst period
# of the bursty mix (8 s), so it holds a burst and the quiet after it; on a
# steady mix it holds some hundreds of decode waves.
TRACE_S = 8.0


def configure_jax() -> str:
    """Persistent compile cache at a fixed path inside the checkout, with
    every compile written to it, so only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chip(chips: int) -> Dict[str, Any]:
    """The device record; exits non-zero off a TPU, with interpreted
    kernels, or with fewer chips than the cell asks for."""
    import jax

    from repro.kernels.ops import default_interpret
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"chipbench: JAX found platform {platform!r}; this "
                         f"benchmark runs only on a TPU")
    if default_interpret():
        raise SystemExit("chipbench: Pallas kernels would run interpreted "
                         "(REPRO_PALLAS_INTERPRET); they must be compiled")
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


# --- the system under test ---------------------------------------------------
def model_config(cell: Dict[str, Any]):
    """The program's ``ModelConfig`` for the cell's configuration file."""
    from repro.configs.base import ModelConfig, with_kernel_impls
    cs = cell["config_spec"]
    return with_kernel_impls(ModelConfig(**cs["model"]), cs["kernels"])


@functools.lru_cache(maxsize=None)
def _init_fn(ref_name: str, model_items: tuple):
    import jax
    import jax.numpy as jnp

    from reference import common
    m = dict(model_items)
    shapes = spec.reference(ref_name).init_spec(m)
    return jax.jit(lambda key: common.make_params(shapes, key,
                                                  jnp.dtype(m["param_dtype"])))


def make_params(cell: Dict[str, Any], seed: int):
    """The configuration's weights, made on the device by one jitted call
    from the seed, in the type they are served in."""
    import jax

    from reference import common
    cs = cell["config_spec"]
    fn = _init_fn(cs["reference"], tuple(sorted(cs["model"].items())))
    return jax.block_until_ready(fn(common.seed_key(seed)))


def build_engine(cell: Dict[str, Any], params):
    from repro.serving.engine import ContinuousEngine
    return ContinuousEngine(model_config(cell), params, n_slots=cell["n_slots"],
                            max_seq=cell["max_seq"])


def warm(engine, traffic: gen.Traffic) -> None:
    """Compile every shape the traffic uses: each prompt length through
    prefill and graft, and the full-slot decode and pick."""
    from repro.serving.batching import GenRequest
    for i, length in enumerate(traffic.prompt_lengths):
        fn = traffic.fn_len.index(length)
        engine.add(GenRequest(id=-1 - i, prompt=traffic.prompts[fn], max_new=2))
    engine.run()


# --- the window ----------------------------------------------------------------
@dataclasses.dataclass
class Rec:
    call: gen.Call
    req: Any                  # the engine's GenRequest
    sent: float               # due (open loop) or sent (closed loop), host clock
    client: Optional[int] = None
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.call.max_new


@dataclasses.dataclass
class Window:
    recs: List[Rec]
    t_open: float
    t_close: float
    t_end: float
    counters_open: Dict[str, int]
    counters_close: Dict[str, int]
    compiles: int                         # backend compiles between open and close
    queue_open: int                       # calls waiting for a slot at open
    queue_close: int                      # ... and at close
    trace: Optional[tuple] = None         # (t_start, t_stop) of the traced span
    trace_save_s: Optional[float] = None  # how long saving the trace stalled the loop


COUNTERS = ("n_decode_steps", "n_slot_steps", "n_emitted", "prefill_tokens")


def _counters(engine) -> Dict[str, int]:
    return {k: int(getattr(engine, k)) for k in COUNTERS}


class _CompileCounter:
    def __init__(self):
        self.n = 0
        self.on = False

    def __call__(self, event, duration, **kwargs):
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.n += 1


def drive(engine, traffic: gen.Traffic, cell: Dict[str, Any], seconds: float,
          trace_dir: Optional[Path] = None, clock: Callable[[], float] = time.perf_counter,
          ) -> Window:
    """Send the traffic for ramp + window, then keep the same load until
    every call sent in the window has finished or the drain cap passes."""
    import jax
    import jax.monitoring
    from jax.profiler import TraceAnnotation

    from repro.serving.batching import GenRequest
    ramp, cap = RAMP_S, float(cell["drain_cap_s"])
    closed = traffic.mix["arrivals"] == "closed"
    t0 = clock()
    t_open, t_close = t0 + ramp, t0 + ramp + seconds
    trace_span = None
    if trace_dir is not None:
        # the window's last TRACE_S seconds: saving the trace stalls the loop
        # for seconds, so the save falls in the drain, whose cap counts from
        # the end of the save
        trace_span = (max(t_open, t_close - TRACE_S), t_close)
    drain_from = t_close
    save_s = None
    compiles = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    live: List[Rec] = []
    recs: List[Rec] = []
    pending = [] if closed else traffic.open_schedule(ramp + seconds + cap)
    nxt = 0
    calls = traffic.closed_calls() if closed else None
    phase = "ramp"
    counters_open = counters_close = queue_open = queue_close = None
    tracing = window_ann = None       # None: not started; False: saved

    def send(call: gen.Call, sent: float, client=None):
        req = GenRequest(id=call.id, prompt=call.prompt, max_new=call.max_new)
        rec = Rec(call, req, sent, client)
        recs.append(rec)
        live.append(rec)
        with TraceAnnotation("engine.add"):
            engine.add(req)
        stamp()

    def stamp():
        t = clock()
        for rec in live:
            n = len(rec.req.generated)
            if n > len(rec.stamps):
                rec.stamps.extend([t] * (n - len(rec.stamps)))

    if closed:
        for client in range(traffic.mix["clients"]):
            send(next(calls), clock(), client)
    try:
        while True:
            now = clock()
            if phase == "ramp" and now >= t_open:
                phase = "window"
                counters_open = _counters(engine)
                queue_open = len(engine.batcher.waiting)
                compiles.on = True
            if trace_span and tracing is None and phase == "window" and now >= trace_span[0]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # host spans only, no Python calls
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
                tracing = True
                window_ann = TraceAnnotation("window")
                window_ann.__enter__()
                trace_span = (clock(), trace_span[1])
            if phase == "window" and now >= t_close:
                phase = "drain"
                counters_close = _counters(engine)
                queue_close = len(engine.batcher.waiting)
                compiles.on = False
                if tracing:
                    window_ann.__exit__(None, None, None)
                    trace_span = (trace_span[0], clock())
                    jax.profiler.stop_trace()
                    tracing = False
                    drain_from = clock()
                    save_s = drain_from - trace_span[1]
            if phase == "drain":
                waiting = [r for r in recs if t_open <= r.sent < t_close and not r.done]
                if not waiting or now >= drain_from + cap:
                    break
            # arrivals
            while nxt < len(pending) and t0 + pending[nxt].due <= now:
                call = pending[nxt]
                nxt += 1
                send(call, t0 + call.due)
            if engine.batcher.active():
                with TraceAnnotation("engine.step"):
                    engine.step()
                stamp()
            elif not closed:
                until = t0 + pending[nxt].due if nxt < len(pending) else now + 1e-3
                for edge in (t_open, t_close) + (trace_span or ()):
                    if edge > now:
                        until = min(until, edge)
                with TraceAnnotation("client.wait"):
                    time.sleep(max(0.0, until - clock()))
            finished = [r for r in live if r.done]
            if finished:
                live[:] = [r for r in live if not r.done]
                if closed:
                    for r in finished:
                        send(next(calls), clock(), r.client)
    finally:
        if tracing:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(compiles)
    return Window(recs=recs, t_open=t_open, t_close=t_close, t_end=clock(),
                  counters_open=counters_open, counters_close=counters_close,
                  compiles=compiles.n, queue_open=queue_open, queue_close=queue_close,
                  trace=trace_span if trace_dir else None, trace_save_s=save_s)


# --- end-to-end metrics --------------------------------------------------------
def nearest_rank(values: List[float], q: float) -> float:
    """The q-th percentile by nearest rank; infinities count as values."""
    if not values:
        return float("inf")
    v = sorted(values)
    return v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)]


def in_window(w: Window) -> List[Rec]:
    return [r for r in w.recs if w.t_open <= r.sent < w.t_close]


def end_to_end(w: Window, setup_s: float) -> Dict[str, float]:
    sent = in_window(w)
    ttft = [r.stamps[0] - r.sent if r.stamps else float("inf") for r in sent]
    latency = [r.stamps[-1] - r.sent if r.done else float("inf") for r in sent]
    gaps = [b - a for r in sent for a, b in zip(r.stamps, r.stamps[1:])]
    toks = sum(1 for r in w.recs for t in r.stamps if w.t_open <= t < w.t_close)
    return {
        "ttft_p95_ms": nearest_rank(ttft, 95) * 1e3,
        "itl_p99_ms": nearest_rank(gaps, 99) * 1e3,
        "latency_p95_s": nearest_rank(latency, 95),
        "tokens_per_s": toks / (w.t_close - w.t_open),
        "setup_s": setup_s,
    }


# --- per-layer metrics -------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cell: Dict[str, Any]
    model: Dict[str, Any]                 # the configuration's sizes
    window: Window
    reduction: Any                        # trace_reduce.Reduction or None
    peaks: Dict[str, Any]

    def traced_tokens(self):
        """(prompt length, token index) of every token that reached the host
        inside the traced span: index 0 came from an admission prefill,
        index j >= 1 from a decode over prompt + j positions."""
        lo, hi = self.window.trace
        return [(len(r.call.prompt), j) for r in self.window.recs
                for j, t in enumerate(r.stamps) if lo <= t <= hi]


def per_layer(ctx: Context, entries: List[Dict[str, Any]], root: Path = spec.HERE
              ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in entries:
        value = spec.metric_reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(red) -> Dict[str, Any]:
    """The ten device operations that took most time, each named by the kind
    of program it ran in, and the ten longest idle gaps by host span."""
    import programs
    kind = {n: k for k, names in programs.classify(red.module_n).items() for n in names}
    ops: Dict[str, float] = {}
    for (module, op), s in red.op_in_s.items():
        name = f"{kind.get(module, module)} {op}"
        ops[name] = ops.get(name, 0.0) + s
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in red.gaps[:10]]}


# --- the run -------------------------------------------------------------------
def serve(cell: Dict[str, Any], seed: int, seconds: float, trace_dir: Optional[Path],
          t_start: float, engine_hook: Optional[Callable] = None):
    """Set-up and window; returns the window, setup seconds, the served
    (prompt, tokens) pairs and the device's peak memory. The engine and its
    weights are gone when this returns."""
    import jax
    cs = cell["config_spec"]
    traffic = gen.Traffic(cell["traffic_spec"], cell["rate"], cs["model"]["vocab_size"], seed)
    params = make_params(cell, seed)
    engine = build_engine(cell, params)
    del params
    if engine_hook is not None:
        engine_hook(engine)
    warm(engine, traffic)
    w = drive(engine, traffic, cell, seconds, trace_dir)
    setup_s = w.t_open - t_start
    stats = jax.devices()[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()) if stats else 0
    served = [(list(r.call.prompt), list(r.req.generated)) for r in w.recs if r.done]
    del engine
    gc.collect()
    return w, setup_s, served, peak


def correctness(cell: Dict[str, Any], seed: int, served) -> Dict[str, Dict[str, float]]:
    """Run the reference over a sample of what was served; the numbers
    compared, each with its limit."""
    cs = cell["config_spec"]
    chk = cell["check"]
    pick = check.sample(served, chk["requests"], seed)
    if not pick:
        return {check.GAP: {"value": float("inf"), "limit": chk[check.GAP]}}
    ref = check.Reference(spec.reference(cs["reference"]), cs["model"], make_params(cell, seed))
    gap = check.widest_gap(ref, [served[i] for i in pick], cell["max_seq"])
    n_tok = sum(len(served[i][1]) for i in pick)
    return {check.GAP: {"value": gap, "limit": chk[check.GAP]},
            "tokens_compared": {"value": n_tok, "limit": chk["min_tokens"]}}


def passed(numbers: Dict[str, Dict[str, float]]) -> bool:
    gap = numbers[check.GAP]
    ok = gap["value"] <= gap["limit"]
    if "tokens_compared" in numbers:
        ok = ok and numbers["tokens_compared"]["value"] >= numbers["tokens_compared"]["limit"]
    return bool(ok)


def run(cell_name: str, seed: int, seconds: float, trace: bool, t_start: float,
        device: Dict[str, Any], engine_hook: Optional[Callable] = None,
        bench: Optional[Dict[str, Any]] = None, root: Path = spec.HERE) -> Dict[str, Any]:
    """One run of a cell; returns the result line's object. ``bench`` and
    ``root`` default to the checkout's benchmark and this directory."""
    bench = spec.benchmark() if bench is None else bench
    cell = spec.cell(cell_name, root)
    trace_dir = None
    if trace:
        trace_dir = TRACE_ROOT / cell_name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    w, setup_s, served, peak = serve(cell, seed, seconds, trace_dir, t_start, engine_hook)
    sent = in_window(w)
    failed = sum(1 for r in sent if not r.done)
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir)
        ctx = Context(cell, cell["config_spec"]["model"], w, red, counts.peaks(device["kind"]))
        metrics = per_layer(ctx, spec.per_layer_for(cell_name, bench), root)
        extra = {"busy_s": red.busy_s, "window_s": red.window_s}
    else:
        e2e = end_to_end(w, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end_for(cell_name, bench)}
        extra = {}
    numbers = correctness(cell, seed, served)
    result = {
        "correct": passed(numbers),
        "attempted": len(sent),
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": peak, **extra},
    }
    if trace:
        result["breakdown"] = breakdown(red)
    result["window"] = {"compiles": w.compiles, "seconds": w.t_close - w.t_open,
                        "drain_s": w.t_end - w.t_close, "setup_s": setup_s}
    if trace:
        result["window"]["trace_save_s"] = w.trace_save_s
    result["check"] = numbers
    return result
