"""One run of one cell: set up, drive the traffic for the window, drain,
check what was served against the reference, and reduce it all to the
result line.

The window drives ``ContinuousEngine.add`` (admission: batch-1 prefill and
the graft into a slot row) and ``ContinuousEngine.step`` (one batched decode
over every slot, then refills) from one host loop. Every engine call ends on
the host (``step`` and admission read their tokens back), so the host clock
after a call is when its tokens reached the caller.

A gang cell (``spec``: ``gang``, ``events``) serves through the program's
``ElasticReplica``: weights made on the gang's mesh, the window driving the
replica's live engine, and each event a ``resize`` between two engine calls.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import logging
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import check
import counts
import gen
import spec

CACHE_DIR = spec.CHECKOUT / ".jax_cache"
TRACE_ROOT = spec.HERE / ".traces"
# JAX records the first event for every compile request, the second inside it
# when the persistent compilation cache answered the request
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# Traffic runs this long before the window opens (counted in set-up), so the
# window starts with the slots already filling rather than from an empty engine.
RAMP_S = 4.0
# The traced span is the window's last TRACE_S seconds: one whole burst period
# of the bursty mix (8 s), so it holds a burst and the quiet after it; on a
# steady mix it holds some hundreds of decode waves.
TRACE_S = 8.0


def configure_jax(cache_dir: Path = CACHE_DIR) -> str:
    """Persistent compile cache at a fixed path inside the checkout, with
    every compile written to it, so only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(cache_dir)


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
def _init_fn(ref_name: str, model_items: tuple, members: int):
    import jax
    import jax.numpy as jnp

    from reference import common
    m = dict(model_items)
    shapes = spec.reference(ref_name).init_spec(m)
    init = lambda key: common.make_params(shapes, key, jnp.dtype(m["param_dtype"]))  # noqa: E731
    if not members:
        return jax.jit(init)
    from repro.configs.base import ModelConfig
    from repro.distributed.elastic_serving import serving_mesh
    from repro.distributed.sharding import param_shardings
    out = jax.eval_shape(init, jax.random.PRNGKey(0))
    mesh = serving_mesh(members, jax.devices()[:members])
    return jax.jit(init, out_shardings=param_shardings(out, ModelConfig(**m), mesh))


def make_params(cell: Dict[str, Any], seed: int, members: int = 0):
    """The configuration's weights, made on the device by one jitted call
    from the seed, in the type they are served in; with ``members``, laid
    out by the program's sharding rules over a gang mesh of that many chips
    (the same values), so no chip holds the whole tree."""
    import jax

    from reference import common
    cs = cell["config_spec"]
    fn = _init_fn(cs["reference"], tuple(sorted(cs["model"].items())), members)
    return jax.block_until_ready(fn(common.seed_key(seed)))


def build_engine(cell: Dict[str, Any], params):
    """The system under test: a ``ContinuousEngine`` on one chip, or for a
    gang cell an ``ElasticReplica`` over the cell's chips."""
    if "gang" in cell:
        import jax

        from repro.distributed.elastic_serving import ElasticReplica
        gang = cell["gang"]
        return ElasticReplica(model_config(cell), params, gang["members"],
                              n_slots=cell["n_slots"], max_seq=cell["max_seq"],
                              kv_mode=gang["kv_mode"], devices=jax.devices()[:cell["chips"]])
    from repro.serving.engine import ContinuousEngine
    return ContinuousEngine(model_config(cell), params, n_slots=cell["n_slots"],
                            max_seq=cell["max_seq"])


def engine_of(server):
    """The ``ContinuousEngine`` that serves now: the server itself, or the
    one a gang's replica built at its last resize."""
    return getattr(server, "engine", server)


def member_ids(server) -> Optional[List[int]]:
    """Device ids of the gang's mesh now; None for a one-chip engine."""
    mesh = getattr(server, "mesh", None)
    return None if mesh is None else [int(d.id) for d in mesh.devices.flat]


def warm(server, traffic: gen.Traffic, sizes: Sequence[int] = ()) -> None:
    """Compile every shape the traffic uses: each prompt length through
    prefill and graft, and the full-slot decode and pick. Then replay a
    gang's events (``sizes``, in order) and return to the starting size.
    A resize builds a new engine and hands it the slot state uncommitted; the
    first program to take that state (a decode when a request is live, an
    admission's graft when none is) compiles a variant of its own and fixes
    the layout the others then meet. So each size is entered both ways, with
    every length admitted after the first call."""
    from repro.serving.batching import GenRequest

    def admit_every_length():
        for i, length in enumerate(traffic.prompt_lengths):
            fn = traffic.fn_len.index(length)
            engine_of(server).add(GenRequest(id=-1 - i, prompt=traffic.prompts[fn], max_new=2))
        engine_of(server).run()
    admit_every_length()
    if not sizes:
        return
    start = server.n_members
    for n in sizes:
        engine_of(server).add(GenRequest(id=-1 - len(traffic.prompt_lengths),
                                         prompt=traffic.prompts[0], max_new=3))
        server.resize(n)
        engine_of(server).step()
        admit_every_length()
        server.resize(n)
        admit_every_length()
    server.resize(start)
    admit_every_length()


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
    compiles: int                         # compile requests between open and close, a gang's reloads left out
    queue_open: int                       # calls waiting for a slot at open
    queue_close: int                      # ... and at close
    trace: Optional[tuple] = None         # (t_start, t_stop) of the traced span
    trace_save_s: Optional[float] = None  # how long saving the trace stalled the loop
    cache_loads: int = 0                  # ... of them and of the reloads, those the persistent cache answered
    events: List[Dict[str, Any]] = dataclasses.field(default_factory=list)  # one per event fired
    members: Optional[List[int]] = None   # device ids of the gang's mesh over the traced span
    carried: List[int] = dataclasses.field(default_factory=list)  # call ids live at an event


COUNTERS = ("n_decode_steps", "n_slot_steps", "n_emitted", "prefill_tokens")


def _counters(server) -> Dict[str, int]:
    return {k: int(getattr(engine_of(server), k)) for k in COUNTERS}


class _CompileCounter:
    """Every compile request while ``on`` (``n``), whether the backend
    compiled it or the persistent cache answered it (``loads`` counts
    those). A gang's resize builds an engine whose jitted programs start
    empty, so at first use each is lowered again and read back from the
    cache. After an event, a request for a program whose cache key set-up
    already requested is that re-lowering: it goes to the event's entry
    (``reloads``, ``reload_s``, host seconds) and not to ``n``. The keys
    come from JAX's persistent-cache log records, which are not printed."""

    LOGGER = "jax._src.compiler"
    KEYED = ("Persistent compilation cache hit", "PERSISTENT COMPILATION CACHE MISS")

    def __init__(self):
        self.n = 0
        self.loads = 0
        self.on = False
        self.event: Optional[Dict[str, Any]] = None   # the entry reloads go to
        self._seen: set = set()                       # cache keys requested in set-up
        self._key: Optional[str] = None               # the request in progress

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._request)
        jax.monitoring.register_event_listener(self._hit)
        log = logging.getLogger(self.LOGGER)
        self._level, self._floor = log.level, log.getEffectiveLevel()
        log.addFilter(self._record)
        log.setLevel(logging.DEBUG)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._request)
        jax.monitoring.unregister_event_listener(self._hit)
        log = logging.getLogger(self.LOGGER)
        log.removeFilter(self._record)
        log.setLevel(self._level)

    def _record(self, record: logging.LogRecord) -> bool:
        """Keep the request's cache key; pass on only the records the
        logger's own level lets through."""
        if isinstance(record.msg, str) and record.msg.startswith(self.KEYED):
            self._key = str(record.args[1])
        return record.levelno >= self._floor

    def _hit(self, event, **kwargs):
        if self.on and event == CACHE_HIT_EVENT:
            self.loads += 1

    def _request(self, event, duration, **kwargs):
        if event != BACKEND_COMPILE_EVENT:
            return
        key, self._key = self._key, None
        if not self.on:
            if key is not None:
                self._seen.add(key)
        elif self.event is not None and key in self._seen:
            self.event["reloads"] += 1
            self.event["reload_s"] += duration
        else:
            self.n += 1


def drive(server, traffic: gen.Traffic, cell: Dict[str, Any], seconds: float,
          compiles: _CompileCounter, trace_dir: Optional[Path] = None,
          clock: Callable[[], float] = time.perf_counter) -> Window:
    """Send the traffic for ramp + window, then keep the same load until
    every call sent in the window has finished or the drain cap passes. A
    gang's events fire between two engine calls, each right before a decode
    wave. The first engine call after an event runs programs of its own
    (it takes the slot state as the resize left it); the run fails if one
    is still to come when the traced span opens, where it would be read as
    a prefill."""
    import jax
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
    events = cell.get("events", [])
    fired: List[Dict[str, Any]] = []
    carried: List[int] = []
    members = None
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
            engine_of(server).add(req)
        settle()
        stamp()

    def fire(event):
        """Resize the gang; the pause lasts until the survivors hold the
        new layout's weights and slot state."""
        before = server.n_members
        carried.extend(r.call.id for r in live if not r.done)
        t = clock()
        with TraceAnnotation("engine.resize"):
            rec = server.resize(event["members"])
            jax.block_until_ready((server.params, engine_of(server).device_state))
        done = clock()
        stamp()
        fired.append({"at_s": event["at_s"], "fired_s": t - t_open, "grace_s": event["grace_s"],
                      "members": [before, server.n_members], "devices": member_ids(server),
                      "pause_s": done - t, "bytes_moved": rec.bytes_moved,
                      "wire_bytes": rec.wire_bytes, "live": rec.n_requests_live,
                      "settled_s": None, "reloads": 0, "reload_s": 0.0})
        compiles.event = fired[-1]

    def settle():
        """The first engine call after an event has ended."""
        for entry in fired:
            if entry["settled_s"] is None:
                entry["settled_s"] = clock() - t_open

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
                counters_open = _counters(server)
                queue_open = len(engine_of(server).batcher.waiting)
                compiles.on = True
            if trace_span and tracing is None and phase == "window" and now >= trace_span[0]:
                if fired and fired[-1]["settled_s"] is None:
                    raise spec.CellError(
                        f"cell {cell['name']!r}: the event at {fired[-1]['at_s']} s was not "
                        f"over when the traced span opened; move it earlier")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # host spans only, no Python calls
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
                tracing = True
                window_ann = TraceAnnotation("window")
                window_ann.__enter__()
                trace_span = (clock(), trace_span[1])
                members = member_ids(server)
            if phase == "window" and now >= t_close:
                phase = "drain"
                counters_close = _counters(server)
                queue_close = len(engine_of(server).batcher.waiting)
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
            while (phase == "window" and len(fired) < len(events)
                   and now >= t_open + events[len(fired)]["at_s"]):
                fire(events[len(fired)])
            if engine_of(server).batcher.active():
                with TraceAnnotation("engine.step"):
                    engine_of(server).step()
                settle()
                stamp()
            elif not closed:
                until = t0 + pending[nxt].due if nxt < len(pending) else now + 1e-3
                due = tuple(t_open + e["at_s"] for e in events[len(fired):])
                for edge in (t_open, t_close) + (trace_span or ()) + due:
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
        compiles.on = False
        if tracing:
            jax.profiler.stop_trace()
    return Window(recs=recs, t_open=t_open, t_close=t_close, t_end=clock(),
                  counters_open=counters_open, counters_close=counters_close,
                  compiles=compiles.n, queue_open=queue_open, queue_close=queue_close,
                  trace=trace_span if trace_dir else None, trace_save_s=save_s,
                  cache_loads=compiles.loads, events=fired, members=members,
                  carried=carried)


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

    @property
    def reference(self):
        """The configuration's plain reference, which counts its model
        step's FLOPs (``prefill_flops``, ``decode_flops``)."""
        return spec.reference(self.cell["config_spec"]["reference"])

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
    (prompt, tokens) pairs and the fullest device's peak memory. The engine
    and its weights are gone when this returns."""
    import jax
    cs = cell["config_spec"]
    traffic = gen.Traffic(cell["traffic_spec"], cell["rate"], cs["model"]["vocab_size"], seed)
    params = make_params(cell, seed, cell.get("gang", {}).get("members", 0))
    server = build_engine(cell, params)
    del params
    if engine_hook is not None:
        engine_hook(server)
    with _CompileCounter() as compiles:
        warm(server, traffic, [e["members"] for e in cell.get("events", [])])
        w = drive(server, traffic, cell, seconds, compiles, trace_dir)
    setup_s = w.t_open - t_start
    stats = jax.devices()[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()) if stats else 0
    served = [(list(r.call.prompt), list(r.req.generated)) for r in w.recs if r.done]
    del server
    gc.collect()
    return w, setup_s, served, peak


PAUSE = "resize_pause_s"


def check_sample(cell: Dict[str, Any], seed: int, served, w: Optional[Window] = None
                 ) -> List[tuple]:
    """The (prompt, tokens) pairs the check compares: a sample drawn from
    the seed, and in a gang cell every finished request live at an event.
    ``served`` lists the finished requests of ``w.recs``, in their order."""
    carried = set(w.carried) if w is not None else set()
    done = [r for r in w.recs if r.done] if carried else []
    pick = check.sample(served, cell["check"]["requests"], seed,
                        [i for i, r in enumerate(done) if r.call.id in carried])
    return [served[i] for i in pick]


def reference(cell: Dict[str, Any], seed: int) -> check.Reference:
    """The plain reference over the cell's weights, made on all of the
    cell's chips: on one chip one program, as always; over several, laid
    out by the program's sharding rules and run in blocks of layers."""
    cs = cell["config_spec"]
    chips = cell.get("chips", 1)
    params = make_params(cell, seed, chips if chips > 1 else 0)
    return check.Reference(spec.reference(cs["reference"]), cs["model"], params)


def correctness(cell: Dict[str, Any], seed: int, served, w: Optional[Window] = None
                ) -> Dict[str, Dict[str, float]]:
    """Run the reference over the check's sample of what was served; the
    numbers compared, each with its limit. In a gang cell each event adds
    its pause, held to the event's grace (one that never fired reads
    infinite)."""
    chk = cell["check"]
    pairs = check_sample(cell, seed, served, w)
    if not pairs:
        numbers = {check.GAP: {"value": float("inf"), "limit": chk[check.GAP]}}
    else:
        gap = check.widest_gap(reference(cell, seed), pairs, cell["max_seq"])
        numbers = {check.GAP: {"value": gap, "limit": chk[check.GAP]},
                   "tokens_compared": {"value": sum(len(t) for _, t in pairs),
                                       "limit": chk["min_tokens"]}}
    fired = w.events if w is not None else []
    for i, event in enumerate(cell.get("events", [])):
        pause = fired[i]["pause_s"] if i < len(fired) else float("inf")
        numbers[PAUSE + (f".{i}" if i else "")] = {"value": pause, "limit": event["grace_s"]}
    return numbers


def passed(numbers: Dict[str, Dict[str, float]]) -> bool:
    gap = numbers[check.GAP]
    ok = gap["value"] <= gap["limit"]
    if "tokens_compared" in numbers:
        ok = ok and numbers["tokens_compared"]["value"] >= numbers["tokens_compared"]["limit"]
    pauses = [n for name, n in numbers.items() if name.startswith(PAUSE)]
    return bool(ok and all(n["value"] <= n["limit"] for n in pauses))


def run(cell_name: str, seed: int, seconds: float, trace: bool, t_start: float,
        device: Dict[str, Any], engine_hook: Optional[Callable] = None,
        bench: Optional[Dict[str, Any]] = None, root: Path = spec.HERE) -> Dict[str, Any]:
    """One run of a cell; returns the result line's object. ``bench`` and
    ``root`` default to the checkout's benchmark and this directory."""
    bench = spec.benchmark() if bench is None else bench
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[cell_name]
    cell = spec.cell(cell_name, root, chips, seconds, TRACE_S)
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
        red = trace_reduce.reduce_dir(trace_dir, w.members)
        ctx = Context(cell, cell["config_spec"]["model"], w, red, counts.peaks(device["kind"]))
        metrics = per_layer(ctx, spec.per_layer_for(cell_name, bench), root)
        extra = {"busy_s": red.busy_s, "window_s": red.window_s}
    else:
        e2e = end_to_end(w, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end_for(cell_name, bench)}
        extra = {}
    numbers = correctness(cell, seed, served, w)
    result = {
        "correct": passed(numbers),
        "attempted": len(sent),
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": peak, **extra},
    }
    if trace:
        result["breakdown"] = breakdown(red)
    result["window"] = {"compiles": w.compiles, "cache_loads": w.cache_loads,
                        "seconds": w.t_close - w.t_open, "drain_s": w.t_end - w.t_close,
                        "setup_s": setup_s}
    if trace:
        result["window"]["trace_save_s"] = w.trace_save_s
    if "gang" in cell:
        result["window"]["events"] = w.events
        if trace:
            result["window"]["members"] = w.members
    result["check"] = numbers
    return result
