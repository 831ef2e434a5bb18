"""Serving engine: weights-resident prefill/decode with KV caches.

This is what an HPC-Whisk *invoker* hosts on a harvested slice: the engine is
constructed once per pilot job (the warm-up cost the paper measures) and then
serves seconds-long invocations (bounded generate calls) until SIGTERM.

Two decode paths:

:class:`ServingEngine`
    run-to-completion ``generate`` on one request batch — the sequential
    baseline, and still the scoring/integrity path.
:class:`ContinuousEngine`
    slot-based continuous batching: each arriving request is prefilled into a
    free batch slot (its KV cache grafted into the live batch cache), every
    active slot advances with ONE batched ``decode_step`` per token using a
    per-slot position vector, and freed slots are refilled without stopping
    the loop. ``drain()`` hands back partial generations for the fast-lane
    requeue (PR 4's ``resubmit()``), which resume instead of restarting.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.serving.batching import GenRequest, SlotBatcher
from repro.serving.kvcache import OutOfBlocks, PagedKVCache, paged_compatible
from repro.serving.slot_state import SlotBatchState, find_batch_axes


def _pick(logits, vocab_size: int, temperature: float, rng):
    """Next-token choice over the un-padded vocab. logits: (B,Vpad)."""
    if temperature <= 0:
        nxt = jnp.argmax(logits[..., :vocab_size], axis=-1)
    else:
        nxt = jax.random.categorical(rng, logits[..., :vocab_size]
                                     / temperature, axis=-1)
    return nxt[:, None].astype(jnp.int32)


_CACHE_BUCKET = 64  # sequential-path caches sized in buckets, not max_seq

# Host spans (``jax.profiler.TraceAnnotation``) the continuous engines open
# around their work, on the profiler's clock: an admission (stats ``req``,
# ``slot``, ``ctx``) holding its context's prefill (``req``, ``ctx``), the
# dense graft (``req``, ``slot``) and the pick of its first token (``req``);
# a decode wave's dispatch, pick with its blocking read-back, and emit plus
# release (each ``active``: slots in the wave). Free when no profiler runs.
SPANS = ADMIT, PREFILL, GRAFT, PICK, DECODE, EMIT = (
    "engine.admit", "engine.prefill", "engine.graft", "engine.pick",
    "engine.decode", "engine.emit")


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, max_seq: int = 512):
        if not cfg.is_autoregressive:
            raise ValueError(f"arch {cfg.arch_id!r} is encoder-only: it is "
                             f"scored, not decoded")
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self._prefill = jax.jit(functools.partial(model_mod.prefill, cfg=cfg))
        self._decode = jax.jit(functools.partial(model_mod.decode_step, cfg=cfg))

    def _grown_cache(self, cache, batch: int, seq_cap: Optional[int] = None):
        """Pad a prefill cache up to ``(batch, seq_cap)``. ``seq_cap`` used
        to be pinned at ``max_seq``, so every 24-token request reserved (and
        paid allocation for) the full window; callers now pass the
        bucket-rounded need (the bucket bounds jit retraces)."""
        full = model_mod.init_cache(self.cfg, batch,
                                    self.max_seq if seq_cap is None else seq_cap)

        def graft(z, c):
            if z.shape == c.shape:
                return c.astype(z.dtype)
            assert z.ndim == c.ndim, (z.shape, c.shape)
            # pad EVERY mismatched axis (batch and sequence can both differ
            # when a cache is grafted across request shapes), never shrink
            pad = [(0, zi - ci) for zi, ci in zip(z.shape, c.shape)]
            assert all(hi >= 0 for _, hi in pad), (z.shape, c.shape)
            out = jnp.pad(c.astype(z.dtype), pad)
            assert out.shape == z.shape, (out.shape, z.shape)
            return out
        return jax.tree.map(graft, full, cache)

    def generate(self, tokens: np.ndarray, n_new: int,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Greedy (or sampled) generation. tokens: (B, S) int32 prompt."""
        b, s = tokens.shape
        assert s + n_new <= self.max_seq, (s, n_new, self.max_seq)
        seq_cap = min(self.max_seq, -(-(s + n_new) // _CACHE_BUCKET) * _CACHE_BUCKET)
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(tokens)})
        cache = self._grown_cache(cache, b, seq_cap)
        rng = jax.random.PRNGKey(seed)
        # key hygiene: the root key is only ever split, never consumed — the
        # first sample uses a subkey so tokens 0 and 1 are uncorrelated
        rng, sub = jax.random.split(rng)
        out = [self._pick(logits, temperature, sub)]
        for i in range(1, n_new):
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode(self.params, out[-1], cache,
                                         jnp.int32(s + i - 1))
            out.append(self._pick(logits, temperature, sub))
        return np.concatenate([np.asarray(t) for t in out], axis=1)

    def _pick(self, logits, temperature, rng):
        return _pick(logits, self.cfg.vocab_size, temperature, rng)

    def score(self, tokens: np.ndarray) -> float:
        """Mean NLL of a token batch (used as a cheap integrity check when an
        invoker re-registers after migration)."""
        batch = {"tokens": jnp.asarray(tokens[:, :-1]),
                 "labels": jnp.asarray(tokens[:, 1:])}
        loss, _ = model_mod.loss_fn(self.params, batch, self.cfg)
        return float(loss)


class ContinuousEngine:
    """Continuous-batching decode: ``n_slots`` requests in flight at once,
    one batched ``decode_step`` per emitted token wave.

    Per-slot state lives host-side (``positions``/``last_tok``) while the
    device-side decode state is a single :class:`SlotBatchState` pytree of
    batch ``n_slots`` — per-layer K/V for GQA, latent caches for MLA,
    SSM recurrent state + conv windows for mamba2/zamba2, or any mix the
    model's ``cache_spec`` declares. The engine is therefore
    architecture-agnostic: admission prefills the request context (prompt +
    any drained partial) at batch 1 and grafts the resulting state into this
    request's batch row; the other rows keep decoding untouched.
    Temperature-0 outputs are token-identical to the sequential
    :meth:`ServingEngine.generate` path.
    """

    def __init__(self, cfg: ModelConfig, params, n_slots: int = 4,
                 max_seq: int = 512, eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0):
        if not cfg.is_autoregressive:
            raise ValueError(f"arch {cfg.arch_id!r} is encoder-only: it is "
                             f"scored, not decoded")
        if n_slots < 1:
            raise ValueError(f"n_slots={n_slots} must be >= 1")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.temperature = temperature
        self.batcher = SlotBatcher(n_slots)
        self.positions = np.zeros(n_slots, np.int32)  # pos of last_tok per slot
        self.last_tok = np.zeros((n_slots, 1), np.int32)
        self._rng = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(functools.partial(model_mod.prefill, cfg=cfg))
        # the slot state is donated: each wave updates it in place
        self._decode = jax.jit(functools.partial(model_mod.decode_step, cfg=cfg),
                               donate_argnums=(2,))
        # counters for occupancy/throughput accounting
        self.n_decode_steps = 0
        self.n_emitted = 0       # tokens produced (prefill-picked + decoded)
        self.n_slot_steps = 0    # sum over steps of active slots
        self.prefill_tokens = 0  # context tokens pushed through prefill
        self._init_cache_state()

    def _init_cache_state(self):
        """Allocate the slot-state pytree; the paged subclass swaps in a
        block pool instead."""
        self._slot_state = SlotBatchState(self.cfg, self.n_slots, self.max_seq)

    @property
    def cache(self):
        """The live decode-state pytree. Settable: the elastic-serving
        migration protocol transplants it wholesale across meshes. Decode
        and graft take it donated and replace it, so a tree read here is
        dead (its leaves deleted) after the next ``step()`` or ``add()``
        that admits: read it again rather than holding it."""
        if self._slot_state is None:
            raise AttributeError(
                "paged engine keeps decode state in the block pool (.kv), "
                "not a dense slot-state pytree")
        return self._slot_state.tree

    @cache.setter
    def cache(self, tree):
        self._slot_state.tree = tree

    @property
    def device_state(self):
        """Device-resident decode state, for ``jax.block_until_ready`` at
        timing boundaries. Unlike ``cache`` this is defined for every
        engine flavour (the paged subclass returns its block pools)."""
        return self._slot_state.tree

    # kept as a staticmethod seam for callers that need the layout without an
    # engine (tests, migration planners)
    _find_batch_axes = staticmethod(find_batch_axes)

    # --- request lifecycle ----------------------------------------------------
    def add(self, req: GenRequest):
        """Admit a request: queue it and prefill any slot it (or a cascade of
        early-EOS admissions) frees up. Safe to call mid-decode."""
        req.queued_at = time.perf_counter()
        for slot in self.batcher.add(req):
            self._admit(slot)

    def _admit(self, slot: int):
        req = self.batcher.slots[slot]
        while req is not None:
            req.admitted_at = time.perf_counter()
            context = list(req.prompt) + list(req.generated)
            with TraceAnnotation(ADMIT, req=req.id, slot=slot,
                                 ctx=len(context)):
                if req.remaining == 0:   # resumed partial that was already full
                    req.done = True
                    self.batcher.finished.append(req)
                    self.batcher.slots[slot] = None
                    self._reap()
                else:
                    assert len(context) + req.remaining <= self.max_seq, \
                        (len(context), req.remaining, self.max_seq)
                    logits = self._context_into_slot(slot, req, context)
                    if logits is None:
                        # mid-decode state restored (paged parked resume): the
                        # next token comes from step(), not an admission prefill
                        return
                    with TraceAnnotation(PICK, req=req.id):
                        tok = int(np.asarray(self._pick_row(logits))[0, 0])
                    req.generated.append(tok)
                    self.n_emitted += 1
                    self.positions[slot] = len(context)
                    self.last_tok[slot, 0] = tok
                    finished = self.batcher._finish_if_done(slot, req, tok,
                                                            self.eos_id)
                    self._reap()
                    if not finished:
                        return
            self.batcher._fill()
            req = self.batcher.slots[slot]

    def _context_into_slot(self, slot: int, req: GenRequest,
                           context: List[int]):
        """Install ``context``'s KV into ``slot``; returns the last-position
        logits (B=1), or None when the slot was restored to a mid-decode
        state and no admission token should be emitted (paged resume)."""
        with TraceAnnotation(PREFILL, req=req.id, ctx=len(context)):
            logits, pre = self._prefill(
                self.params, {"tokens": jnp.asarray([context], jnp.int32)})
        with TraceAnnotation(GRAFT, req=req.id, slot=slot):
            self._slot_state.graft(pre, slot)
        self.prefill_tokens += len(context)
        return logits

    def _reap(self):
        """Release per-request KV state of newly finished requests (no-op
        for the dense layout: slot rows are simply overwritten)."""

    def register_prefix(self, tokens: List[int]) -> bool:
        """Pre-install a shared context prefix. The dense layout has no
        sharing to exploit; returns False so callers can skip it."""
        return False

    def _pick_row(self, logits):
        if self.temperature <= 0:
            return _pick(logits, self.cfg.vocab_size, 0.0, None)
        self._rng, sub = jax.random.split(self._rng)
        return _pick(logits, self.cfg.vocab_size, self.temperature, sub)

    def step(self) -> int:
        """One batched decode: every active slot advances one token; finished
        slots are refilled (and prefilled) without stopping the loop. Returns
        the number of tokens emitted."""
        active = self.batcher.active()
        if not active:
            return 0
        pos = np.minimum(self.positions, self.max_seq - 1)
        with TraceAnnotation(DECODE, active=len(active)):
            logits = self._decode_active(pos)
        # re-read: _decode_active may have preempted a slot to reclaim memory
        active = self.batcher.active()
        with TraceAnnotation(PICK, active=len(active)):
            toks = np.asarray(self._pick_row(logits))  # (n_slots,1)
        self.n_decode_steps += 1
        self.n_slot_steps += len(active)
        slot_of = {req.id: i for i, req in active.items()}

        def emit(req: GenRequest) -> int:
            i = slot_of[req.id]
            self.positions[i] += 1
            self.last_tok[i, 0] = toks[i, 0]
            return int(toks[i, 0])

        with TraceAnnotation(EMIT, active=len(active)):
            filled = self.batcher.step(emit, eos_id=self.eos_id)
            self._reap()
        emitted = len(active)
        self.n_emitted += len(active)
        for slot in filled:
            self._admit(slot)
        return emitted

    def _decode_active(self, pos: np.ndarray):
        """One batched decode over every slot row; returns (n_slots, Vpad)
        logits and advances the KV state."""
        logits, self.cache = self._decode(
            self.params, jnp.asarray(self.last_tok), self.cache,
            jnp.asarray(pos, jnp.int32))
        return logits

    def run(self) -> List[GenRequest]:
        """Drive to quiescence; returns (and clears) the finished list."""
        while self.batcher.active():
            self.step()
        done, self.batcher.finished = self.batcher.finished, []
        return done

    def serve(self, gens: List[GenRequest]) -> Dict[int, float]:
        """Admit ``gens`` and run to quiescence, timing each request: returns
        ``{request id -> completion offset in wall seconds}`` (prefill
        included; a request can finish at admission). The finished requests
        stay on ``batcher.finished`` for the caller to consume. This is the
        one timed loop both the batched executor and the serving benchmark
        charge from."""
        t0 = time.perf_counter()
        finished_at: Dict[int, float] = {}

        def sweep():
            now = time.perf_counter() - t0
            for f in self.batcher.finished:
                finished_at.setdefault(f.id, now)

        for g in gens:
            self.add(g)
            sweep()
        while self.batcher.active():
            self.step()
            sweep()
        return finished_at

    def drain(self) -> List[GenRequest]:
        """SIGTERM hand-off: stop decoding and return all unfinished requests
        with their partial ``generated`` intact, so the platform's fast-lane
        ``resubmit()`` can resume them elsewhere instead of restarting."""
        return self.batcher.drain()

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots doing useful work per decode step."""
        if self.n_decode_steps == 0:
            return float("nan")
        return self.n_slot_steps / (self.n_decode_steps * self.n_slots)

    def kv_stats(self) -> Dict[str, float]:
        """KV-memory accounting in the same shape as the paged engine's, so
        metrics gauges and benchmarks compare layouts key-for-key. The dense
        layout reserves everything up front, hence high-water == total."""
        total = int(sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache)))
        cap = self.n_slots * self.max_seq
        used = int(sum(int(self.positions[i]) + 1
                       for i in self.batcher.active()))
        return {
            "layout": "dense",
            "pool_bytes": total,
            "bytes_in_use": total,
            "bytes_high_water": total,
            "blocks_total": self.n_slots,       # a dense "block" is one row
            "blocks_in_use": len(self.batcher.active()),
            "blocks_high_water": self.n_slots,
            "tokens_in_use": used,
            "capacity_tokens": cap,
            "cow_copies": 0,
            "prefill_tokens": self.prefill_tokens,
            "shared_tokens": 0,
            "resumed_tokens": 0,
            "share_hits": 0,
            "resume_hits": 0,
            "mem_preempts": 0,
            "share_hit_rate": 0.0,
        }


def _paged_gather_decode(params, token, k_pool, v_pool, tables, pos, cfg,
                         seg_name, s_max):
    """Gather-path paged decode: reassemble a dense-layout cache view from
    the block tables and run the stock ``decode_step`` on it — bit-identical
    math to the dense engine (garbage past each row's length is masked by the
    per-row position mask). Returns the wave's logits plus the K/V entries
    written at ``pos`` so the caller can scatter them back into the pool."""
    l, _, bs = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    b, maxb = tables.shape
    trail = k_pool.shape[3:]

    def gather(pool):
        return pool[:, tables].reshape(l, b, maxb * bs, *trail)[:, :, :s_max]

    cache = {seg_name: {"k": gather(k_pool), "v": gather(v_pool)}}
    logits, new_cache = model_mod.decode_step(params, token, cache, pos, cfg)
    rows = jnp.arange(b)
    k_ent = new_cache[seg_name]["k"][:, rows, pos]
    v_ent = new_cache[seg_name]["v"][:, rows, pos]
    return logits, k_ent, v_ent


class PagedContinuousEngine(ContinuousEngine):
    """Continuous batching over a block-paged KV cache (``kv_layout=paged``).

    Same request lifecycle and token streams as :class:`ContinuousEngine`
    (temperature-0 outputs are bit-identical on the default gather attention
    path), but KV memory is a pool of fixed-size blocks shared by refcount:

    * admission writes the context's K/V into just ``ceil(len/bs)`` blocks
      instead of reserving a full ``max_seq`` row;
    * a registered per-tenant prefix (:meth:`register_prefix`) is prefilled
      once and forked into every request that starts with it — shared blocks
      are referenced, not copied, and the first divergent write into a
      partially-filled tail block copy-on-writes;
    * :meth:`drain` parks each in-flight request's blocks (pinned under its
      request id) so a later resume re-references them instead of
      re-prefilling;
    * when the pool runs dry, admission requeues and decode waves preempt
      the highest slot back to the waiting queue (parked sequences are
      evicted first) — requests queue, memory never corrupts.

    ``attn="gather"`` reassembles a dense view per wave (reference oracle);
    ``attn="kernel"`` runs the Pallas paged-attention kernel, gathering K/V
    through the block table inside the kernel grid.
    """

    def __init__(self, cfg: ModelConfig, params, n_slots: int = 4,
                 max_seq: int = 512, eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0, *,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 attn: str = "gather", max_parked: int = 64,
                 interpret: Optional[bool] = None):
        if attn not in ("gather", "kernel"):
            raise ValueError(
                f"PagedContinuousEngine: unknown attn={attn!r}; allowed "
                f"values: ('gather', 'kernel')")
        if max_seq % block_size != 0:
            raise ValueError(f"max_seq={max_seq} must be a multiple of "
                             f"block_size={block_size}")
        self.block_size = block_size
        self.max_blocks = max_seq // block_size
        if n_blocks is None:
            # dense-equivalent capacity + the null block
            n_blocks = n_slots * self.max_blocks + 1
        self.n_blocks = n_blocks
        self.attn = attn
        self.max_parked = max_parked
        if interpret is None:
            from repro.kernels.ops import default_interpret
            interpret = default_interpret()
        self._interpret = interpret
        super().__init__(cfg, params, n_slots, max_seq, eos_id, temperature,
                         seed)

    @property
    def device_state(self):
        return (self.kv.k_pool, self.kv.v_pool)

    def _init_cache_state(self):
        from repro.models import transformer
        self._slot_state = None   # state lives in the block pool, not a tree
        self.kv = PagedKVCache(self.cfg, self.n_blocks, self.block_size)
        self._slot_seq: List[Optional[Hashable]] = [None] * self.n_slots
        self._parked: Dict[int, Tuple[int, ...]] = {}   # req.id -> context
        self._prefixes: Dict[Tuple[int, ...], Hashable] = {}
        self.shared_tokens = 0     # context tokens satisfied by a prefix fork
        self.resumed_tokens = 0    # context tokens satisfied by parked blocks
        self.share_hits = 0
        self.resume_hits = 0
        self.n_mem_preempts = 0
        segs = transformer.segments_for(self.cfg)
        assert len(segs) == 1 and segs[0].kind == "dense", segs
        self._gather_step = jax.jit(functools.partial(
            _paged_gather_decode, cfg=self.cfg, seg_name=segs[0].name,
            s_max=self.max_seq))
        self._kernel_step = jax.jit(functools.partial(
            model_mod.paged_decode_step, cfg=self.cfg,
            interpret=self._interpret))

    # --- one paged decode wave ------------------------------------------------
    def _decode_paged(self, token, tables, pos, bids, offs):
        """Run one decode wave (any batch) against the pool, writing each
        row's new K/V entry into its reserved ``(bids, offs)`` slot."""
        tables = jnp.asarray(tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        if self.attn == "kernel":
            logits, self.kv.k_pool, self.kv.v_pool = self._kernel_step(
                self.params, token, self.kv.k_pool, self.kv.v_pool, tables,
                pos, jnp.asarray(bids, jnp.int32), jnp.asarray(offs, jnp.int32))
        else:
            logits, k_ent, v_ent = self._gather_step(
                self.params, token, self.kv.k_pool, self.kv.v_pool, tables,
                pos)
            self.kv.write_tokens(np.asarray(bids), np.asarray(offs), k_ent,
                                 v_ent)
        return logits

    # --- admission ------------------------------------------------------------
    def _context_into_slot(self, slot: int, req: GenRequest,
                           context: List[int]):
        key = ("req", req.id)
        parked = self._parked.pop(req.id, None)
        if key in self.kv.alloc.tables:
            n_keep = len(context) - 1
            if (parked is not None and 0 <= n_keep <= self.kv.length(key)
                    and parked[:len(context)] == tuple(context)):
                # drained blocks were pinned: re-reference them and restore
                # the mid-decode state (cache holds 0..n_keep-1, context[-1]
                # pending) — the next token comes from step(), bit-identical
                # to never having drained
                self.kv.trim(key, n_keep)
                self._slot_seq[slot] = key
                self.positions[slot] = n_keep
                self.last_tok[slot, 0] = context[-1]
                self.resume_hits += 1
                self.resumed_tokens += n_keep
                return None
            self.kv.free(key)   # diverged/stale park: fall through to fresh
        toks = tuple(context)
        best_n, best_seq = 0, None
        for ptoks, pseq in self._prefixes.items():
            n = len(ptoks)
            if best_n < n <= len(context) - 1 and toks[:n] == ptoks:
                best_n, best_seq = n, pseq
        while True:
            try:
                if best_seq is not None:
                    self.kv.fork(best_seq, key, best_n)
                    with TraceAnnotation(PREFILL, req=req.id, ctx=len(context)):
                        logits = self._extend(key, context, best_n)
                    self.share_hits += 1       # only successful installs count
                    self.shared_tokens += best_n
                else:
                    self.kv.create(key)
                    with TraceAnnotation(PREFILL, req=req.id, ctx=len(context)):
                        logits = self._install_prefill(key, context)
                self._slot_seq[slot] = key
                return logits
            except OutOfBlocks:
                if key in self.kv.alloc.tables:
                    self.kv.free(key)
                if self._evict_parked():
                    continue
                others = [j for j, r in self.batcher.active().items()
                          if j != slot]
                if not others:
                    raise   # nothing to wait for: the pool is simply too small
                # requeue at the head: a finishing slot will retry the admit
                self.batcher.slots[slot] = None
                self.batcher.waiting.insert(0, req)
                return None

    def _install_prefill(self, key, context):
        need = -(-len(context) // self.block_size)
        if len(self.kv.alloc.free_list) < need:   # fail before the device
            raise OutOfBlocks(f"need {need} blocks for admission, "
                              f"{len(self.kv.alloc.free_list)} free")
        logits, pre = self._prefill(
            self.params, {"tokens": jnp.asarray([context], jnp.int32)})
        seg = pre[next(iter(pre))]
        self.kv.write_prefill(key, seg["k"][:, 0], seg["v"][:, 0])
        self.prefill_tokens += len(context)
        return logits

    def _extend(self, key, context, start):
        """Append ``context[start:]`` through the paged decode path (the
        forked prefix supplies positions ``0..start-1``), one token per wave
        at batch 1 — exactly the math decode would have run, so the suffix's
        K/V (and the admission token) match an unshared install."""
        logits = None
        for p in range(start, len(context)):
            bid, off = self.kv.append(key)
            logits = self._decode_paged(
                jnp.asarray([[context[p]]], jnp.int32),
                self.kv.table_array([key], self.max_blocks),
                np.asarray([p]), np.asarray([bid]), np.asarray([off]))
            self.prefill_tokens += 1
        return logits

    # --- decode wave ----------------------------------------------------------
    def _decode_active(self, pos: np.ndarray):
        bids = np.zeros(self.n_slots, np.int64) + self.kv.null_block
        offs = np.zeros(self.n_slots, np.int64)
        seqs: List[Hashable] = [self.kv.NULL_SEQ] * self.n_slots
        pos = np.asarray(pos).copy()
        i = 0
        while i < self.n_slots:
            if self.batcher.slots[i] is None:
                pos[i] = 0
                i += 1
                continue
            try:
                bids[i], offs[i] = self.kv.append(self._slot_seq[i])
            except OutOfBlocks:
                if self._evict_parked():
                    continue
                victim = self._pick_victim(i)
                if victim is None:
                    raise
                self._preempt_slot(victim)
                continue    # slot i unchanged unless it was its own victim
            seqs[i] = self._slot_seq[i]
            i += 1
        tables = self.kv.table_array(seqs, self.max_blocks)
        return self._decode_paged(jnp.asarray(self.last_tok), tables, pos,
                                  bids, offs)

    def _pick_victim(self, min_slot: int) -> Optional[int]:
        """Memory-pressure victim: the highest-index active slot at or above
        ``min_slot`` — slots below it already appended this wave and must
        keep their reservation."""
        for j in range(self.n_slots - 1, min_slot - 1, -1):
            if self.batcher.slots[j] is not None:
                return j
        return None

    def _preempt_slot(self, j: int):
        """Hand slot ``j``'s request (partial generation intact) back to the
        head of the waiting queue and release its blocks; a later admission
        re-prefills its context."""
        req = self.batcher.slots[j]
        self.batcher.slots[j] = None
        self.batcher.waiting.insert(0, req)
        self.kv.free(self._slot_seq[j])
        self._slot_seq[j] = None
        self.n_mem_preempts += 1

    def _evict_parked(self) -> bool:
        """Free the oldest parked sequence's blocks; True if one existed."""
        if not self._parked:
            return False
        rid = next(iter(self._parked))
        del self._parked[rid]
        self.kv.free(("req", rid))
        return True

    # --- lifecycle ------------------------------------------------------------
    def _reap(self):
        for req in self.batcher.finished:
            key = ("req", req.id)
            self._parked.pop(req.id, None)
            if key in self.kv.alloc.tables:
                self.kv.free(key)
        for i in range(self.n_slots):
            if self.batcher.slots[i] is None:
                self._slot_seq[i] = None

    def drain(self) -> List[GenRequest]:
        # pin each in-flight request's blocks under its id: the sequence
        # stays in the allocator until resumed, evicted, or finished
        for i, req in self.batcher.active().items():
            self._parked[req.id] = tuple(req.prompt) + tuple(req.generated)
        out = super().drain()
        self._slot_seq = [None] * self.n_slots
        while len(self._parked) > self.max_parked:
            self._evict_parked()
        return out

    def register_prefix(self, tokens: List[int]) -> bool:
        """Prefill a shared context prefix once; later admissions whose
        context starts with it fork its blocks instead of re-prefilling."""
        toks = tuple(int(t) for t in tokens)
        if not toks:
            return False
        if toks in self._prefixes:
            return True
        assert len(toks) < self.max_seq, (len(toks), self.max_seq)
        key = ("prefix", len(self._prefixes))
        self.kv.create(key)
        try:
            _, pre = self._prefill(
                self.params, {"tokens": jnp.asarray([list(toks)], jnp.int32)})
            seg = pre[next(iter(pre))]
            self.kv.write_prefill(key, seg["k"][:, 0], seg["v"][:, 0])
        except OutOfBlocks:
            self.kv.free(key)
            return False
        self.prefill_tokens += len(toks)
        self._prefixes[toks] = key
        return True

    def kv_stats(self) -> Dict[str, float]:
        st = self.kv.stats()
        denom = self.prefill_tokens + self.shared_tokens + self.resumed_tokens
        reused = self.shared_tokens + self.resumed_tokens
        st.update({
            "layout": "paged",
            "tokens_in_use": int(sum(
                self.kv.length(s) for s in self.kv.alloc.tables
                if s != self.kv.NULL_SEQ)),
            "capacity_tokens": (self.n_blocks - 1) * self.block_size,
            "prefill_tokens": self.prefill_tokens,
            "shared_tokens": self.shared_tokens,
            "resumed_tokens": self.resumed_tokens,
            "share_hits": self.share_hits,
            "resume_hits": self.resume_hits,
            "mem_preempts": self.n_mem_preempts,
            "share_hit_rate": reused / denom if denom else 0.0,
        })
        return st


# FaaS-request -> real-execution adaptation lives behind the platform's
# Executor seam: see repro.platform.executors.ServingExecutor (sequential)
# and BatchedServingExecutor (continuous batching, key "batched-serving").
