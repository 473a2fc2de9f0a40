"""Continuous-batching serving engine over the paged quantized KV cache.

vLLM-style iteration loop on top of ``models.Model.decode_step``:

* **admission** — per-tenant round-robin over FIFO queues, gated on a free
  batch slot *and* a conservative page reservation for the whole request
  (prompt + max_new_tokens; no preemption, so an admitted request can
  always finish).  Head-of-line blocking is global: the first request
  that doesn't fit stops admission for the iteration, so big requests
  are never starved by later small ones.
* **chunked prefill** — each admitted prompt is absorbed in fixed-size
  chunks at batch width 1 (its own slot view of the shared pool).  Chunk
  boundaries are a pure function of (prompt length, ``prefill_chunk``):
  the per-iteration token budget decides *how many whole chunks* run,
  never where they split — so a request's compute graph, and therefore
  its rounding streams, are identical whatever else is in flight.
* **decode** — one batched single-token step per iteration across all
  slots (inactive slots ride along masked: token 0 in, scatter diverted
  to the scratch page, output discarded).
* **completion/eviction** — pages and the slot are freed the moment a
  request hits its token budget; ``cancel`` evicts early.

Determinism contract: with a GEMM-identity policy (attention sites +
``kv_cache_fmt`` only — e.g. ``make_policy(attn=..., kv_cache_fmt=...)``)
every rounded value a request sees is keyed by (request seed, layer,
position, kv head, site), so its decoded token stream is bit-identical
across arrival schedules, slot placements, co-tenants and batch widths
(tests/test_serving.py).  Policies that also round the GEMM projections
stay deterministic per engine configuration but are schedule-dependent,
exactly like the fixed-batch driver.

Profiler spans (``jax.profiler.TraceAnnotation``: written into an active
``jax.profiler`` trace on the device events' clock, close to free when no
trace is active; they add no sync and no device work):

* ``engine.step`` — one iteration, the whole of ``step()``;
* ``engine.admit`` — admission (``_admit``);
* ``engine.decode`` (``active`` = slots decoded; ``live_pages`` = the
  pages the paged kernel walks, each slot's ``ceil(length / page)`` with
  the new token, of ``table_pages`` = slots x table width) — the batched
  decode: table/word mirrors, host-to-device copies, the dispatch of the
  ``engine_decode`` program and the emission of its tokens;
* ``engine.decode.wait`` — inside it, the host blocked on the sampled
  tokens;
* ``engine.prefill`` (``rid``, ``tokens``) — one prefill chunk: its cache
  view, host-to-device copies and the dispatch of ``engine_prefill``;
* ``engine.prefill.wait`` (``rid``) — inside a request's last chunk, the
  host blocked on its first token.

The two jitted programs are named ``engine_decode`` and
``engine_prefill``, so their device modules are ``jit_engine_decode`` and
``jit_engine_prefill`` in a trace.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
import weakref
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.precision import attention as PA
from repro.serving.paged_cache import (BlockAllocator, PagedKVCache,
                                       init_paged_cache, request_words)

_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Sequence[int]
    max_new_tokens: int
    tenant: str = "default"
    seed: int = 0


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: List[int]
    arrival_time: float
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    prompt_len: int = 0
    slot: Optional[int] = None     # batch slot it was admitted to


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    page_size: int = 8
    total_pages: int = 64          # incl. the reserved scratch page 0
    max_pages_per_request: int = 8  # block-table width n_max
    prefill_chunk: int = 8
    token_budget: int = 16         # decode + prefill tokens per iteration
    max_queue: int = 256


@dataclasses.dataclass
class _Slot:
    req: Request
    pages: List[int]
    layer_words: np.ndarray        # (L, 2) uint32
    prefilled: int = 0             # prompt tokens absorbed so far
    length: int = 0                # tokens in the cache
    cur_token: int = -1            # next decode input (last sampled token)
    generated: int = 0


# one jitted (step, decode) pair per model, shared by every engine
# instance — `jax.jit(model.decode_step)` wraps a fresh bound method each
# time, so a per-engine wrapper would recompile every shape an earlier
# engine already compiled (e.g. a restarted engine, or a benchmark's
# warmup instance)
_STEP_CACHE = weakref.WeakKeyDictionary()


@functools.lru_cache(maxsize=4096)
def _layer_words(seed: int, n_layers: int) -> np.ndarray:
    """Per-layer request words (pure in (seed, n_layers)).  The fold chain
    runs as jnp threefry dispatches — a few ms per admission that would
    otherwise land on the serving critical path."""
    return np.asarray(PA.request_layer_words(
        jnp.asarray(request_words(seed))[None], n_layers))[:, 0]


def _jitted_step(model):
    fns = _STEP_CACHE.get(model)
    if fns is None:
        # the prefill chunk step (its Python name is the program's name)
        def engine_prefill(params, caches, tokens, pos, rng=None,
                           compute_logits=True):
            return model.decode_step(params, caches, tokens, pos, rng=rng,
                                     compute_logits=compute_logits)

        step = jax.jit(engine_prefill, donate_argnums=(1,),
                       static_argnames=("compute_logits",))

        # decode-path wrapper: argmax inside the jit (sampling on device
        # saves a separate dispatch + logits sync per engine iteration)
        # and ONLY the pools donated — the tables/words mirrors are reused
        # across calls, so donating the whole cache pytree would delete
        # them out from under the next iteration
        def engine_decode(params, k_pages, v_pages, tables, lengths, words,
                          append, tokens, pos, rng):
            cache = PagedKVCache(k_pages=k_pages, v_pages=v_pages,
                                 tables=tables, lengths=lengths,
                                 words=words, append=append)
            logits, nc = model.decode_step(params, {"attn": cache}, tokens,
                                           pos, rng=rng, compute_logits=True)
            return (jnp.argmax(logits[:, -1], axis=-1),
                    nc["attn"].k_pages, nc["attn"].v_pages)

        fns = (step, jax.jit(engine_decode, donate_argnums=(1, 2)))
        _STEP_CACHE[model] = fns
    return fns


class ContinuousBatchingEngine:
    def __init__(self, model, params, engine_cfg: EngineConfig = None,
                 clock=time.perf_counter):
        cfg = model.cfg
        plan = model.decoder_plan()
        if set(plan) != {"attn"} or cfg.mla is not None \
                or cfg.encoder_layers:
            raise ValueError("continuous batching supports pure attention "
                             f"decoder plans (got {sorted(set(plan))})")
        self.model = model
        self.params = params
        self.cfg = engine_cfg or EngineConfig()
        self.clock = clock
        ec = self.cfg
        self._n_layers = len(plan)
        self._alloc = BlockAllocator(ec.total_pages)
        cache = init_paged_cache(cfg, ec.n_slots, ec.total_pages,
                                 ec.page_size, ec.max_pages_per_request,
                                 n_layers=self._n_layers)
        self._k_pages = cache.k_pages
        self._v_pages = cache.v_pages
        self.hbm_bytes = self._k_pages.nbytes + self._v_pages.nbytes
        self._slots: List[Optional[_Slot]] = [None] * ec.n_slots
        self._queues: Dict[str, collections.deque] = {}
        self._tenant_rr: List[str] = []
        self._rr = 0
        self._ticks = 0           # model calls issued (rng decorrelation)
        self.iterations = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.results: Dict[int, RequestResult] = {}
        self._step_fn, self._decode_fn = _jitted_step(model)
        self._mirror = None       # cached device (tables, words) mirrors

    # ------------------------------------------------------------- intake --
    def _pages_needed(self, req: Request) -> int:
        return math.ceil((len(req.prompt) + req.max_new_tokens)
                         / self.cfg.page_size)

    def submit(self, req: Request) -> None:
        if req.rid in self.results:
            raise ValueError(f"duplicate rid {req.rid}")
        if not len(req.prompt) or req.max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens>=1")
        if self._pages_needed(req) > self.cfg.max_pages_per_request:
            raise ValueError(
                f"request {req.rid} needs {self._pages_needed(req)} pages "
                f"> table width {self.cfg.max_pages_per_request}")
        if sum(len(q) for q in self._queues.values()) >= self.cfg.max_queue:
            raise ValueError("queue full")
        if req.tenant not in self._queues:
            self._queues[req.tenant] = collections.deque()
            self._tenant_rr.append(req.tenant)
        self._queues[req.tenant].append(req)
        self.results[req.rid] = RequestResult(
            rid=req.rid, tokens=[], arrival_time=self.clock(),
            prompt_len=len(req.prompt))

    def cancel(self, rid: int) -> bool:
        """Evict a request: drop it from its queue, or free its slot and
        pages mid-flight.  Returns True if it was still live."""
        for q in self._queues.values():
            for r in list(q):
                if r.rid == rid:
                    q.remove(r)
                    return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.rid == rid:
                self._release(i, finished=False)
                return True
        return False

    def _admit(self) -> None:
        n_t = len(self._tenant_rr)
        if not n_t:
            return
        scanned = 0
        while scanned < n_t:
            tenant = self._tenant_rr[self._rr % n_t]
            q = self._queues[tenant]
            if not q:
                self._rr += 1
                scanned += 1
                continue
            free_slots = [i for i, s in enumerate(self._slots) if s is None]
            if not free_slots:
                return
            req = q[0]
            pages = self._alloc.alloc(self._pages_needed(req))
            if pages is None:
                return              # head-of-line blocks: no starvation
            q.popleft()
            lw = _layer_words(req.seed, self._n_layers)
            self._slots[free_slots[0]] = _Slot(req=req, pages=pages,
                                               layer_words=lw)
            self.results[req.rid].slot = free_slots[0]
            self._mirror = None
            self._rr += 1
            scanned = 0             # fresh round after a successful admit

    def _release(self, i: int, finished: bool) -> None:
        slot = self._slots[i]
        self._alloc.free(slot.pages)
        self._slots[i] = None
        self._mirror = None
        if finished:
            self.results[slot.req.rid].finish_time = self.clock()

    # ------------------------------------------------------- device plumbing
    def _make_cache(self, idx: Sequence[int], append: np.ndarray
                    ) -> PagedKVCache:
        """Assemble the PagedKVCache for slots ``idx`` (host mirrors →
        device; the big pools ride through by reference)."""
        ec, L = self.cfg, self._n_layers
        B = len(idx)
        tables = np.zeros((B, ec.max_pages_per_request), np.int32)
        lengths = np.zeros((B,), np.int32)
        words = np.zeros((L, B, 2), np.uint32)
        for j, i in enumerate(idx):
            slot = self._slots[i]
            if slot is not None:
                tables[j, :len(slot.pages)] = slot.pages
                lengths[j] = slot.length
                words[:, j] = slot.layer_words
        return PagedKVCache(
            k_pages=self._k_pages, v_pages=self._v_pages,
            tables=jnp.asarray(np.broadcast_to(tables, (L,) + tables.shape)),
            lengths=jnp.asarray(np.broadcast_to(lengths, (L, B))),
            words=jnp.asarray(words),
            append=jnp.asarray(np.broadcast_to(append, (L, B))))

    def _tick_rng(self):
        """Per-call rng key, built host-side — a ``fold_in`` here would be
        its own device dispatch on every engine call.  Uniqueness per tick
        is all that's required (and under the GEMM-identity determinism
        contract the key is unused entirely: every rounded site is keyed
        by the request words)."""
        t = self._ticks
        self._ticks += 1
        return jnp.asarray(np.array([t >> 32, t & 0xFFFFFFFF], np.uint32))

    def _call(self, idx, append, tokens, compute_logits):
        lengths = np.array([self._slots[i].length if self._slots[i] else 0
                            for i in idx], np.int32)
        cache = self._make_cache(idx, append)
        logits, nc = self._step_fn(self.params, {"attn": cache},
                                   jnp.asarray(tokens), jnp.asarray(lengths),
                                   rng=self._tick_rng(),
                                   compute_logits=compute_logits)
        self._k_pages = nc["attn"].k_pages
        self._v_pages = nc["attn"].v_pages
        return logits

    # --------------------------------------------------------------- step --
    def _prefill_chunks(self, budget: int) -> int:
        """Run whole prefill chunks round-robin until the budget is spent.
        At least one chunk always runs when any prefill is pending, so a
        chunk larger than the leftover budget can't livelock."""
        spent = 0
        progressed = True
        while progressed:
            progressed = False
            for i, slot in enumerate(self._slots):
                if slot is None or slot.prefilled >= len(slot.req.prompt):
                    continue
                chunk = min(self.cfg.prefill_chunk,
                            len(slot.req.prompt) - slot.prefilled)
                if spent and spent + chunk > budget:
                    continue
                lo, hi = slot.prefilled, slot.prefilled + chunk
                last = hi == len(slot.req.prompt)
                rid = slot.req.rid
                with _span("engine.prefill", rid=rid, tokens=chunk):
                    toks = np.asarray(slot.req.prompt[lo:hi], np.int32)[None]
                    logits = self._call([i], np.ones((1,), bool), toks,
                                        compute_logits=last)
                    if last:
                        with _span("engine.prefill.wait", rid=rid):
                            tok = int(jnp.argmax(logits[0, -1]))
                slot.prefilled = hi
                slot.length += chunk
                spent += chunk
                self.prefill_tokens += chunk
                progressed = True
                if last:
                    self._emit(i, tok)
        return spent

    def _emit(self, i: int, tok: int) -> None:
        slot = self._slots[i]
        res = self.results[slot.req.rid]
        if res.first_token_time is None:
            res.first_token_time = self.clock()
        res.tokens.append(tok)
        slot.generated += 1
        slot.cur_token = tok
        if slot.generated >= slot.req.max_new_tokens:
            self._release(i, finished=True)

    def _decode_batch(self) -> None:
        idx = list(range(self.cfg.n_slots))
        ec, L = self.cfg, self._n_layers
        active = np.array([s is not None and s.cur_token >= 0
                           for s in self._slots], bool)
        lengths = np.array([s.length if s is not None else 0
                            for s in self._slots], np.int32)
        # the pages the paged kernel walks (each slot's length with the new
        # token) against the block table's width
        live_pages = int(np.sum(-(-(lengths + active) // ec.page_size)))
        with _span("engine.decode", active=int(active.sum()),
                   live_pages=live_pages,
                   table_pages=ec.n_slots * ec.max_pages_per_request):
            tokens = np.array([[s.cur_token if s is not None
                                and s.cur_token >= 0 else 0]
                               for s in self._slots], np.int32)
            # full-width fast path: tables/words device mirrors change only
            # on admit/release, so reuse them; lengths/append are per-call
            if self._mirror is None:
                tables = np.zeros((ec.n_slots, ec.max_pages_per_request),
                                  np.int32)
                words = np.zeros((L, ec.n_slots, 2), np.uint32)
                for i, slot in enumerate(self._slots):
                    if slot is not None:
                        tables[i, :len(slot.pages)] = slot.pages
                        words[:, i] = slot.layer_words
                self._mirror = (
                    jnp.asarray(np.broadcast_to(tables,
                                                (L,) + tables.shape)),
                    jnp.asarray(words))
            nxt, self._k_pages, self._v_pages = self._decode_fn(
                self.params, self._k_pages, self._v_pages, self._mirror[0],
                jnp.asarray(np.broadcast_to(lengths, (L, ec.n_slots))),
                self._mirror[1],
                jnp.asarray(np.broadcast_to(active, (L, ec.n_slots))),
                jnp.asarray(tokens), jnp.asarray(lengths), self._tick_rng())
            with _span("engine.decode.wait"):
                nxt = np.asarray(nxt)
            for i in idx:
                if active[i]:
                    self._slots[i].length += 1
                    self.decode_tokens += 1
                    self._emit(i, int(nxt[i]))

    @functools.partial(jax.profiler.annotate_function, name="engine.step")
    def step(self) -> List[int]:
        """One engine iteration: admit → batched decode → prefill chunks.
        Returns the rids finished this iteration."""
        before = {rid for rid, r in self.results.items()
                  if r.finish_time is not None}
        with _span("engine.admit"):
            self._admit()
        budget = self.cfg.token_budget
        n_active = sum(1 for s in self._slots
                       if s is not None and s.cur_token >= 0)
        if n_active:
            self._decode_batch()
        budget = max(0, budget - n_active)
        self._prefill_chunks(budget)
        self.iterations += 1
        return [rid for rid, r in self.results.items()
                if r.finish_time is not None and rid not in before]

    @property
    def busy(self) -> bool:
        return any(s is not None for s in self._slots) or \
            any(self._queues[t] for t in self._queues)

    def run(self, requests: Sequence[Request], arrivals=None,
            max_iterations: int = 100_000) -> Dict[int, RequestResult]:
        """Drive to completion.  ``arrivals`` gives each request's arrival
        *iteration* (default: all at 0) — the knob the bit-reproducibility
        tests turn to perturb the batching schedule."""
        if arrivals is None:
            arrivals = [0] * len(requests)
        order = sorted(range(len(requests)), key=lambda i: arrivals[i])
        cursor = 0
        for it in range(max_iterations):
            while cursor < len(order) and arrivals[order[cursor]] <= it:
                self.submit(requests[order[cursor]])
                cursor += 1
            self.step()
            if cursor == len(order) and not self.busy:
                return self.results
        raise RuntimeError(f"engine did not drain in {max_iterations} "
                           "iterations")

    # ---------------------------------------------------------------- stats
    def utilization(self) -> Dict[str, float]:
        used = self._alloc.total_pages - 1 - self._alloc.free_pages
        return {"pages_used": used,
                "page_util": used / (self._alloc.total_pages - 1),
                "slots_used": sum(s is not None for s in self._slots),
                "slot_util": (sum(s is not None for s in self._slots)
                              / self.cfg.n_slots),
                "hbm_bytes": self.hbm_bytes}
