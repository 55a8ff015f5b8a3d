"""Continuous-batching request scheduler (Orca/vLLM pattern, DESIGN.md §9).

The one-shot engine compiles one ``(batch, prompt_len)`` shape and runs
it start-to-finish: every request waits for the whole batch, the batch
waits for its slowest sequence, and each new shape recompiles. The
scheduler fixes all three on top of the engine's slot-pool primitives:

  * a FIFO request queue with arrival times;
  * a persistent slot pool (`engine.init_slot_pool`): each live request
    owns one slot row of the fixed-``max_seq`` decode cache;
  * length-bucketed admission: new prompts are right-padded to the
    smallest configured bucket and prefilled in fixed-width groups
    (`engine.prefill_into_slots`), so prefill compiles once per bucket;
  * one batched decode executable over ALL slots at per-slot positions
    (`engine.decode_pool_step`) — compile count O(buckets + 1);
  * mid-flight admission: a slot retires the moment its request finishes
    (EOS or per-request token budget) and is re-prefilled with the next
    queued prompt while the other slots keep decoding.

Output parity: with greedy decoding and non-binding eval expert capacity
(``eval_capacity_factor >= n_experts``), every request's tokens are
BITWISE identical to a per-request one-shot ``generate`` run against the
same cache length (``GenerateConfig(max_seq=pool max_seq)``) — asserted
in ``tests/test_scheduler.py`` and ``benchmarks/table8_serving.py``.
Sampled requests draw from per-request key streams ``fold(fold(rng,
seed), token_index)`` (engine._select_rows), so sampling is also
placement-invariant given the request's ``seed``.

Exactness policy: SSM-state archs (``cfg.ssm``) integrate right-padding
into their prefilled state, and sliding-window rings evict real tokens
when ``bucket - prompt_len`` pushes pads into the window — those configs
are prefilled at EXACT prompt length (one compile per distinct length)
instead of padded buckets. Attention-cache archs keep bucketed padding:
causal masking hides pads at prefill and pool decode overwrites each pad
cache row exactly when it would become visible.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, PagedKVConfig
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import Tracer, get_tracer, monotonic
from repro.serve.engine import (GenerateConfig, _check_local_routing,
                                _select_rows, decode_pool_step,
                                prefill_into_slots, slot_pool_like)
from repro.serve.paged import (PageAllocator, PagedLayout, PagePoolExhausted,
                               PrefixCache, _cache_page_axes, ceil_div,
                               copy_pages, decode_paged_step,
                               gather_slot_state, paged_kv_bytes,
                               paged_pool_like, prefill_into_pages,
                               restore_slot_state)


@dataclasses.dataclass
class Request:
    """One generation request. ``extras`` holds the family's conditioning
    inputs WITHOUT a batch axis (e.g. ``enc_tokens (S,)``, ``frames
    (S, d)``). ``max_new`` caps this request's generated tokens (defaults
    to the scheduler's ``GenerateConfig.max_new``); ``seed`` keys its
    sampling stream; ``arrival`` is in scheduler-clock seconds."""
    rid: int
    tokens: np.ndarray
    extras: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    max_new: Optional[int] = None
    seed: Optional[int] = None
    arrival: float = 0.0


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray          # (length,) generated tokens incl. EOS
    length: int
    score: float                # sum log p of emitted tokens
    arrival: float              # scheduler-clock seconds
    admitted_at: float          # prefill started (slot assigned)
    first_token_at: float       # TTFT reference point
    finished_at: float

    @property
    def ttft(self) -> float:
        return self.first_token_at - self.arrival

    @property
    def per_token_latency(self) -> float:
        return ((self.finished_at - self.arrival) / self.length
                if self.length else 0.0)


@functools.lru_cache(maxsize=32)
def _pool_decode_fn(cfg: ModelConfig, gen: GenerateConfig, ctx):
    """THE decode executable of a serving process (jit caches per pool
    shape). Memoized so every scheduler instance over the same config
    shares one compiled step."""
    @jax.jit
    def step(params, pool, tok, pos, alive, rng, seeds, steps):
        lg, pool = decode_pool_step(params, pool, tok, pos, alive, cfg,
                                    ctx, local_routing=gen.local_routing,
                                    flash_decode=gen.flash_decode)
        nxt, lp = _select_rows(gen, lg.astype(jnp.float32), rng, seeds,
                               steps)
        return pool, nxt, lp

    return step


@functools.lru_cache(maxsize=32)
def _bucket_prefill_fn(cfg: ModelConfig, gen: GenerateConfig, ctx,
                       max_seq: int):
    """Admission executable; jit specializes per (admit_width, bucket)
    token shape — one compile per bucket at fixed admission width."""
    @jax.jit
    def pf(params, batch, lengths, slots, pool, rng, seeds):
        logits, pool = prefill_into_slots(params, batch, lengths, slots,
                                          pool, cfg, ctx, max_seq=max_seq)
        tok0, lp0 = _select_rows(gen, logits.astype(jnp.float32), rng,
                                 seeds, jnp.zeros(lengths.shape, jnp.int32))
        return pool, tok0, lp0

    return pf


def needs_exact_prefill(cfg: ModelConfig, max_bucket: int) -> bool:
    """True when right-padded bucket prefill cannot reproduce exact-length
    prefill: SSM state integrates pads; sliding-window rings evict real
    tokens once the padded length exceeds the window."""
    if cfg.ssm is not None:
        return True
    return cfg.sliding_window > 0 and max_bucket > cfg.sliding_window


class ContinuousScheduler:
    """Slot-based continuous-batching serving loop (host-side driver).

    The device-side work is two jitted executables: one prefill per
    bucket (fixed admission width) and ONE pool decode step. The host
    keeps per-slot bookkeeping as numpy vectors, feeds them to the decode
    step each tick, and collects one token per live slot per tick."""

    def __init__(self, params, cfg: ModelConfig, gen: GenerateConfig, *,
                 n_slots: int = 8, ctx=None,
                 prefill_buckets: Sequence[int] = (8, 16, 32, 64),
                 admit_width: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 rng: Optional[jax.Array] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        assert gen.beam_width == 1, "continuous batching serves sampling/" \
            "greedy requests; beam search stays on the one-shot engine"
        _check_local_routing(cfg, gen)
        self.params = params
        self.cfg = cfg
        self.gen = gen
        self.ctx = ctx
        self.n_slots = n_slots
        self.buckets = tuple(sorted(prefill_buckets))
        self.exact_prefill = needs_exact_prefill(cfg, self.buckets[-1])
        self.admit_width = admit_width or min(4, n_slots)
        self.max_seq = max_seq or (self.buckets[-1] + gen.max_new)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # pool row n_slots is a scratch slot: admission groups are padded
        # with dummy rows that scatter there. Allocation is deferred to
        # the first admission (slot_pool_like): cross-KV leaf length
        # follows the conditioning inputs actually served, which may
        # differ from config defaults.
        self.pool = None
        self._extras_shapes: Optional[Dict[str, Tuple]] = None
        S = n_slots + 1
        self._tok = np.zeros(S, np.int32)
        self._pos = np.zeros(S, np.int32)
        self._ngen = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._done = np.zeros(S, bool)
        self._budget = np.full(S, gen.max_new, np.int32)
        self._length = np.zeros(S, np.int32)
        self._score = np.zeros(S, np.float64)
        self._seed = np.zeros(S, np.int32)
        self._slot_rid: List[Optional[int]] = [None] * S
        self._free = deque(range(n_slots))
        self._queue: deque[Request] = deque()
        self._buffers: Dict[int, List[int]] = {}
        self._meta: Dict[int, Dict[str, float]] = {}
        self._reqs: Dict[int, Request] = {}
        self.stats = {"admitted": 0, "finished": 0, "prefill_calls": 0,
                      "decode_steps": 0, "max_concurrent": 0,
                      "slot_reuse": 0}
        # observability (DESIGN.md §15): one registry backs every serving
        # metric of this scheduler — the legacy tick_log/alive_log
        # attributes are live views over two registry Series, and TTFT /
        # per-token latency land in registry histograms at retire time
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else get_tracer()
        # (kind, tokens) per executed device call, in order — the comm
        # accounting feed: launch/serve.py --trace prices each tick with
        # the exchange's bytes model (comm/exchange.py, DESIGN.md §10)
        self._ticks = self.metrics.series(
            "serve/tick_log", "device calls: label=kind, value=tokens")
        # live-slot count per decode tick: the sustained-concurrency
        # series benchmarks/table10_paged.py compares across cache layouts
        self._alive_series = self.metrics.series(
            "serve/alive_log", "live slots per decode tick")
        self._ttft = self.metrics.histogram(
            "serve/ttft_s", "arrival -> first token, seconds")
        self._lat = self.metrics.histogram(
            "serve/per_token_latency_s", "request seconds per token")
        self._slot_uses = np.zeros(n_slots, np.int64)
        self._prefill = _bucket_prefill_fn(cfg, gen, ctx, self.max_seq)
        self._decode_fn = _pool_decode_fn(cfg, gen, ctx)
        # clock state so the tick API (submit + step) works without run()
        self._t0 = monotonic()
        self._skip = 0.0

    # -- legacy metric views (exact aliases of the registry Series) ---------

    @property
    def tick_log(self) -> List[Tuple[str, int]]:
        return self._ticks.items

    @property
    def alive_log(self) -> List[int]:
        return self._alive_series.values

    # -- request intake -----------------------------------------------------

    def submit(self, req: Request):
        assert req.tokens.ndim == 1
        if not self.exact_prefill and len(req.tokens) > self.buckets[-1]:
            raise ValueError(
                f"prompt length {len(req.tokens)} exceeds the largest "
                f"prefill bucket {self.buckets[-1]}; add a larger bucket "
                f"at scheduler init")
        budget = req.max_new or self.gen.max_new
        if budget > self.gen.max_new:
            raise ValueError(
                f"request max_new {budget} exceeds the scheduler's "
                f"GenerateConfig.max_new {self.gen.max_new}")
        # holds for bucketed admission by construction (bucket + max_new
        # <= max_seq); the exact-prefill path (SSM/oversized-window) has
        # no bucket cap, and an overflow would silently wrap cache writes
        # back into live positions — fail loudly up front instead
        if len(req.tokens) + budget > self.max_seq:
            raise ValueError(
                f"prompt {len(req.tokens)} + budget {budget} exceeds the "
                f"pinned pool cache length max_seq={self.max_seq}; raise "
                f"max_seq= at scheduler init — the pool cannot grow")
        self._queue.append(req)
        self._reqs[req.rid] = req
        self._meta[req.rid] = {"arrival": req.arrival}

    def _bucket(self, n: int) -> int:
        if self.exact_prefill:
            return n
        for b in self.buckets:
            if n <= b:
                return b
        raise AssertionError(n)

    # -- scheduling ticks ---------------------------------------------------

    def _retire(self, now: float) -> List[RequestResult]:
        out = []
        for s in range(self.n_slots):
            rid = self._slot_rid[s]
            if rid is None or not self._done[s]:
                continue
            meta = self._meta[rid]
            res = RequestResult(
                rid=rid, tokens=np.asarray(self._buffers[rid], np.int32),
                length=int(self._length[s]), score=float(self._score[s]),
                arrival=meta["arrival"], admitted_at=meta["admitted_at"],
                first_token_at=meta["first_token_at"], finished_at=now)
            out.append(res)
            self._ttft.observe(res.ttft)
            self._lat.observe(res.per_token_latency)
            self._slot_rid[s] = None
            self._active[s] = False
            self._done[s] = False
            self._free.append(s)
            self.stats["finished"] += 1
        return out

    def _token_done(self, tok: int, ngen: int, budget: int) -> bool:
        """One-shot `_advance` semantics: done on EOS or budget reached."""
        return (self.gen.eos_id >= 0 and tok == self.gen.eos_id) \
            or ngen >= budget

    def _can_admit(self, req: Request) -> bool:
        """Admission gate hook beyond slot availability — the base
        scheduler admits whenever a slot is free; the paged scheduler
        overrides this with free-page accounting (reserving the pages as
        a side effect, so a True answer cannot fail later)."""
        return True

    def _admit(self, now: float):
        if not (self._free and self._queue
                and self._queue[0].arrival <= now):
            return
        with self.tracer.span("sched.admit", queued=len(self._queue)):
            self._admit_loop(now)

    def _admit_loop(self, now: float):
        while self._free and self._queue \
                and self._queue[0].arrival <= now:
            # head-of-queue request sets the bucket; scan the ELIGIBLE
            # queue prefix for same-bucket peers so admission groups fill
            # up instead of fragmenting into per-request prefills (the
            # head request is always admitted — no starvation)
            if not self._can_admit(self._queue[0]):
                break                     # backpressure: keep FIFO order
            bucket = self._bucket(len(self._queue[0].tokens))
            group: List[Request] = []
            skipped: List[Request] = []
            while (self._queue and len(group) < self.admit_width
                   and len(group) < len(self._free)
                   and self._queue[0].arrival <= now):
                r = self._queue.popleft()
                if self._bucket(len(r.tokens)) == bucket \
                        and (group == [] or self._can_admit(r)):
                    group.append(r)
                else:
                    skipped.append(r)
            for r in reversed(skipped):
                self._queue.appendleft(r)
            if not group:
                break
            self._prefill_group(group, bucket, now)

    def _stage_group(self, group: List[Request], bucket: int):
        """Host-side admission staging shared by the slot-pool and paged
        schedulers: pad the group to the next power-of-two width (<=
        admit_width) so mid-flight single-slot refills cost a width-1
        prefill, not a full admit_width one (compile count stays
        O(buckets * log W)); assign freed slots; build the device batch."""
        W = 1
        while W < len(group):
            W *= 2
        pad = self.gen.pad_id
        tokens = np.full((W, bucket), pad, np.int32)
        lengths = np.ones(W, np.int32)
        slots = np.full(W, self.n_slots, np.int32)      # dummies -> scratch
        seeds = np.zeros(W, np.int32)
        for i, req in enumerate(group):
            tokens[i, :len(req.tokens)] = req.tokens
            lengths[i] = len(req.tokens)
            s = self._free.popleft()
            slots[i] = s
            seeds[i] = req.seed if req.seed is not None else req.rid
            self._slot_rid[s] = req.rid
            self._slot_uses[s] += 1
            if self._slot_uses[s] > 1:
                self.stats["slot_reuse"] += 1
        batch = {"tokens": jnp.asarray(tokens)}
        for k in group[0].extras:
            rows = np.stack([r.extras[k] for r in group])
            if len(group) < W:
                fill = np.zeros((W - len(group),) + rows.shape[1:],
                                rows.dtype)
                rows = np.concatenate([rows, fill], 0)
            batch[k] = jnp.asarray(rows)
        self._ensure_pool(batch)
        return W, lengths, slots, seeds, batch

    def _alloc_pool(self, batch):
        return slot_pool_like(self.params, batch, self.cfg, self.ctx,
                              max_seq=self.max_seq,
                              n_slots=self.n_slots + 1)

    def _ensure_pool(self, batch):
        shapes = {k: tuple(v.shape[1:]) for k, v in batch.items()
                  if k != "tokens"}
        if self.pool is None:
            self._extras_shapes = shapes
            self.pool = self._alloc_pool(batch)
        else:
            assert shapes == self._extras_shapes, \
                "every request of a serving process must carry the same " \
                f"conditioning shapes: {shapes} != {self._extras_shapes}"

    def _finish_admission(self, group: List[Request], bucket: int, W: int,
                          lengths, slots, seeds, tok0, lp0, now: float):
        """Per-slot host bookkeeping once the admission prefill's first
        tokens are on the host."""
        t_first = self._now()
        for i, req in enumerate(group):
            s = int(slots[i])
            self._tok[s] = tok0[i]
            self._pos[s] = lengths[i]          # tok0 lives at position P
            self._ngen[s] = 1
            self._active[s] = True
            self._budget[s] = req.max_new or self.gen.max_new
            self._done[s] = self._token_done(int(tok0[i]), 1,
                                             int(self._budget[s]))
            self._length[s] = 1
            self._score[s] = lp0[i]
            self._seed[s] = seeds[i]
            self._buffers[req.rid] = [int(tok0[i])]
            self._meta[req.rid].update(admitted_at=now,
                                       first_token_at=t_first)
            self.stats["admitted"] += 1
        self.stats["prefill_calls"] += 1
        self._ticks.append(W * bucket, label="prefill")
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"],
            int(self._active[:self.n_slots].sum()))

    def _prefill_group(self, group: List[Request], bucket: int, now: float):
        with self.tracer.span("sched.prefill", bucket=bucket,
                              group=len(group)):
            W, lengths, slots, seeds, batch = self._stage_group(group, bucket)
            pool, tok0, lp0 = self._prefill(
                self.params, batch, jnp.asarray(lengths), jnp.asarray(slots),
                self.pool, self.rng, jnp.asarray(seeds))
            self.pool = pool
            tok0, lp0 = jax.device_get((tok0, lp0))   # the tick's one sync
            self._finish_admission(group, bucket, W, lengths, slots, seeds,
                                   tok0, lp0, now)

    def _decode_call(self, alive):
        """Launch the pool decode executable (overridden by the paged
        scheduler to feed block tables); returns (nxt, lp) device arrays
        and reassigns ``self.pool``."""
        pool, nxt, lp = self._decode_fn(
            self.params, self.pool, jnp.asarray(self._tok),
            jnp.asarray(self._pos), jnp.asarray(alive), self.rng,
            jnp.asarray(self._seed), jnp.asarray(self._ngen))
        self.pool = pool
        return nxt, lp

    def _decode_tick(self):
        alive = self._active & ~self._done
        if not alive[:self.n_slots].any():
            return
        with self.tracer.span("sched.decode",
                              alive=int(alive[:self.n_slots].sum())):
            self._decode_tick_body(alive)

    def _decode_tick_body(self, alive):
        nxt, lp = self._decode_call(alive)
        # recompute: paged page-exhaustion preemption can deactivate slots
        # inside the decode call (their rows decode dead, outputs ignored)
        alive = self._active & ~self._done
        self._alive_series.append(int(alive[:self.n_slots].sum()))
        nxt, lp = jax.device_get((nxt, lp))       # the tick's one sync
        for s in range(self.n_slots):
            if not alive[s]:
                continue
            self._buffers[self._slot_rid[s]].append(int(nxt[s]))
            self._tok[s] = nxt[s]
            self._pos[s] += 1
            self._ngen[s] += 1
            self._length[s] += 1
            self._score[s] += float(lp[s])
            self._done[s] = self._token_done(int(nxt[s]),
                                             int(self._ngen[s]),
                                             int(self._budget[s]))
        self.stats["decode_steps"] += 1
        self._ticks.append(self.n_slots + 1, label="decode")

    # -- driving loop -------------------------------------------------------

    def _now(self) -> float:
        return monotonic() - self._t0 + self._skip

    def step(self, now: float) -> List[RequestResult]:
        """One scheduler tick: retire finished slots, admit eligible
        queued requests into freed slots, run one pool decode step."""
        finished = self._retire(now)
        self._admit(now)
        self._decode_tick()
        return finished

    def run(self, requests: Sequence[Request]) -> List[RequestResult]:
        """Serve ``requests`` (arrival-stamped) to completion. The clock
        is wall time, fast-forwarded across idle gaps between arrivals so
        sparse traces don't busy-wait."""
        for r in sorted(requests, key=lambda r: r.arrival):
            self.submit(r)
        self._t0 = monotonic()
        self._skip = 0.0
        results: List[RequestResult] = []
        while self._queue or self._active[:self.n_slots].any():
            now = self._now()
            if (not self._active[:self.n_slots].any() and self._queue
                    and self._queue[0].arrival > now):
                self._skip += self._queue[0].arrival - now
                now = self._now()
            results.extend(self.step(now))
        results.extend(self._retire(self._now()))
        return sorted(results, key=lambda r: r.rid)


# ---------------------------------------------------------------------------
# paged scheduler (block-table addressed KV, DESIGN.md §13)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _paged_decode_fn(cfg: ModelConfig, gen: GenerateConfig, ctx):
    """THE decode executable of a paged serving process — the slot-pool
    twin of `_pool_decode_fn` plus the block-table operand."""
    @jax.jit
    def step(params, pool, tables, tok, pos, alive, rng, seeds, steps):
        lg, pool = decode_paged_step(params, pool, tables, tok, pos, alive,
                                     cfg, ctx,
                                     local_routing=gen.local_routing,
                                     flash_decode=gen.flash_decode)
        nxt, lp = _select_rows(gen, lg.astype(jnp.float32), rng, seeds,
                               steps)
        return pool, nxt, lp

    return step


@functools.lru_cache(maxsize=32)
def _paged_prefill_fn(cfg: ModelConfig, gen: GenerateConfig, ctx,
                      max_seq: int, layout: PagedLayout):
    @jax.jit
    def pf(params, batch, lengths, write_tables, slot_rows, pool, rng,
           seeds):
        logits, pool = prefill_into_pages(
            params, batch, lengths, write_tables, slot_rows, pool, cfg,
            ctx, max_seq=max_seq, layout=layout)
        tok0, lp0 = _select_rows(gen, logits.astype(jnp.float32), rng,
                                 seeds, jnp.zeros(lengths.shape, jnp.int32))
        return pool, tok0, lp0

    return pf


@functools.lru_cache(maxsize=8)
def _copy_pages_fn(cfg: ModelConfig):
    @jax.jit
    def cp(pool, src, dst):
        return copy_pages(pool, cfg, src, dst)

    return cp


@functools.lru_cache(maxsize=8)
def _gather_slot_fn(cfg: ModelConfig):
    @jax.jit
    def g(pool, table_row, slot):
        return gather_slot_state(pool, cfg, table_row, slot)

    return g


@functools.lru_cache(maxsize=8)
def _restore_slot_fn(cfg: ModelConfig):
    @jax.jit
    def r(pool, saved, table_row, slot):
        return restore_slot_state(pool, cfg, saved, table_row, slot)

    return r


@dataclasses.dataclass
class _SwapState:
    """Host snapshot of a preempted slot: the per-slot scheduler scalars
    plus the device cache state (its pages, page-major, and its
    slot-addressed leaf rows) pulled to host memory."""
    tok: int
    pos: int
    ngen: int
    budget: int
    length: int
    score: float
    seed: int
    saved: object


class PagedScheduler(ContinuousScheduler):
    """Continuous batching over a paged KV cache (DESIGN.md §13).

    Same host driver as `ContinuousScheduler`, three paged behaviours on
    top, all through the base class's hook methods:

      * ADMISSION BY FREE PAGES (`_can_admit`): a request is admitted only
        when its prompt's pages (minus prefix-cache hits) fit in the free
        list with `reserve_pages` headroom; reservation happens inside the
        gate so a True answer cannot fail later. Backpressure keeps FIFO
        order — the queue head blocks admission until pages free up.
      * PREFIX SHARING: full prompt pages (and whole identical prompts)
        are published to a `PrefixCache` after prefill; later requests
        point their leading block-table entries at the shared pages and
        skip re-writing them. First divergent write => COW.
      * COPY-ON-WRITE + PREEMPTION (`_ensure_writable`): before each
        decode tick every live slot's write-block must be private and
        real. A shared write-page is copied (batched `copy_pages`, padded
        to a power-of-two pair count); page exhaustion evicts cache
        entries, then preempts the youngest-admitted live slot — swap-OUT
        to host memory, not recompute, so re-admitted requests keep
        bitwise-identical outputs.

    Host syncs: one `device_get` per tick on the steady path (inherited
    from the base scheduler); preemption swap-out adds one exceptional
    gather sync, which the analysis-lint scenario deliberately avoids.
    """

    def __init__(self, params, cfg: ModelConfig, gen: GenerateConfig, *,
                 paged: PagedKVConfig = PagedKVConfig(),
                 n_slots: int = 8, ctx=None,
                 prefill_buckets: Sequence[int] = (8, 16, 32, 64),
                 admit_width: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 rng: Optional[jax.Array] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        super().__init__(params, cfg, gen, n_slots=n_slots, ctx=ctx,
                         prefill_buckets=prefill_buckets,
                         admit_width=admit_width, max_seq=max_seq, rng=rng,
                         registry=registry, tracer=tracer)
        _, seq_axes = _cache_page_axes(cfg)
        if not any(a >= 0 for a in jax.tree.leaves(seq_axes)):
            raise ValueError(
                f"{cfg.arch_id}: no cache leaf tracks max_seq (pure "
                "SSM/ring cache) — nothing to page; use "
                "ContinuousScheduler")
        self.paged = paged
        ps = paged.page_size
        self._n_meta = (cfg.hybrid.n_meta_tokens
                        if cfg.hybrid is not None else 0)
        seq_len = self.max_seq + self._n_meta
        n_blocks = ceil_div(seq_len, ps)
        n_pages = paged.n_pages or paged.n_slots_equiv * n_blocks
        if n_pages < n_blocks + paged.reserve_pages:
            raise ValueError(
                f"n_pages={n_pages} cannot hold one full-length request "
                f"({n_blocks} blocks of {ps}) plus reserve_pages="
                f"{paged.reserve_pages}; the scheduler could deadlock")
        self.layout = PagedLayout(page_size=ps, n_pages=n_pages,
                                  seq_len=seq_len)
        self._pages = PageAllocator(n_pages)
        self._prefix = (PrefixCache(self._pages)
                        if paged.prefix_caching else None)
        # rid -> (reserved page list, #prefix-shared prefix) while the
        # request sits between its _can_admit reservation and its prefill
        self._plans: Dict[int, Tuple[List[int], int]] = {}
        self._swapped: Dict[int, _SwapState] = {}
        self._cow_src: List[int] = []
        self._cow_dst: List[int] = []
        self._tables = np.full((n_slots + 1, n_blocks),
                               self.layout.scratch, np.int32)
        self.stats.update(prefix_lookups=0, prefix_hits=0, cow_copies=0,
                          preemptions=0, swap_ins=0, peak_pages_in_use=0)
        self._prefill = _paged_prefill_fn(cfg, gen, ctx, self.max_seq,
                                          self.layout)
        self._decode_fn = _paged_decode_fn(cfg, gen, ctx)
        self._copy = _copy_pages_fn(cfg)
        self._gather = _gather_slot_fn(cfg)
        self._restore = _restore_slot_fn(cfg)

    # -- page accounting ----------------------------------------------------

    @property
    def page_bytes(self) -> int:
        """Bytes one physical page pins across the pageable leaves."""
        if self.pool is None:
            return 0
        return paged_kv_bytes(self.pool, self.cfg) \
            // (self.layout.n_pages + 1)

    # note: no submit() page-budget override is needed — the base class's
    # ``prompt + budget <= max_seq`` check plus the __init__ deadlock
    # check (``n_pages >= n_blocks + reserve_pages``) together bound any
    # accepted request's worst-case page need by the arena size

    def _page_or_none(self) -> Optional[int]:
        """try_alloc with prefix-cache eviction pressure."""
        p = self._pages.try_alloc()
        while p is None and self._prefix is not None \
                and self._prefix.evict_one():
            p = self._pages.try_alloc()
        return p

    def _free_capacity(self) -> int:
        ev = (self._prefix.evictable_pages()
              if self._prefix is not None else 0)
        return self._pages.n_free + ev

    def _note_pages(self):
        self.stats["peak_pages_in_use"] = max(
            self.stats["peak_pages_in_use"], self._pages.in_use())

    def _page_key(self, tokens: np.ndarray, f: int):
        """Key of the first ``f`` full pages: page f-1 ends at logical
        position f*ps - 1, which depends on tokens up to index
        f*ps - n_meta - 1 (meta tokens occupy the first logical slots)."""
        cut = max(0, f * self.layout.page_size - self._n_meta)
        return ("PG", f, tokens[:cut].tobytes())

    def _full_key(self, tokens: np.ndarray):
        return ("FULL", len(tokens), tokens.tobytes())

    def _slot_pages(self, s: int) -> List[int]:
        scratch = self.layout.scratch
        return [int(p) for p in self._tables[s] if p != scratch]

    def _release_slot_pages(self, s: int):
        for p in self._slot_pages(s):
            self._pages.decref(p)
        self._tables[s] = self.layout.scratch

    # -- admission ----------------------------------------------------------

    def _can_admit(self, req: Request) -> bool:
        if req.rid in self._plans:      # re-asked within the same tick
            return True
        tokens = np.asarray(req.tokens, np.int32)
        need = self.layout.pages_for(len(tokens) + self._n_meta)
        shared: List[int] = []
        if self._prefix is not None:
            self.stats["prefix_lookups"] += 1
            self._prefix.lookups += 1
            hit = self._prefix.get(self._full_key(tokens))
            if hit is None:
                f_max = (len(tokens) + self._n_meta) \
                    // self.layout.page_size
                for f in range(f_max, 0, -1):
                    hit = self._prefix.get(self._page_key(tokens, f))
                    if hit is not None:
                        break
            if hit is not None:
                shared = list(hit)
                self.stats["prefix_hits"] += 1
                self._prefix.hits += 1
                self.tracer.instant("prefix_cache.hit", rid=req.rid,
                                    shared_pages=len(shared))
            else:
                self.tracer.instant("prefix_cache.miss", rid=req.rid)
        n_fresh = need - len(shared)
        if self._free_capacity() < n_fresh + self.paged.reserve_pages:
            return False                # backpressure
        for p in shared:
            self._pages.incref(p)
        fresh = [self._page_or_none() for _ in range(n_fresh)]
        assert all(p is not None for p in fresh)  # capacity checked above
        self._plans[req.rid] = (shared + fresh, len(shared))
        return True

    def _alloc_pool(self, batch):
        return paged_pool_like(self.params, batch, self.cfg, self.ctx,
                               max_seq=self.max_seq,
                               n_slots=self.n_slots + 1,
                               layout=self.layout)

    def _prefill_group(self, group: List[Request], bucket: int, now: float):
        W, lengths, slots, seeds, batch = self._stage_group(group, bucket)
        nb, scratch = self.layout.n_blocks, self.layout.scratch
        wt = np.full((W, nb), scratch, np.int32)
        for i, req in enumerate(group):
            pages, h = self._plans.pop(req.rid)
            s = int(slots[i])
            self._tables[s] = scratch
            self._tables[s, :len(pages)] = pages
            wt[i, h:len(pages)] = pages[h:]     # shared blocks stay scratch
        pool, tok0, lp0 = self._prefill(
            self.params, batch, jnp.asarray(lengths), jnp.asarray(wt),
            jnp.asarray(slots), self.pool, self.rng, jnp.asarray(seeds))
        self.pool = pool
        tok0, lp0 = jax.device_get((tok0, lp0))   # the tick's one sync
        if self._prefix is not None:
            for i, req in enumerate(group):
                tokens = np.asarray(req.tokens, np.int32)
                need = self.layout.pages_for(len(tokens) + self._n_meta)
                pages = [int(p) for p in self._tables[int(slots[i])][:need]]
                f_max = (len(tokens) + self._n_meta) \
                    // self.layout.page_size
                for f in range(1, f_max + 1):
                    self._prefix.put(self._page_key(tokens, f), pages[:f])
                self._prefix.put(self._full_key(tokens), pages)
        self._finish_admission(group, bucket, W, lengths, slots, seeds,
                               tok0, lp0, now)
        self._note_pages()

    def _try_swap_in(self, req: Request) -> bool:
        st = self._swapped[req.rid]
        need = self.layout.pages_for(st.pos + self._n_meta)
        if self._free_capacity() < need + self.paged.reserve_pages:
            return False
        with self.tracer.span("sched.swap_in", rid=req.rid, pages=need):
            return self._swap_in(req, st, need)

    def _swap_in(self, req: Request, st: _SwapState, need: int) -> bool:
        pages = [self._page_or_none() for _ in range(need)]
        assert all(p is not None for p in pages)
        s = self._free.popleft()
        self._tables[s] = self.layout.scratch
        self._tables[s, :need] = pages
        self.pool = self._restore(self.pool, st.saved,
                                  jnp.asarray(self._tables[s]),
                                  jnp.asarray(s))
        self._queue.popleft()
        del self._swapped[req.rid]
        self._slot_rid[s] = req.rid
        self._slot_uses[s] += 1
        self._tok[s] = st.tok
        self._pos[s] = st.pos
        self._ngen[s] = st.ngen
        self._active[s] = True
        self._done[s] = False
        self._budget[s] = st.budget
        self._length[s] = st.length
        self._score[s] = st.score
        self._seed[s] = st.seed
        self.stats["swap_ins"] += 1
        self._note_pages()
        return True

    def _admit(self, now: float):
        # preempted requests sit at the queue front (swap state, no plan);
        # drain them before normal bucketed admission
        while (self._free and self._queue
               and self._queue[0].rid in self._swapped):
            if not self._try_swap_in(self._queue[0]):
                return                  # backpressure: keep FIFO order
        super()._admit(now)

    # -- decode: COW + page growth + preemption -----------------------------

    def _victim(self) -> Optional[int]:
        """Youngest-admitted live slot (LIFO preemption: the youngest
        request has done the least work and re-enters the queue FIRST of
        the preempted, preserving FIFO completion order overall)."""
        live = [s for s in range(self.n_slots)
                if self._slot_rid[s] is not None
                and self._active[s] and not self._done[s]]
        if not live:
            return None
        return max(live, key=lambda s: (
            self._meta[self._slot_rid[s]]["admitted_at"], s))

    def _preempt(self, s: int):
        rid = self._slot_rid[s]
        with self.tracer.span("sched.preempt.swap_out", rid=rid, slot=s):
            self._swap_out(s, rid)

    def _swap_out(self, s: int, rid: int):
        # the victim's own write-block may have been COW'd earlier in this
        # _ensure_writable pass — its table already points at the copy
        # destination, so the pending copy must execute before the gather
        # reads it
        self._flush_cow()
        # exceptional second host sync of the tick: swap-out must land in
        # host memory before its pages are recycled by the next alloc
        saved = jax.device_get(self._gather(
            self.pool, jnp.asarray(self._tables[s]), jnp.asarray(s)))
        self._swapped[rid] = _SwapState(
            tok=int(self._tok[s]), pos=int(self._pos[s]),
            ngen=int(self._ngen[s]), budget=int(self._budget[s]),
            length=int(self._length[s]), score=float(self._score[s]),
            seed=int(self._seed[s]), saved=saved)
        self._queue.appendleft(self._reqs[rid])
        self._release_slot_pages(s)
        self._slot_rid[s] = None
        self._active[s] = False
        self._done[s] = False
        self._free.append(s)
        self.stats["preemptions"] += 1

    def _grow_page(self, s: int) -> Optional[int]:
        """A page for slot ``s``'s next write — evicting prefix-cache
        entries, then preempting victims until one frees up. None means
        ``s`` itself was preempted (it was the last live slot)."""
        while True:
            p = self._page_or_none()
            if p is not None:
                return p
            v = self._victim()
            if v is None:
                raise PagePoolExhausted(
                    "no free pages and no live slot to preempt")
            self._preempt(v)
            if v == s:
                return None

    def _flush_cow(self):
        """Execute queued COW page copies, padded with scratch->scratch
        no-op pairs to a power-of-two width (bounded executable count).
        Gather-before-scatter semantics of `.at[dst].set(leaf[src])` make
        one batched call safe even when a freed source page was already
        handed back out as another pair's destination."""
        src, dst = self._cow_src, self._cow_dst
        if not src:
            return
        self._cow_src, self._cow_dst = [], []
        scratch = self.layout.scratch
        w = 1
        while w < len(src):
            w *= 2
        with self.tracer.span("sched.cow_flush", pairs=len(src), width=w):
            src = src + [scratch] * (w - len(src))
            dst = dst + [scratch] * (w - len(dst))
            self.pool = self._copy(self.pool,
                                   jnp.asarray(np.asarray(src, np.int32)),
                                   jnp.asarray(np.asarray(dst, np.int32)))

    def _ensure_writable(self, alive):
        """Pre-decode pass: every live slot's write-block must point at a
        private real page before the step writes K/V there."""
        ps, scratch = self.layout.page_size, self.layout.scratch
        self._cow_src, self._cow_dst = [], []
        for s in range(self.n_slots):
            if not alive[s] or self._slot_rid[s] is None:
                continue                # rid None: preempted this pass
            wb = (int(self._pos[s]) + self._n_meta) // ps
            page = int(self._tables[s, wb])
            if page == scratch:
                p = self._grow_page(s)
                if p is None:
                    continue
                self._tables[s, wb] = p
            elif self._pages.ref(page) > 1:
                p = self._grow_page(s)
                if p is None:
                    continue
                # the preemption inside _grow_page may itself have COW'd +
                # flushed; re-read the current page (still shared: only
                # OTHER slots' pages were released)
                self._cow_src.append(int(self._tables[s, wb]))
                self._cow_dst.append(p)
                self._pages.decref(int(self._tables[s, wb]))
                self._tables[s, wb] = p
                self.stats["cow_copies"] += 1
        self._flush_cow()
        self._note_pages()

    def _decode_call(self, alive):
        self._ensure_writable(alive)
        alive = self._active & ~self._done      # preemption may shrink it
        pool, nxt, lp = self._decode_fn(
            self.params, self.pool, jnp.asarray(self._tables),
            jnp.asarray(self._tok), jnp.asarray(self._pos),
            jnp.asarray(alive), self.rng, jnp.asarray(self._seed),
            jnp.asarray(self._ngen))
        self.pool = pool
        return nxt, lp

    def _retire(self, now: float) -> List[RequestResult]:
        retiring = [s for s in range(self.n_slots)
                    if self._slot_rid[s] is not None and self._done[s]]
        out = super()._retire(now)
        for s in retiring:
            self._release_slot_pages(s)
        return out


# ---------------------------------------------------------------------------
# static-batching baseline (table8's comparison point)
# ---------------------------------------------------------------------------

def static_batch_serve(params, cfg: ModelConfig, gen: GenerateConfig,
                       requests: Sequence[Request], *, batch_size: int,
                       ctx=None, rng: Optional[jax.Array] = None,
                       max_seq: Optional[int] = None
                       ) -> Tuple[Dict[int, np.ndarray], float]:
    """Pre-refactor serving shape: group requests FIFO into same-length
    batches of ``batch_size`` and run the one-shot engine batch by batch.
    Every batch runs until its slowest member finishes (max_new or all-
    EOS); per-request outputs are truncated to the request's budget —
    greedy decoding is prefix-stable, so truncation equals a shorter run.
    Returns ({rid: tokens}, wall_seconds)."""
    from repro.serve.engine import generate
    groups: Dict[Tuple[int, ...], List[Request]] = {}
    order: List[List[Request]] = []
    for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
        key = (len(r.tokens),)
        g = groups.get(key)
        if g is None or len(g) >= batch_size:
            g = groups[key] = []
            order.append(g)
        g.append(r)
    g2 = dataclasses.replace(gen, max_seq=max_seq or gen.max_seq)
    out: Dict[int, np.ndarray] = {}
    t0 = monotonic()
    for g in order:
        batch = {"tokens": jnp.asarray(np.stack([r.tokens for r in g]))}
        for k in g[0].extras:
            batch[k] = jnp.asarray(np.stack([r.extras[k] for r in g]))
        # engine instances cache on (cfg, gen, ctx) + batch shape, so a
        # warmed-up trace replay pays zero compiles
        res = jax.block_until_ready(generate(params, batch, cfg, g2, ctx,
                                             rng))
        toks = np.asarray(res.tokens)
        lens = np.asarray(res.lengths)
        for i, r in enumerate(g):
            n = min(int(lens[i]), r.max_new or gen.max_new)
            out[r.rid] = toks[i, :n]
    return out, monotonic() - t0
