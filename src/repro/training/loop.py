"""Scan-fused Trainer — THE training loop of the repo (DESIGN.md §8).

Seed-era training dispatched one jitted step per Python-loop iteration:
a per-step executable dispatch, a host-side consensus draw (three eager
jax calls), per-step batch synthesis, and a host sync per log interval.
The paper's claim is *wall-clock* convergence, so the host loop must not
be part of the measurement. The Trainer executes training as CHUNKS
instead: ``lax.scan`` over K steps inside a single jit, per-step metrics
accumulated on-device and fetched once per chunk, fed by the
double-buffered background prefetcher (``repro.data.prefetch``) over
vectorized batch synthesis (``repro.data.pipeline``).

Host-device syncs — ``run()`` keeps one chunk in flight, so nothing the
host waits on stands between the enqueue of one chunk and the next:

  * one explicit ``jax.device_get`` of the metrics per chunk, lagged by
    one chunk: chunk i's metrics are fetched after chunk i+1 is enqueued,
    so the next batch's put and the record work overlap the step in
    flight;
  * drained (nothing left in flight) before ``eval_fn`` runs, and at the
    end of ``run()``, so the state ``eval_fn`` sees, the checkpoint and
    ``state`` / ``history`` after ``run()`` are exactly the unpipelined
    loop's;
  * host_cond's consensus bits for the whole of ``run()`` drawn in one
    batched call before the first chunk is enqueued;
  * a record's ``time_s`` / ``tok_s`` are stamped when its chunk's
    metrics are fetched.

Decision semantics — both bitwise-faithful to K legacy per-step calls
(asserted in ``tests/test_trainer.py``):

  traced_cond — the chunk precomputes the K consensus bits IN-GRAPH as a
      length-K vector: ``vmap`` of ``drop_decision`` over
      (seed, absolute_step) — the identical fold the per-step path uses,
      so the bits agree bitwise and stay traced (``lax.cond`` per step).
  host_cond  — the host draws the bits (``drop_decisions_host``, once per
      ``run()``), splits each chunk into MAXIMAL SAME-DECISION RUNS, and
      dispatches each run
      to a scan-fused executable whose decision is a static argument:
      the dropped run executable still contains zero all-to-alls
      (``tests/test_trainer.py::test_dropped_chunk_executable_has_no_alltoall``).
      jit caches one executable per (decision, run-length), so a chunk of
      K steps costs at most 2K compiles over a whole run.

Eval points are forced onto chunk ends by the schedule, so ``eval_fn``
always sees exactly the post-step params the legacy loop evaluated.
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.gating_dropout import drop_decision, drop_decisions_host
from repro.core.moe import ParallelContext
from repro.data.prefetch import Prefetcher, stack_batches
from repro.models import init_model
from repro.obs.frame import load_imbalance
from repro.obs.trace import Tracer, get_tracer, monotonic
from repro.training.steps import init_train_state, make_train_step

# tokens a step consumes: decoder tokens AND (for enc-dec tasks) encoder
# tokens — counting only "tokens" undercounted MT throughput ~2x
TOKEN_KEYS = ("tokens", "enc_tokens")


def train_state_sharding(cfg: ModelConfig, tc: TrainConfig,
                         ctx: Optional[ParallelContext] = None) -> Any:
    """The train state's NamedShardings on ``ctx``'s mesh by the rules of
    DESIGN.md §4 (``parallel/sharding.py::state_specs``): expert weights
    and their Adam moments split over the expert axis. None off a mesh."""
    if ctx is None or not ctx.active:
        return None
    from repro.parallel.sharding import state_specs, to_shardings
    shape = jax.eval_shape(lambda: init_train_state(
        init_model(jax.random.PRNGKey(tc.seed), cfg), tc))
    return to_shardings(ctx.mesh, state_specs(cfg, ctx, shape))


def make_chunk_step(cfg: ModelConfig, tc: TrainConfig,
                    ctx: Optional[ParallelContext] = None,
                    *, jit: bool = True) -> Callable:
    """Returns chunk_fn(state, batches, decision) -> (state, metrics).

    ``batches``: pytree with a leading K axis (``stack_batches``).
    ``metrics``: the per-step metric dict stacked to (K, ...) — fetched by
    the caller once per chunk, never per step.
    ``decision``:
      None -> traced_cond: the K consensus bits are computed in-graph
              from (seed, absolute_step) as a length-K traced vector.
      bool -> host_cond run: baked in as a static argument; jit caches
              one executable per (decision, K). With the decision static
              the dropped executable contains no all-to-all at all.
    On a mesh the returned state is pinned to ``train_state_sharding`` so
    it keeps its layout chunk to chunk.
    """
    step_fn = make_train_step(cfg, tc, ctx, jit=False)
    gd = cfg.moe.gating_dropout if cfg.moe is not None else None
    use_gd = gd is not None and gd.enabled

    def chunk_fn(state, batches, decision):
        k = jax.tree.leaves(batches)[0].shape[0]
        if decision is None and use_gd:
            steps = state["step"] + jnp.arange(k, dtype=state["step"].dtype)
            decs = jax.vmap(lambda s: drop_decision(gd, tc.seed, s))(steps)

            def body(s, xs):
                b, d = xs
                return step_fn(s, b, d)

            return jax.lax.scan(body, state, (batches, decs))

        dec = bool(decision) if decision is not None else False

        def body(s, b):
            return step_fn(s, b, dec)

        return jax.lax.scan(body, state, batches)

    state_sharding = train_state_sharding(cfg, tc, ctx)
    if state_sharding is not None:
        unpinned = chunk_fn

        def chunk_fn(state, batches, decision):
            state, ms = unpinned(state, batches, decision)
            return jax.lax.with_sharding_constraint(state, state_sharding), ms

    if jit:
        return jax.jit(chunk_fn, static_argnums=(2,), donate_argnums=(0,))
    return chunk_fn


def split_runs(bits: np.ndarray, lo: int) -> List[Tuple[int, int, bool]]:
    """Maximal runs of equal consensus bits, ``bits[j]`` being step
    ``lo + j``'s: [(start, stop, decision), ...] covering the span in
    order."""
    decs = [bool(d) for d in bits]
    runs, i = [], 0
    while i < len(decs):
        j = i
        while j < len(decs) and decs[j] == decs[i]:
            j += 1
        runs.append((lo + i, lo + j, decs[i]))
        i = j
    return runs


def same_decision_runs(gd, seed: int, lo: int, hi: int
                       ) -> List[Tuple[int, int, bool]]:
    """Split [lo, hi) into maximal runs of equal host-drawn consensus bits.
    The bits come from ONE batched draw (``drop_decisions_host``), not
    per-step eager dispatches."""
    return split_runs(drop_decisions_host(gd, seed, lo, hi), lo)


class Trainer:
    """Owns a training run: state, data, chunked execution, checkpointing,
    eval, logging, and resume.

    Parameters
    ----------
    batch_fn : step -> dict of numpy arrays (one per-step batch). Called
        from the prefetch thread; must be pure host work (no jax).
    chunk : steps fused per dispatch (K). Eval points shorten individual
        chunks so they land on chunk ends.
    strategy : "traced_cond" | "host_cond" | None (None = follow
        ``cfg.moe.gating_dropout.strategy``; DESIGN.md §5).
    eval_fn : (state, step) -> dict merged into that step's history
        record; runs at chunk ends only, with no chunk in flight.
    log : callable for per-record lines (default: print as JSON); None
        disables printing (history is still returned).
    """

    def __init__(self, cfg: ModelConfig, tc: TrainConfig,
                 batch_fn: Callable[[int], Dict[str, np.ndarray]], *,
                 ctx: Optional[ParallelContext] = None,
                 params: Any = None,
                 chunk: int = 8,
                 strategy: Optional[str] = None,
                 ckpt_dir: Optional[str] = None,
                 ckpt_meta: Optional[Dict] = None,
                 eval_every: int = 0,
                 eval_fn: Optional[Callable[[Any, int], Dict]] = None,
                 log_every: int = 20,
                 prefetch: bool = True,
                 prefetch_depth: int = 2,
                 log: Optional[Callable[[str], None]] = print,
                 tracer: Optional[Tracer] = None):
        self.cfg, self.tc, self.ctx = cfg, tc, ctx
        self.batch_fn = batch_fn
        self.chunk = max(int(chunk), 1)
        gd = cfg.moe.gating_dropout if cfg.moe is not None else None
        self.gd = gd if (gd is not None and gd.enabled) else None
        self.strategy = strategy or (self.gd.strategy if self.gd
                                     else "traced_cond")
        assert self.strategy in ("traced_cond", "host_cond"), self.strategy
        self.ckpt_dir, self.ckpt_meta = ckpt_dir, ckpt_meta
        self.eval_every, self.eval_fn = eval_every, eval_fn
        self.log_every, self.log = log_every, log
        self.prefetch, self.prefetch_depth = prefetch, prefetch_depth
        self.state_sharding = train_state_sharding(cfg, tc, ctx)
        self.batch_sharding = None
        if self.state_sharding is not None:
            # on a mesh the state is built in place, never gathered on one
            # device; batches split over the data axes
            init = lambda p: init_train_state(  # noqa: E731
                init_model(jax.random.PRNGKey(tc.seed), cfg)
                if p is None else p, tc)
            self.state = jax.jit(init, out_shardings=self.state_sharding)(
                params)
            self.batch_sharding = jax.sharding.NamedSharding(
                ctx.mesh, jax.sharding.PartitionSpec(None, ctx.dp_axes))
        else:
            if params is None:
                params = init_model(jax.random.PRNGKey(tc.seed), cfg)
            self.state = init_train_state(params, tc)
        self.start_step = 0
        self.history: List[Dict] = []
        self.chunk_fn = make_chunk_step(cfg, tc, ctx)
        # span tracer (DESIGN.md §15): default is the process-global one
        # (disabled unless a launcher enabled it via --trace-out)
        self.tracer = tracer if tracer is not None else get_tracer()

    # ---- resume -----------------------------------------------------------
    def restore(self) -> int:
        """Restore params + opt + step from ``ckpt_dir`` and continue at
        the ABSOLUTE step: both the data stream (batch_fn) and the
        consensus PRNG (seed, step) pick up exactly where the
        checkpointed run left off (DESIGN.md §2). On a mesh each leaf
        lands where the current state's does."""
        assert self.ckpt_dir, "restore() needs ckpt_dir"
        assert latest_step(self.ckpt_dir) is not None, \
            f"restore: no checkpoint in {self.ckpt_dir}"
        self.state, meta = restore_checkpoint(self.ckpt_dir, self.state)
        self.start_step = int(meta["step"])
        return self.start_step

    # ---- schedule ---------------------------------------------------------
    def _eval_steps(self) -> set:
        if not self.eval_every or self.eval_fn is None:
            return set()
        return ({i for i in range(self.tc.steps) if i % self.eval_every == 0}
                | {self.tc.steps - 1})

    def _record_steps(self) -> set:
        rec = {self.tc.steps - 1} | self._eval_steps()
        if self.log_every:
            rec |= {i for i in range(self.tc.steps)
                    if i % self.log_every == 0}
        return rec

    def schedule(self) -> List[Tuple[int, int]]:
        """Chunk spans [s, e) covering [start_step, steps): at most
        ``chunk`` long, cut so every eval step is a chunk's LAST step."""
        ends = sorted({i + 1 for i in self._eval_steps()} | {self.tc.steps})
        spans, s = [], self.start_step
        for e in ends:
            while s < e:
                spans.append((s, min(s + self.chunk, e)))
                s = spans[-1][1]
        return spans

    # ---- run --------------------------------------------------------------
    def _dispatch(self, runs: List[Tuple[int, int, Optional[bool]]],
                  stacked: Dict, in_flight: int) -> List[Dict]:
        """Enqueue one chunk, given as its same-decision runs (one run with
        decision None under traced_cond), and return its per-run metrics
        unfetched: ``_fetch`` is the chunk's one host-device sync. Per
        run, ``chunk.put`` then ``chunk.execute`` (the enqueue), whose arg
        ``in_flight`` is the number of chunks enqueued and not yet fetched
        when this one was."""
        s, e = runs[0][0], runs[-1][1]
        tr = self.tracer
        # jit-retrace detection: _cache_size is host-only introspection,
        # read only when tracing (it never syncs, but stays off the
        # steady path regardless)
        n0 = tr.enabled and self.chunk_fn._cache_size()

        def put(v):   # (K, B, ...) batch: B split over the data axes
            if self.batch_sharding is None:
                return jnp.asarray(v)
            return jax.device_put(v, self.batch_sharding)

        parts = []
        for rs, re, dec in runs:
            with tr.span("chunk.put", start=rs, stop=re):
                sub = {k: put(v[rs - s:re - s]) for k, v in stacked.items()}
            with tr.span("chunk.execute", start=rs, stop=re,
                         decision="traced" if dec is None else bool(dec),
                         in_flight=in_flight):
                self.state, m = self.chunk_fn(self.state, sub, dec)
            parts.append(m)
        if tr.enabled and self.chunk_fn._cache_size() > n0:
            tr.instant("jit_retrace", fn="chunk_fn", start=s, stop=e)
        return parts

    def _fetch(self, s: int, e: int, parts: List[Dict]
               ) -> Dict[str, np.ndarray]:
        """The chunk [s, e)'s per-step metrics stacked over the span, by an
        explicit ``jax.device_get`` (the analysis.hostsync guard flags
        implicit pulls inside steady-state ticks); waits for the chunk."""
        with self.tracer.span("chunk.fetch", start=s, stop=e):
            parts = jax.device_get(parts)
            return {k: np.concatenate([p[k] for p in parts])
                    for k in parts[0]}

    def _record(self, s: int, e: int, ms: Dict[str, np.ndarray], el: float,
                tok_s: float, rec_steps: set, eval_steps: set) -> None:
        """History records of the chunk's recorded steps, from its fetched
        metrics; ``el`` is the time from the run's start to the fetch."""
        for i in range(s, e):
            if i not in rec_steps:
                continue
            j = i - s
            # tok_s pairs the CHUNK-complete token count with the
            # chunk's fetch timestamp (el) — same convention as
            # time_s; pro-rating tokens to step i against el would
            # understate mid-chunk throughput
            rec = {"step": i, "loss": float(ms["loss"][j]),
                   "acc": float(ms["acc"][j]),
                   "lr": float(ms["lr"][j]),
                   "tok_s": tok_s,
                   "time_s": el}
            if "balance" in ms:
                rec["balance"] = float(ms["balance"][j])
            if "comm_wire_bytes" in ms:
                # per-device wire bytes this step's forward moved
                # (in-graph exchange telemetry, DESIGN.md §10)
                rec["comm_wire_bytes"] = float(ms["comm_wire_bytes"][j])
                rec["comm_a2a_calls"] = float(ms["comm_a2a_calls"][j])
            if "router_entropy" in ms:
                # MetricsFrame router-health fields (§15): per-
                # step entropy / load imbalance / consensus bit,
                # already on host from the chunk fetch
                rec["router_entropy"] = float(ms["router_entropy"][j])
                rec["load_imbalance"] = float(load_imbalance(
                    np.asarray(ms["expert_load"][j])))
                rec["gate_dropped"] = float(ms["gate_dropped"][j])
            if i in eval_steps:   # schedule guarantees i == e - 1
                with self.tracer.span("eval", step=i):
                    rec.update(self.eval_fn(self.state, i))
            self.history.append(rec)
            if self.log is not None:
                self.log(json.dumps(rec))

    def run(self) -> Tuple[Any, List[Dict]]:
        """Train [start_step, tc.steps), keeping one chunk in flight (module
        docstring); returns ``(state, history)`` with every chunk fetched
        and recorded."""
        tc, tr = self.tc, self.tracer
        spans = self.schedule()
        fetch = lambda span: stack_batches(self.batch_fn, *span)  # noqa: E731
        it = (Prefetcher(fetch, spans, self.prefetch_depth,
                         tracer=self.tracer)
              if self.prefetch else map(fetch, spans))
        rec_steps, eval_steps = self._record_steps(), self._eval_steps()
        tokens_done, t0 = 0, monotonic()
        lo, bits = self.start_step, None
        if self.strategy == "host_cond" and spans:
            with tr.span("chunk.decide", start=lo, stop=tc.steps,
                         steps=tc.steps - lo):
                bits = drop_decisions_host(self.gd, tc.seed, lo, tc.steps)

        def retire(s, e, parts, tokens):
            nonlocal tokens_done
            ms = self._fetch(s, e, parts)
            with tr.span("chunk.record", start=s, stop=e):
                el = monotonic() - t0
                tokens_done += tokens
                self._record(s, e, ms, el, tokens_done / max(el, 1e-9),
                             rec_steps, eval_steps)

        pending = None        # the chunk enqueued and not yet fetched
        try:
            for span, stacked in zip(spans, it):
                s, e = span
                tok_per_step = sum(int(stacked[k][0].size)
                                   for k in TOKEN_KEYS if k in stacked)
                # ``positions`` counts padded positions, not tokens
                with tr.span("train_chunk", start=s, stop=e,
                             strategy=self.strategy,
                             positions=(e - s) * tok_per_step):
                    runs = ([(s, e, None)] if bits is None
                            else split_runs(bits[s - lo:e - lo], s))
                    parts = self._dispatch(runs, stacked,
                                           in_flight=int(pending is not None))
                    if pending is not None:
                        retire(*pending)
                    pending = (s, e, parts, (e - s) * tok_per_step)
                    # drain: eval_fn, the checkpoint and the caller read
                    # the state after this chunk
                    if e - 1 in eval_steps or e == tc.steps:
                        retire(*pending)
                        pending = None
        finally:
            if isinstance(it, Prefetcher):
                it.close()
        if self.ckpt_dir:
            save_checkpoint(self.ckpt_dir, tc.steps, self.state,
                            {"arch": self.cfg.arch_id,
                             **(self.ckpt_meta or {})})
        return self.state, self.history
