"""Compiled decoding engine — the ONE generation loop in the repo.

Every caller that turns a model + prompt into tokens goes through here:
``launch/serve.py``, ``launch/train.py`` (BLEU eval), the examples, and
the BLEU benchmarks. The contract (DESIGN.md §7):

  * prefill writes cache positions ``[0, P)`` for a P-token prompt and
    returns the logits of position ``P-1`` — i.e. the distribution of the
    FIRST generated token. The first ``decode_step`` therefore runs at
    absolute index ``P`` (feeding the token that lives at position P),
    never at 0 — feeding index 0 after prefill overwrites the BOS slot
    and shifts every RoPE phase/mask one position early.
  * the per-token loop is a ``jax.lax.while_loop`` inside ONE jitted
    function (no per-token Python dispatch), with per-sequence EOS
    early-exit masking: once a sequence emits ``eos_id`` it produces only
    ``pad_id`` and stops counting toward ``lengths``; the loop exits as
    soon as every sequence is done.
  * hybrid archs add their meta-token offset INSIDE ``decode_step``
    (models/model.py), so callers always pass logical token positions.
  * ``ParallelContext`` and the MoE backend registry (DESIGN.md §6) are
    threaded through unchanged — decoding with ``--backend pallas``
    uses the same engine. Routed decode moves its dispatch/combine bytes
    over the expert exchange (DESIGN.md §10), and the scheduler's
    ``tick_log`` feeds the ``launch/serve.py --trace`` comm accounting.

Since the continuous-batching refactor (DESIGN.md §9) the engine is built
from SLOT-ADDRESSED STEPWISE PRIMITIVES:

  * ``init_slot_pool``      -- persistent fixed-``max_seq`` decode cache
                               whose rows are request slots; EVERY leaf
                               carries a slot axis (the ring-buffer
                               ``pos`` leaf, batchless in the one-shot
                               cache, is batched per slot here).
  * ``prefill_into_slots``  -- prefill a group of new requests (right-
                               padded to a shared bucket length) and
                               scatter their caches into assigned slot
                               rows; returns each row's logits at its
                               TRUE last prompt token.
  * ``decode_pool_step``    -- one batched ``decode_step`` over all S
                               slots with PER-SLOT positions, so requests
                               at different depths advance together. The
                               compile count of a serving process is
                               O(prefill buckets + 1), not O(shapes).
  * ``_select_rows``        -- per-row token selection whose sampling
                               stream is keyed by (request seed, token
                               index): a request's draws are invariant to
                               its slot/batch placement.

The one-shot ``_generate_sample`` is a thin driver over these primitives
(every prompt row is a slot, all admitted at step 0); beam search
(``GenerateConfig.beam_width > 1``) keeps its bespoke loop that tiles the
batch to ``B*W`` rows and re-gathers every cache leaf along its batch
axis at each step (DESIGN.md §7 beam bookkeeping).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.moe import ParallelContext
from repro.models.model import decode_step, init_cache, prefill

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class GenerateConfig:
    """Decoding options (hashable — baked into the jitted engine).

    temperature <= 0 means greedy argmax; ``top_k`` restricts sampling to
    the k highest logits (0 = full vocab; ``top_k=1`` == greedy).
    ``beam_width > 1`` switches to deterministic beam search (sampling
    options are ignored). ``eos_id < 0`` disables EOS early exit.
    ``local_routing`` reuses Gating Dropout's LOCAL routing path at decode
    time (DESIGN.md §9): MoE tokens route within the local expert group
    only, so the sharded backend's decode executable carries no
    all-to-all — the same communication the paper drops in training.
    ``flash_decode`` routes every full-cache attention read through the
    ``kernels.flash_decode`` online-softmax Pallas kernel (per-row
    positions supported; ring/window caches keep the reference path).
    """
    max_new: int = 32
    temperature: float = 0.0
    top_k: int = 0
    beam_width: int = 1
    eos_id: int = 2
    pad_id: int = 0
    length_penalty: float = 1.0     # beam score norm: score / len**penalty
    early_exit: bool = True         # stop the loop when every row is done
    local_routing: bool = False     # Gate-Drop local path at decode (§9)
    flash_decode: bool = False      # decode attention via Pallas kernel
    max_seq: int = 0                # cache length override (0 = prompt_len
                                    # + max_new). Set to a slot pool's
                                    # max_seq to compare one-shot outputs
                                    # with pool decode BITWISE: equal cache
                                    # lengths keep every masked-softmax
                                    # reduction shape identical.

    def __post_init__(self):
        assert self.max_new >= 1
        assert self.beam_width >= 1


class GenerateResult(NamedTuple):
    tokens: jax.Array    # (B, max_new) int32; pad_id after EOS
    lengths: jax.Array   # (B,) int32 generated tokens incl. the EOS itself
    scores: jax.Array    # (B,) f32 sum log p of emitted tokens (beam:
                         #  length-penalized best-hypothesis score)
    steps: jax.Array     # () int32 decode-loop iterations actually run


# ---------------------------------------------------------------------------
# cache batch-axis discovery (beam gathers + slot-pool scatters reuse it)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _cache_batch_axes(cfg: ModelConfig):
    """Per-leaf batch-axis index for the decode cache (-1 = no batch dim).

    Found structurally: build the cache at two batch sizes under
    ``eval_shape`` and diff the leaf shapes — robust to every cache family
    (full KV, ring buffer + its batchless ``pos`` leaf, MLA latents, SSM
    state, cross KV). Memoized per ``ModelConfig``: the two eval_shape
    cache builds used to re-run on every beam-engine trace."""
    a = jax.eval_shape(lambda: init_cache(cfg, 2, 16))
    b = jax.eval_shape(lambda: init_cache(cfg, 5, 16))

    def axis(sa, sb):
        diff = [i for i, (x, y) in enumerate(zip(sa.shape, sb.shape))
                if x != y]
        assert len(diff) <= 1, (sa.shape, sb.shape)
        return diff[0] if diff else -1

    return jax.tree.map(axis, a, b)


def _gather_cache(caches, axes, idx):
    """Reorder every batched cache leaf by ``idx`` along its batch axis."""
    return jax.tree.map(
        lambda leaf, ax: leaf if ax < 0 else jnp.take(leaf, idx, axis=ax),
        caches, axes)


# ---------------------------------------------------------------------------
# slot pool (continuous batching, DESIGN.md §9)
# ---------------------------------------------------------------------------

def init_slot_pool(cfg: ModelConfig, n_slots: int, max_seq: int, dtype=None):
    """Persistent slot-addressed decode cache for ``n_slots`` requests.

    Identical to ``init_cache`` except that leaves WITHOUT a batch axis
    (the ring-buffer ``pos`` leaf) gain a per-slot axis right after the
    scan-repeats axis: in a pool, every slot sits at its own depth, so
    even "shared" position bookkeeping must be per-slot. ``decode_step``
    detects the batched leaf (ndim) and takes the per-row path.

    NOTE cross-attending families size their cross-KV leaf by the
    conditioning input actually fed to ``prefill`` (which may differ from
    ``cfg.encdec.encoder_seq``); serve a trace through
    ``slot_pool_like``/``ContinuousScheduler``, which allocate the pool
    from the prefill-produced cache structure instead."""
    caches = init_cache(cfg, n_slots, max_seq, dtype)
    axes = _cache_batch_axes(cfg)

    def batch_leaf(leaf, ax):
        if ax >= 0:
            return leaf
        return jnp.broadcast_to(jnp.expand_dims(leaf, 1),
                                leaf.shape[:1] + (n_slots,) + leaf.shape[1:])

    return jax.tree.map(batch_leaf, caches, axes)


def _alloc_pool_like(fresh_shapes, axes, n_slots: int):
    """Zero slot pool whose leaves mirror a per-request cache tree with
    the batch axis resized to ``n_slots`` (unbatched leaves gain the slot
    axis after the scan-repeats axis)."""
    def alloc(fr, ax):
        if ax >= 0:
            shape = fr.shape[:ax] + (n_slots,) + fr.shape[ax + 1:]
        else:
            shape = fr.shape[:1] + (n_slots,) + fr.shape[1:]
        return jnp.zeros(shape, fr.dtype)

    return jax.tree.map(alloc, fresh_shapes, axes)


def slot_pool_like(params, batch, cfg: ModelConfig,
                   ctx: Optional[ParallelContext] = None, *,
                   max_seq: int, n_slots: int):
    """Slot pool shaped like the caches ``prefill`` will ACTUALLY produce
    for ``batch`` — cross-KV length follows the batch's conditioning
    inputs, not config defaults. Shape-only (``eval_shape``): no compute."""
    _, fresh = jax.eval_shape(
        lambda p, b: prefill(p, b, cfg, ctx, max_seq=max_seq),
        params, batch)
    return _alloc_pool_like(fresh, _cache_batch_axes(cfg), n_slots)


def _scatter_slots(pool, fresh, axes, slots):
    """Write per-request cache rows ``fresh`` into pool rows ``slots``.

    ``axes`` is the request-cache batch-axis tree (`_cache_batch_axes`);
    leaves without a batch axis live at pool axis 1 (after the scan
    repeats axis) and are broadcast to every written slot."""
    n = slots.shape[0]

    def put(pl, fr, ax):
        pool_ax = ax if ax >= 0 else 1
        if ax >= 0:
            rows = jnp.moveaxis(fr, ax, 0).astype(pl.dtype)
        else:
            rows = jnp.broadcast_to(fr.astype(pl.dtype), (n,) + fr.shape)
        pl2 = jnp.moveaxis(pl, pool_ax, 0).at[slots].set(rows)
        return jnp.moveaxis(pl2, 0, pool_ax)

    return jax.tree.map(put, pool, fresh, axes)


def prefill_into_slots(params, batch: Dict[str, Any], lengths: jax.Array,
                       slots: jax.Array, pool, cfg: ModelConfig,
                       ctx: Optional[ParallelContext] = None, *,
                       max_seq: int, rng: Optional[jax.Array] = None):
    """Prefill a group of new requests into assigned pool slots.

    ``batch["tokens"]`` is (n, bucket_len) right-padded; ``lengths`` (n,)
    are the true prompt lengths. Causal masking keeps each row's real
    positions independent of its padding, and later ``decode_pool_step``
    writes overwrite pad cache rows exactly as they would become visible,
    so padded prefill matches exact-length prefill for attention-cache
    families (SSM state integrates the pads — the scheduler prefills
    those archs at exact length instead; DESIGN.md §9).

    Returns ``(logits (n, V) at each row's last real token, pool')``."""
    logits, fresh = prefill(params, batch, cfg, ctx, max_seq=max_seq,
                            rng=rng, last_index=lengths - 1)
    pool = _scatter_slots(pool, fresh, _cache_batch_axes(cfg), slots)
    return logits[:, 0], pool


def decode_pool_step(params, pool, tok: jax.Array, pos: jax.Array,
                     alive: jax.Array, cfg: ModelConfig,
                     ctx: Optional[ParallelContext] = None, *,
                     local_routing: bool = False,
                     flash_decode: bool = False):
    """One batched ``decode_step`` over ALL pool slots at per-slot
    positions. ``tok``/``pos``/``alive`` are (S,): the token each slot
    feeds, its absolute position, and whether the slot is live (active
    and not done — dead slots still step, but ``token_valid`` keeps them
    out of expert-capacity competition and their outputs are ignored).

    Returns ``(logits (S, V), pool')``. This is the ONE decode executable
    of a serving process — compile count O(prefill buckets + 1)."""
    lg, pool = decode_step(params, pool, tok[:, None], pos, cfg, ctx,
                           local_routing=local_routing, token_valid=alive,
                           flash_decode=flash_decode)
    return lg[:, 0], pool


# ---------------------------------------------------------------------------
# token selection
# ---------------------------------------------------------------------------

def _select_rows(gen: GenerateConfig, logits: jax.Array, rng: jax.Array,
                 seeds: jax.Array, steps: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """(N, V) f32 logits -> (token (N,), log p of token (N,)).

    Sampling draws per-row keys ``fold(fold(rng, seeds[r]), steps[r])``
    (request seed x its own token index), so a request's sample stream
    does not depend on which slot it occupies or who shares the batch —
    the property continuous batching needs for placement-invariant
    outputs."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    if gen.temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1)
    else:
        scaled = logits / gen.temperature
        if gen.top_k > 0:
            kth = jax.lax.top_k(scaled, gen.top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, NEG, scaled)
        keys = jax.vmap(lambda s, i: jax.random.fold_in(
            jax.random.fold_in(rng, s), i))(seeds, steps)
        tok = jax.vmap(jax.random.categorical)(keys, scaled)
    tok = tok.astype(jnp.int32)
    return tok, jnp.take_along_axis(logp, tok[:, None], axis=1)[:, 0]


def _advance(gen: GenerateConfig, nxt, lp, done, length, score):
    """Post-selection bookkeeping shared by the one-shot driver and the
    scheduler step: finished rows emit pad, stop counting, and set done
    on EOS."""
    nxt = jnp.where(done, gen.pad_id, nxt)
    score = score + jnp.where(done, 0.0, lp)
    length = length + jnp.where(done, 0, 1).astype(jnp.int32)
    if gen.eos_id >= 0:
        done = done | (nxt == gen.eos_id)
    return nxt, done, length, score


# ---------------------------------------------------------------------------
# greedy / sampling loop — thin driver over the slot-pool primitives
# ---------------------------------------------------------------------------

def _check_cache_budget(max_seq: int, prompt_len: int, max_new: int):
    """The decode cache is pinned at ``max_seq`` positions; a request that
    could outgrow it would silently wrap ``.at[index]`` writes back into
    live positions and corrupt every later read. Fail loudly instead."""
    if max_seq < prompt_len + max_new:
        raise ValueError(
            f"prompt_len ({prompt_len}) + max_new ({max_new}) = "
            f"{prompt_len + max_new} exceeds the pinned cache length "
            f"max_seq={max_seq}; raise GenerateConfig.max_seq (or lower "
            f"max_new) — the cache cannot grow after allocation")


def _generate_sample(params, batch, rng, cfg: ModelConfig,
                     gen: GenerateConfig, ctx) -> GenerateResult:
    prompt_len = batch["tokens"].shape[1]
    b = batch["tokens"].shape[0]
    max_seq = gen.max_seq or (prompt_len + gen.max_new)
    _check_cache_budget(max_seq, prompt_len, gen.max_new)
    seeds = jnp.arange(b, dtype=jnp.int32)
    lengths = jnp.full((b,), prompt_len, jnp.int32)
    # every prompt row is a slot, all admitted at step 0: the pool is
    # allocated from the prefill-produced cache structure and filled by
    # an identity scatter
    logits, fresh = prefill(params, batch, cfg, ctx, max_seq=max_seq,
                            last_index=lengths - 1)
    axes = _cache_batch_axes(cfg)
    pool = _scatter_slots(_alloc_pool_like(fresh, axes, b), fresh, axes,
                          jnp.arange(b))
    tok0, lp0 = _select_rows(gen, logits[:, 0].astype(jnp.float32), rng,
                             seeds, jnp.zeros((b,), jnp.int32))
    done0 = (tok0 == gen.eos_id) if gen.eos_id >= 0 else jnp.zeros(b, bool)
    buf = jnp.full((b, gen.max_new), gen.pad_id, jnp.int32).at[:, 0].set(tok0)
    pos0 = jnp.full((b,), prompt_len, jnp.int32)   # tok0 lives at position P

    def cond(state):
        i, _, _, _, _, done, _, _ = state
        keep = i < gen.max_new
        if gen.early_exit:
            keep = keep & ~jnp.all(done)
        return keep

    def body(state):
        i, cur, pos, pool, buf, done, length, score = state
        lg, pool = decode_pool_step(params, pool, cur, pos, ~done, cfg, ctx,
                                    local_routing=gen.local_routing,
                                    flash_decode=gen.flash_decode)
        nxt, lp = _select_rows(gen, lg.astype(jnp.float32), rng, seeds,
                               jnp.full((b,), i, jnp.int32))
        nxt, done, length, score = _advance(gen, nxt, lp, done, length,
                                            score)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
        return i + 1, nxt, pos + 1, pool, buf, done, length, score

    state = (jnp.asarray(1, jnp.int32), tok0, pos0, pool, buf, done0,
             jnp.ones((b,), jnp.int32), lp0)
    i, _, _, _, buf, _, length, score = jax.lax.while_loop(cond, body, state)
    return GenerateResult(tokens=buf, lengths=length, scores=score,
                          steps=i - 1)


# ---------------------------------------------------------------------------
# beam search loop
# ---------------------------------------------------------------------------

def _generate_beam(params, batch, rng, cfg: ModelConfig,
                   gen: GenerateConfig, ctx) -> GenerateResult:
    del rng  # beam search is deterministic
    W = gen.beam_width
    b = batch["tokens"].shape[0]
    prompt_len = batch["tokens"].shape[1]
    axes = _cache_batch_axes(cfg)
    # Tile every prompt to W identical rows; prefill at B*W so every cache
    # leaf already carries the beam-expanded batch axis.
    max_seq = gen.max_seq or (prompt_len + gen.max_new)
    _check_cache_budget(max_seq, prompt_len, gen.max_new)
    tiled = {k: jnp.repeat(v, W, axis=0) for k, v in batch.items()}
    logits0, caches = prefill(params, tiled, cfg, ctx, max_seq=max_seq)
    logp0 = jax.nn.log_softmax(logits0[:, 0].astype(jnp.float32), -1)
    # all W rows of a prompt are identical after prefill: seed the beams
    # with the top-W distinct first tokens of row 0
    scores, tok0 = jax.lax.top_k(logp0.reshape(b, W, -1)[:, 0], W)  # (B, W)
    tok0 = tok0.astype(jnp.int32)
    done = (tok0 == gen.eos_id) if gen.eos_id >= 0 \
        else jnp.zeros((b, W), bool)
    buf = jnp.full((b, W, gen.max_new), gen.pad_id,
                   jnp.int32).at[:, :, 0].set(tok0)
    V = logp0.shape[-1]
    # frozen-beam continuation: a finished beam re-proposes only pad_id at
    # log p = 0, so its score is carried unchanged through top-k
    frozen = jnp.full((V,), NEG, jnp.float32).at[gen.pad_id].set(0.0)

    def cond(state):
        i, _, _, _, _, done, _ = state
        keep = i < gen.max_new
        if gen.early_exit:
            keep = keep & ~jnp.all(done)
        return keep

    def body(state):
        i, cur, caches, buf, scores, done, length = state
        lg, caches = decode_step(params, caches, cur.reshape(b * W, 1),
                                 prompt_len + i - 1, cfg, ctx,
                                 local_routing=gen.local_routing,
                                 flash_decode=gen.flash_decode)
        logp = jax.nn.log_softmax(lg[:, 0].astype(jnp.float32), -1)
        logp = logp.reshape(b, W, V)
        logp = jnp.where(done[..., None], frozen[None, None], logp)
        total = (scores[..., None] + logp).reshape(b, W * V)
        scores, flat = jax.lax.top_k(total, W)                    # (B, W)
        parent = (flat // V).astype(jnp.int32)
        tok = (flat % V).astype(jnp.int32)
        # re-gather all beam state by parent
        buf = jnp.take_along_axis(buf, parent[..., None], axis=1)
        done = jnp.take_along_axis(done, parent, axis=1)
        length = jnp.take_along_axis(length, parent, axis=1)
        flat_parent = (jnp.arange(b, dtype=jnp.int32)[:, None] * W
                       + parent).reshape(-1)
        caches = _gather_cache(caches, axes, flat_parent)
        length = length + jnp.where(done, 0, 1).astype(jnp.int32)
        if gen.eos_id >= 0:
            done = done | (tok == gen.eos_id)
        buf = jax.lax.dynamic_update_slice(buf, tok[..., None], (0, 0, i))
        return i + 1, tok, caches, buf, scores, done, length

    state = (jnp.asarray(1, jnp.int32), tok0, caches, buf, scores, done,
             jnp.ones((b, W), jnp.int32))
    i, _, _, buf, scores, _, length = jax.lax.while_loop(cond, body, state)
    norm = scores / jnp.maximum(length, 1).astype(
        jnp.float32) ** gen.length_penalty
    best = jnp.argmax(norm, axis=1)
    take = lambda x: jnp.take_along_axis(
        x, best.reshape((b,) + (1,) * (x.ndim - 1)), axis=1).squeeze(1)
    return GenerateResult(tokens=take(buf), lengths=take(length),
                          scores=take(norm), steps=i - 1)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _check_local_routing(cfg: ModelConfig, gen: GenerateConfig):
    if (gen.local_routing and cfg.moe is not None
            and cfg.moe.gating_dropout.mode == "gate_expert_drop"):
        raise ValueError(
            "local_routing reuses the Gate-Drop LOCAL path; with "
            "gating_dropout.mode='gate_expert_drop' the dropped branch "
            "skips the MoE layer entirely — not a serving mode")


def make_generate_fn(cfg: ModelConfig, gen: GenerateConfig,
                     ctx: Optional[ParallelContext] = None):
    """Build the single-jit generation function.

    Returns ``fn(params, batch, rng=None) -> GenerateResult`` where
    ``batch`` holds the prompt ``tokens (B, P)`` plus the family's
    conditioning inputs (``enc_tokens`` / ``frames`` / ``img_embeds``).
    Prefill, the whole decode loop, and EOS bookkeeping compile into ONE
    executable per (batch shape, config)."""
    _check_local_routing(cfg, gen)
    inner = _generate_beam if gen.beam_width > 1 else _generate_sample

    @jax.jit
    def fn(params, batch, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return inner(params, batch, rng, cfg, gen, ctx)

    return fn


@functools.lru_cache(maxsize=32)
def _cached_fn(cfg: ModelConfig, gen: GenerateConfig,
               ctx: Optional[ParallelContext]):
    return make_generate_fn(cfg, gen, ctx)


def generate(params, batch: Dict[str, Any], cfg: ModelConfig,
             gen: GenerateConfig = GenerateConfig(),
             ctx: Optional[ParallelContext] = None,
             rng: Optional[jax.Array] = None) -> GenerateResult:
    """Convenience wrapper: jitted engines are cached on (cfg, gen, ctx)."""
    return _cached_fn(cfg, gen, ctx)(params, batch, rng)
