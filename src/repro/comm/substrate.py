"""Pluggable collective-communication substrate (DESIGN.md §10).

The MoE dispatch/combine all-to-all — the one collective Gating Dropout
exists to avoid paying — used to be two inline ``jax.lax.all_to_all``
calls buried in ``core/moe.py::_routed_shard``: unmeasured, uncompressed,
and blind to network topology. This module makes the wire a first-class,
swappable component behind a registry (mirroring the §6 execution-backend
registry), selected by ``MoEConfig.comm`` (`CommConfig`):

  dense                   -- single-hop all-to-all over the full ep group
                             (bit-for-bit the historical inline path).
  hierarchical            -- two-hop exchange over a factored
                             ep = ep_inner x ep_outer group: an intra-tier
                             all-to-all (consecutive ranks = one machine/
                             node) followed by an inter-tier all-to-all
                             over strided groups. Delivers the SAME
                             permutation as dense (bitwise — asserted),
                             while turning each device's (ep - ep_inner)
                             cross-tier messages into (ep_outer - 1)
                             aggregated ones, the Shazeer-style
                             hierarchical dispatch.
  compressed              -- dense topology, payload quantized to int8 or
                             fp8 (e4m3) with one f32 scale per
                             (expert, capacity-slot) row; dequantized on
                             arrival. A custom VJP makes the backward wire
                             compressed too (straight-through estimator
                             through the rounding), so the routed path
                             still trains — Switch-Transformer-style
                             selective precision on the routed tensors.
  hierarchical_compressed -- both composed: quantize once, carry the int8
                             payload + scales through both hops,
                             dequantize once.
  overlapped[...]         -- any of the above, micro-chunked (DESIGN.md
                             §14): the (E, cap, d) payload splits into
                             ``CommConfig.n_chunks`` pieces along the
                             capacity axis and the chunks run through a
                             double-buffered software pipeline — the
                             dispatch of chunk i+1 and the combine of
                             chunk i-1 are issued around the expert FFN
                             of chunk i, so XLA's scheduler can hide
                             them behind the compute. The pipeline is an
                             UNROLLED Python loop (static chunk count):
                             the compiled HLO contains n_eff distinct
                             per-chunk collectives per hop, keeping the
                             telemetry == parsed-HLO invariant countable.
                             Each chunk is the same permutation its base
                             substrate performs and the expert FFN is
                             per-capacity-row independent, so the result
                             stays BITWISE-equal to the base substrate
                             (pinned in tests, like hierarchical).

Every substrate exposes the transport in two execution modes so the whole
matrix is testable on CPU:

  * ``dispatch``/``combine``   -- real collectives inside shard_map; the
                                  two-hop substrate factors a single mesh
                                  axis via ``axis_index_groups``
                                  (`parallel/sharding.py::ep_tier_groups`)
                                  or, for the ep_on_model layout, uses the
                                  (model, data) mesh axes AS the tiers.
  * ``vdispatch``/``vcombine`` -- the oracle backend's virtual emulation:
                                  identical permutation algebra as pure
                                  transposes over the stacked
                                  (ep, E, cap, d) tensor, factored axes
                                  and all.

Telemetry: ``Transport.telemetry`` returns the layer's exact all-to-all
call count / payload bytes / per-device wire bytes as in-graph constants,
computed from the SAME analytic model (`comm/cost.py`) that
``tests/test_comm.py`` validates against compiled-HLO collective counts —
counters, model, and executable cannot drift apart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm import cost as C
from repro.comm.cost import ep_tier_groups, factored_ep
from repro.configs.base import CommConfig

__all__ = ["CommConfig", "CommEnv", "OverlappedTransport", "Transport",
           "available_substrates", "comm_zero", "get_substrate",
           "make_transport", "register_substrate"]


@dataclasses.dataclass(frozen=True)
class CommEnv:
    """Where a transport runs: the collective axis and its factorization.

    ``axis`` is the shard_map axis name (or tuple, for the ep_on_model
    layout) the exchange runs over; ``None`` selects the virtual
    (oracle) emulation. When the ep factorization is GIVEN by two mesh
    axes (ep_on_model: intra = model, inter = data), ``inner_axis``/
    ``outer_axis``/``inner_size`` name them and override
    ``CommConfig.ep_inner``."""
    ep: int
    axis: Any = None
    inner_axis: Optional[str] = None
    outer_axis: Optional[str] = None
    inner_size: int = 0


# ---------------------------------------------------------------------------
# quantization (compressed substrates)
# ---------------------------------------------------------------------------

_FP8_MAX = 448.0          # float8_e4m3fn finite max
_INT8_MAX = 127.0


def quantize(x: jax.Array, mode: str) -> Tuple[jax.Array, jax.Array]:
    """Per-row (last-dim) scaled quantization: (..., d) -> int8/fp8
    payload + one f32 scale per row. Zero rows get scale 1 so dequant is
    exact there."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    if mode == "fp8":
        scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
        q = jnp.clip(xf / scale, -_FP8_MAX, _FP8_MAX).astype(
            jnp.float8_e4m3fn)
    else:
        scale = jnp.where(amax > 0, amax / _INT8_MAX, 1.0)
        q = jnp.round(jnp.clip(xf / scale, -_INT8_MAX, _INT8_MAX)
                      ).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _compressed_pair(fwd_perm: Callable, bwd_perm: Callable, mode: str
                     ) -> Callable:
    """Wire transform ``dequant(perm(quant(x)))`` with a custom VJP:
    the cotangent takes the REVERSE permutation, also quantized (the
    backward all-to-all is compressed too), straight-through w.r.t. the
    rounding. ``perm`` must be a pure permutation (its linear transpose
    is its inverse), which every substrate's hop sequence is."""

    def _wire(perm, x):
        q, s = quantize(x, mode)
        return dequantize(perm(q), perm(s), x.dtype)

    @jax.custom_vjp
    def f(x):
        return _wire(fwd_perm, x)

    f.defvjp(lambda x: (_wire(fwd_perm, x), None),
             lambda _, g: (_wire(bwd_perm, g),))
    return f


# ---------------------------------------------------------------------------
# topologies (permutation algebra; payload-dtype agnostic)
# ---------------------------------------------------------------------------

def _a2a(buf, axis, split, concat, groups=None):
    return jax.lax.all_to_all(buf, axis, split_axis=split,
                              concat_axis=concat,
                              axis_index_groups=groups, tiled=True)


class _FlatTopo:
    """Single-hop all-to-all over the whole ep group."""

    def __init__(self, env: CommEnv):
        self.env = env
        self.tiers = None

    def dispatch(self, buf):                       # (E, cap, ...) per shard
        return _a2a(buf, self.env.axis, 0, 1)      # -> (E/ep, ep*cap, ...)

    def combine(self, buf):
        return _a2a(buf, self.env.axis, 1, 0)

    def vdispatch(self, bufs):                     # (ep, E, cap, ...)
        ep, E = bufs.shape[:2]
        b = bufs.reshape((ep, ep, E // ep) + bufs.shape[2:])
        b = jnp.moveaxis(b, 0, 2)                  # (dst, e_loc, src, cap,..)
        return b.reshape((E, ep * bufs.shape[2]) + bufs.shape[3:])

    def vcombine(self, buf):                       # (E, ep*cap, ...)
        ep = self.env.ep
        E = buf.shape[0]
        cap = buf.shape[1] // ep
        b = buf.reshape((ep, E // ep, ep, cap) + buf.shape[2:])
        b = jnp.moveaxis(b, 2, 0)                  # (src, dst, e_loc, cap,..)
        return b.reshape((ep, E, cap) + buf.shape[2:])


class _FactoredTopo:
    """Two-hop exchange over ep = ep_inner x ep_outer (rank = o*gi + i).

    Hop algebra (X[src][dst] = the chunk src holds for dst; src=(o,i)):
      intra:  A[(o,i)][o',i'] = X[(o,i')][o',i]     (tiers exchange inside)
      inter:  B[(o,i)][o2,i2] = A[(o2,i)][o ,i2]    (strided across tiers)
      =>      B[(o,i)][o2,i2] = X[(o2,i2)][o ,i ]   — exactly the flat a2a.
    Both hops are self-inverse tiled exchanges, so ``combine`` replays
    them in reverse order around the inverse reshape."""

    def __init__(self, comm: CommConfig, env: CommEnv):
        self.env = env
        if env.inner_axis is not None:             # tiers ARE mesh axes
            gi = env.inner_size
            go = env.ep // gi
            self.hops = ((env.inner_axis, None, 1),
                         (env.outer_axis, None, 0))
        else:                                      # factor one mesh axis
            gi, go = factored_ep(env.ep, comm.ep_inner)
            intra, inter = ep_tier_groups(env.ep, comm.ep_inner)
            self.hops = ((env.axis, [list(g) for g in intra], 1),
                         (env.axis, [list(g) for g in inter], 0))
        self.tiers = (gi, go)

    def _exchange(self, b, reverse=False):
        for axis, groups, ax in (reversed(self.hops) if reverse
                                 else self.hops):
            b = _a2a(b, axis, ax, ax, groups)
        return b

    def dispatch(self, buf):                       # (E, cap, ...) per shard
        E, cap = buf.shape[:2]
        gi, go = self.tiers
        e_loc = E // self.env.ep
        b = buf.reshape((go, gi, e_loc) + buf.shape[1:])
        b = self._exchange(b)                      # axes -> (o_src, i_src,..)
        b = jnp.moveaxis(b, 2, 0)                  # (e_loc, o_src, i_src,..)
        return b.reshape((e_loc, self.env.ep * cap) + buf.shape[2:])

    def combine(self, buf):                        # (e_loc, ep*cap, ...)
        gi, go = self.tiers
        e_loc = buf.shape[0]
        cap = buf.shape[1] // self.env.ep
        b = buf.reshape((e_loc, go, gi, cap) + buf.shape[2:])
        b = jnp.moveaxis(b, 0, 2)                  # (go, gi, e_loc, cap, ..)
        b = self._exchange(b, reverse=True)
        return b.reshape((self.env.ep * e_loc, cap) + buf.shape[2:])

    # virtual emulation: the same two hops as stacked-axis swaps
    def vdispatch(self, bufs):                     # (ep, E, cap, ...)
        gi, go = self.tiers
        ep, E, cap = bufs.shape[:3]
        e_loc = E // ep
        b = bufs.reshape((go, gi, go, gi, e_loc) + bufs.shape[2:])
        b = b.swapaxes(1, 3)                       # intra hop
        b = b.swapaxes(0, 2)                       # inter hop
        # axes now (o_dst, i_dst, o_src, i_src, e_loc, cap, ...)
        b = jnp.moveaxis(b, 4, 2)                  # (o_d, i_d, e_loc, o_s,..)
        return b.reshape((E, ep * cap) + bufs.shape[3:])

    def vcombine(self, buf):                       # (E, ep*cap, ...)
        gi, go = self.tiers
        ep = self.env.ep
        E = buf.shape[0]
        cap = buf.shape[1] // ep
        b = buf.reshape((go, gi, E // ep, go, gi, cap) + buf.shape[2:])
        b = jnp.moveaxis(b, 2, 4)                  # (o_d, i_d, o_s, i_s, e,..)
        b = b.swapaxes(0, 2)                       # undo inter hop
        b = b.swapaxes(1, 3)                       # undo intra hop
        return b.reshape((ep, E, cap) + buf.shape[2:])


# ---------------------------------------------------------------------------
# transport = topology (+ optional compression) + telemetry
# ---------------------------------------------------------------------------

class Transport:
    """One routed layer's wire. ``dispatch``: per-shard (E, cap, d) ->
    (E/ep, ep*cap, d); ``combine`` is the exact inverse; both run under
    the ``exchange`` name scope. ``vdispatch``/``vcombine`` are the
    oracle's stacked-tensor emulation (ep, E, cap, d) <-> (E, ep*cap, d).
    ``roundtrip`` applies only the payload wire transform (quant->dequant,
    no movement) — the ep=1 kernel pipeline uses it so backend choice
    never changes numerics."""

    def __init__(self, comm: CommConfig, env: CommEnv, topo):
        self.comm, self.env, self.topo = comm, env, topo
        if comm.compressed:
            q = comm.quant
            self.dispatch = _compressed_pair(topo.dispatch, topo.combine, q)
            self.combine = _compressed_pair(topo.combine, topo.dispatch, q)
            self.vdispatch = _compressed_pair(topo.vdispatch,
                                              topo.vcombine, q)
            self.vcombine = _compressed_pair(topo.vcombine,
                                             topo.vdispatch, q)
            self.roundtrip = _compressed_pair(lambda x: x, lambda x: x, q)
        else:
            self.dispatch = topo.dispatch
            self.combine = topo.combine
            self.vdispatch = topo.vdispatch
            self.vcombine = topo.vcombine
            self.roundtrip = lambda x: x
        # every op of a real exchange, the collectives and the packing,
        # quantizing and reshaping around them, forward and backward,
        # carries ``exchange`` in its ``op_name``, which a profiler trace
        # reads back
        self.dispatch = jax.named_scope("exchange")(self.dispatch)
        self.combine = jax.named_scope("exchange")(self.combine)

    def pipelined(self, buf: jax.Array, fn: Callable) -> jax.Array:
        """The §14 transport contract: run ``dispatch -> fn -> combine``
        as ONE transaction, with the grouped-FFN body handed in as a
        per-chunk callable so overlapped substrates can interleave its
        chunks with the wire. Non-overlapped substrates are the trivial
        one-chunk case."""
        return self.combine(fn(self.dispatch(buf)))

    def vpipelined(self, bufs: jax.Array, fn: Callable) -> jax.Array:
        """``pipelined`` over the oracle's stacked (ep, E, cap, d)
        virtual emulation."""
        return self.vcombine(fn(self.vdispatch(bufs)))

    def telemetry(self, n_experts: int, cap: int, d_model: int,
                  itemsize: int) -> Dict[str, jax.Array]:
        """In-graph (constant) telemetry for one layer's transport —
        the §10/§14 counters, straight from the analytic model.
        ``comm_exposed_bytes``/``comm_hidden_bytes`` split the wire into
        the structurally non-overlappable fraction (the pipeline's edge
        chunks) and the remainder a chunked schedule can hide behind
        expert compute; non-overlapped substrates expose everything."""
        c = C.transport_cost(self.comm, ep=self.env.ep, n_experts=n_experts,
                             cap=cap, d_model=d_model, itemsize=itemsize,
                             tiers=self.topo.tiers)
        return {"comm_a2a_calls": jnp.asarray(c["calls"], jnp.float32),
                "comm_bytes": jnp.asarray(c["bytes"], jnp.float32),
                "comm_wire_bytes": jnp.asarray(c["wire_bytes"],
                                               jnp.float32),
                "comm_exposed_bytes": jnp.asarray(c["exposed_wire_bytes"],
                                                  jnp.float32),
                "comm_hidden_bytes": jnp.asarray(c["hidden_wire_bytes"],
                                                 jnp.float32)}


class OverlappedTransport(Transport):
    """Micro-chunked pipeline over any base topology (DESIGN.md §14).

    ``pipelined`` splits the (E, cap, d) payload into
    ``effective_chunks(cap, n_chunks)`` slices along the capacity axis
    and issues, per chunk i: dispatch(i+1) — prefetching the next
    chunk's wire — then FFN(i), then combine(i) (which overlaps
    FFN(i+1) on the next iteration). The loop is UNROLLED over the
    static chunk count so each per-chunk collective is a distinct HLO op
    (a lax.scan body would be counted once by the HLO walker, breaking
    the telemetry == parsed-HLO invariant) and so XLA's latency-hiding
    scheduler is free to slide the collectives behind the grouped
    matmuls.

    Bitwise equality with the base substrate holds because (a) each
    chunk undergoes the exact permutation the base substrate applies —
    dense's dispatched axis-1 layout is (src_rank, cap), so chunk i is
    precisely the [:, i*cc:(i+1)*cc] capacity slice of every source's
    block — (b) the expert FFN is independent per capacity row, and
    (c) the compressed pair's quantization scales are per (expert, slot)
    row, so quantizing chunkwise equals quantizing once then slicing."""

    def _n_chunks(self, cap: int) -> int:
        return C.effective_chunks(cap, self.comm.n_chunks)

    def pipelined(self, buf: jax.Array, fn: Callable) -> jax.Array:
        n = self._n_chunks(buf.shape[1])
        if n == 1:
            return self.combine(fn(self.dispatch(buf)))
        cc = buf.shape[1] // n
        chunks = [buf[:, i * cc:(i + 1) * cc] for i in range(n)]
        disp = [None] * n
        outs = [None] * n
        disp[0] = self.dispatch(chunks[0])
        for i in range(n):
            if i + 1 < n:                  # prefetch next chunk's wire
                disp[i + 1] = self.dispatch(chunks[i + 1])
            y = fn(disp[i])
            outs[i] = self.combine(y)      # overlaps FFN(i+1)
        return jnp.concatenate(outs, axis=1)

    def vpipelined(self, bufs: jax.Array, fn: Callable) -> jax.Array:
        n = self._n_chunks(bufs.shape[2])
        if n == 1:
            return self.vcombine(fn(self.vdispatch(bufs)))
        cc = bufs.shape[2] // n
        chunks = [bufs[:, :, i * cc:(i + 1) * cc] for i in range(n)]
        disp = [None] * n
        outs = [None] * n
        disp[0] = self.vdispatch(chunks[0])
        for i in range(n):
            if i + 1 < n:
                disp[i + 1] = self.vdispatch(chunks[i + 1])
            y = fn(disp[i])
            outs[i] = self.vcombine(y)
        return jnp.concatenate(outs, axis=2)


def comm_zero() -> Dict[str, jax.Array]:
    """Telemetry of a step that moves nothing (Gate-Drop local /
    expert-drop / dense-FFN layers)."""
    return {"comm_a2a_calls": jnp.zeros((), jnp.float32),
            "comm_bytes": jnp.zeros((), jnp.float32),
            "comm_wire_bytes": jnp.zeros((), jnp.float32),
            "comm_exposed_bytes": jnp.zeros((), jnp.float32),
            "comm_hidden_bytes": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# registry (mirrors core/backend.py)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[CommConfig, CommEnv], Transport]] = {}


def register_substrate(name: str):
    """Decorator: add a communication substrate under ``name``."""
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def available_substrates() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_substrate(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown comm substrate {name!r}; available: "
            f"{', '.join(available_substrates())}") from None


def make_transport(comm: CommConfig, env: CommEnv) -> Transport:
    """Build the configured substrate's transport for one layer trace."""
    return get_substrate(comm.substrate)(comm, env)


@register_substrate("dense")
def _dense(comm: CommConfig, env: CommEnv) -> Transport:
    return Transport(comm, env, _FlatTopo(env))


@register_substrate("hierarchical")
def _hierarchical(comm: CommConfig, env: CommEnv) -> Transport:
    return Transport(comm, env, _FactoredTopo(comm, env))


@register_substrate("compressed")
def _compressed(comm: CommConfig, env: CommEnv) -> Transport:
    return Transport(comm, env, _FlatTopo(env))


@register_substrate("hierarchical_compressed")
def _hierarchical_compressed(comm: CommConfig, env: CommEnv) -> Transport:
    return Transport(comm, env, _FactoredTopo(comm, env))


@register_substrate("overlapped")
def _overlapped(comm: CommConfig, env: CommEnv) -> Transport:
    return OverlappedTransport(comm, env, _FlatTopo(env))


@register_substrate("overlapped_hierarchical")
def _overlapped_hierarchical(comm: CommConfig, env: CommEnv) -> Transport:
    return OverlappedTransport(comm, env, _FactoredTopo(comm, env))


@register_substrate("overlapped_compressed")
def _overlapped_compressed(comm: CommConfig, env: CommEnv) -> Transport:
    return OverlappedTransport(comm, env, _FlatTopo(env))


@register_substrate("overlapped_hierarchical_compressed")
def _overlapped_hierarchical_compressed(comm: CommConfig,
                                        env: CommEnv) -> Transport:
    return OverlappedTransport(comm, env, _FactoredTopo(comm, env))
