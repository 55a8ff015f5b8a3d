"""MoE execution-backend registry (DESIGN.md §6).

One MoE layer, three interchangeable execution paths, selected by
``MoEConfig.backend``:

  oracle  -- pure-jnp vmap over virtual shards; ground truth. Runs anywhere.
  sharded -- shard_map over a real mesh; dispatch/combine are explicit
             ``jax.lax.all_to_all`` collectives (the path Gating Dropout
             skips on dropped steps).
  pallas  -- the compiled kernel pipeline: routing tables built ONCE per
             step (kernels.ops.routing_tables), then scalar-prefetch
             dispatch gather -> grouped-matmul expert FFN -> weighted
             combine gather. interpret mode auto-detected per platform.
  auto    -- (default) sharded when a real mesh is active, oracle otherwise
             — the historical moe_apply behavior.

New fast paths register here (``@register_backend("name")``) and become
selectable via config + one parity test, instead of forking moe.py. All
backends share the router (core/router.py) and the Gating Dropout branch
selection (core/moe.py), so parity is by construction up to kernel
numerics.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core import router as R

Params = Dict[str, Any]
# fn(params, x, cfg, ctx, *, rng, decision, is_training, token_ids)
BackendFn = Callable[..., Tuple[jax.Array, Dict]]

_REGISTRY: Dict[str, BackendFn] = {}


def register_backend(name: str) -> Callable[[BackendFn], BackendFn]:
    """Decorator: add an execution backend under ``name``."""
    def deco(fn: BackendFn) -> BackendFn:
        _REGISTRY[name] = fn
        return fn
    return deco


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> BackendFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown MoE backend {name!r}; available: "
                       f"{', '.join(available_backends())}") from None


def resolve_backend(moe: MoEConfig, ctx) -> str:
    """'auto' -> 'sharded' iff a real (multi-device) mesh is active."""
    name = moe.backend
    if name == "auto":
        return "sharded" if (ctx is not None and ctx.active) else "oracle"
    return name


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

@register_backend("oracle")
def oracle_backend(params: Params, x: jax.Array, cfg: ModelConfig, ctx=None,
                   **kw) -> Tuple[jax.Array, Dict]:
    """Pure-jnp ground truth (single virtual shard)."""
    from repro.core.moe import moe_oracle
    return moe_oracle(params, x, cfg, ep=1, **kw)


@register_backend("sharded")
def sharded_backend(params: Params, x: jax.Array, cfg: ModelConfig, ctx=None,
                    **kw) -> Tuple[jax.Array, Dict]:
    """shard_map + explicit all_to_all. Without a mesh in ctx, a 1-axis
    mesh over every visible device is built (so the path is exercised —
    and parity-testable — even on a single-device host)."""
    from repro.core.moe import ParallelContext, moe_sharded
    from repro.launch.mesh import make_mesh
    if ctx is None or ctx.mesh is None:
        ctx = ParallelContext(mesh=make_mesh((jax.device_count(),), ("data",)))
    return moe_sharded(params, x, cfg, ctx, **kw)


@register_backend("pallas")
def pallas_backend(params: Params, x: jax.Array, cfg: ModelConfig, ctx=None,
                   *, rng: Optional[jax.Array] = None, decision=None,
                   is_training: bool = True,
                   token_ids: Optional[jax.Array] = None,
                   token_valid: Optional[jax.Array] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, Dict]:
    """Kernel pipeline: route -> routing_tables (once) -> dispatch gather
    -> grouped-FFN -> combine gather. Numerically matches the oracle at
    ep=1. With a real mesh active, expert parallelism composes by running
    the sharded path with the per-shard kernel pipeline enabled — the
    all-to-alls and per-shard routing noise stay exactly as `sharded`."""
    import contextlib
    from repro.core.moe import (_local_adjust, _local_aux, _routed_aux,
                                _select_branch, _shard_rng, _zero_aux)
    from repro.kernels import ops as K
    from repro.kernels.platform import force_interpret

    if ctx is not None and ctx.active:
        pin = (force_interpret(interpret) if interpret is not None
               else contextlib.nullcontext())
        with K.use_kernels(True), pin:
            return sharded_backend(params, x, cfg, ctx, rng=rng,
                                   decision=decision, is_training=is_training,
                                   token_ids=token_ids,
                                   token_valid=token_valid)

    from repro.comm import CommEnv, make_transport

    moe = cfg.moe
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    E = moe.n_experts
    tok = None if token_ids is None else token_ids.reshape(-1)
    tv = (None if token_valid is None
          else jnp.broadcast_to(token_valid.reshape(-1, 1), (T, moe.top_k)))
    wr = params["router"]["w"]
    experts = params["experts"]
    cf = moe.capacity_factor if is_training else moe.eval_capacity_factor
    cap = min(R.capacity(T, E, moe.top_k, cf), T)
    # ep=1 wire: no movement, but the payload transform (compressed
    # substrates' quant->dequant) still applies so backend choice never
    # changes numerics vs the oracle (DESIGN.md §10)
    transport = make_transport(moe.comm, CommEnv(ep=1))

    def _pipeline(info: R.DispatchInfo) -> jax.Array:
        tables = K.routing_tables(info, E, cap)    # built once, reused twice
        buf = K.dispatch(xf, tables.slot_token, tables.slot_valid,
                         interpret=interpret).reshape(E, cap, -1)
        buf = transport.roundtrip(buf)
        w_in = experts["w_in"]
        out = K.expert_ffn_op(buf.astype(w_in.dtype), w_in,
                              experts.get("w_gate"), experts["w_out"],
                              cfg.act, interpret=interpret)
        out = transport.roundtrip(out.astype(xf.dtype))
        return K.combine(out.reshape(E * cap, -1), tables.token_slot,
                         info.topk_w, info.keep, interpret=interpret)

    def routed():
        rr = R.route(wr, xf, moe, rng=_shard_rng(rng, 0),
                     is_training=is_training, token_ids=tok)
        info = R.dispatch_info(rr, E, cap, valid=tv)
        comm_t = transport.telemetry(E, cap, shape[-1],
                                     jnp.dtype(xf.dtype).itemsize)
        return _pipeline(info), _routed_aux(rr, info, moe, comm=comm_t)

    def local():
        # ep=1 Gate-Drop: the "local group" is all E experts (mirrors
        # moe.py::_local_shard with my_shard=0, e_loc=E), kernel-executed.
        rr = R.route(wr, xf, moe, rng=_shard_rng(rng, 0),
                     is_training=is_training, token_ids=tok,
                     expert_lo=0, n_local=E)
        rr, valid = _local_adjust(rr, moe, 0, E)
        if tv is not None:
            valid = valid & tv
        info = R.dispatch_info(rr, E, cap, valid=valid)
        return _pipeline(info), _local_aux(rr, info, moe, T)

    def expert_drop():
        return jnp.zeros((T, shape[-1]), x.dtype), _zero_aux(E)

    y, aux = _select_branch(moe, decision, routed, local, expert_drop)
    return y.reshape(shape), aux


@register_backend("pallas_fused")
def pallas_fused_backend(params: Params, x: jax.Array, cfg: ModelConfig,
                         ctx=None, *, rng: Optional[jax.Array] = None,
                         decision=None, is_training: bool = True,
                         token_ids: Optional[jax.Array] = None,
                         token_valid: Optional[jax.Array] = None,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jax.Array, Dict]:
    """ONE-launch megakernel pipeline (kernels.moe_megakernel, DESIGN.md
    §11): route -> fused gather + expert FFN + weighted scatter. Same
    router, same Gating Dropout branches, same aux as `pallas` — the
    (E, C, d) buffer and its two extra HBM roundtrips are gone, and the
    five per-layer kernel launches collapse to one.

    Raises under a real mesh (expert parallelism needs the materialized
    buffer on the wire) or a compressed substrate (the quant->dequant
    payload transform applies to that buffer) rather than turn into the
    `pallas` path: choose `pallas` there. ep=1 dense/hierarchical wires are
    identity, so skipping them changes nothing (DESIGN.md §10)."""
    from repro.core.moe import (_local_adjust, _local_aux, _routed_aux,
                                _select_branch, _shard_rng, _zero_aux)
    from repro.kernels import ops as K

    moe = cfg.moe
    if (ctx is not None and ctx.active) or moe.comm.compressed:
        raise ValueError(
            "backend 'pallas_fused' has no expert-parallel or compressed-"
            "wire path; use backend 'pallas'")

    from repro.comm import CommEnv, make_transport

    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    E = moe.n_experts
    tok = None if token_ids is None else token_ids.reshape(-1)
    tv = (None if token_valid is None
          else jnp.broadcast_to(token_valid.reshape(-1, 1), (T, moe.top_k)))
    wr = params["router"]["w"]
    experts = params["experts"]
    cf = moe.capacity_factor if is_training else moe.eval_capacity_factor
    cap = min(R.capacity(T, E, moe.top_k, cf), T)
    # telemetry priced identically to `pallas` (ep=1 wire) so the aux dict
    # is backend-invariant; the identity roundtrip itself is fused away
    transport = make_transport(moe.comm, CommEnv(ep=1))

    def _pipeline(info: R.DispatchInfo) -> jax.Array:
        tables = K.routing_tables(info, E, cap)
        return K.fused_moe_op(xf, info, experts["w_in"],
                              experts.get("w_gate"), experts["w_out"],
                              E, cap, cfg.act, interpret=interpret,
                              tables=tables)

    def routed():
        rr = R.route(wr, xf, moe, rng=_shard_rng(rng, 0),
                     is_training=is_training, token_ids=tok)
        info = R.dispatch_info(rr, E, cap, valid=tv)
        comm_t = transport.telemetry(E, cap, shape[-1],
                                     jnp.dtype(xf.dtype).itemsize)
        return _pipeline(info), _routed_aux(rr, info, moe, comm=comm_t)

    def local():
        rr = R.route(wr, xf, moe, rng=_shard_rng(rng, 0),
                     is_training=is_training, token_ids=tok,
                     expert_lo=0, n_local=E)
        rr, valid = _local_adjust(rr, moe, 0, E)
        if tv is not None:
            valid = valid & tv
        info = R.dispatch_info(rr, E, cap, valid=valid)
        return _pipeline(info), _local_aux(rr, info, moe, T)

    def expert_drop():
        return jnp.zeros((T, shape[-1]), x.dtype), _zero_aux(E)

    y, aux = _select_branch(moe, decision, routed, local, expert_drop)
    return y.reshape(shape), aux
