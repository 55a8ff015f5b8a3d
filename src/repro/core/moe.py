"""Expert-parallel Mixture-of-Experts layer with Gating Dropout.

Layout (paper-faithful, DESIGN.md §4): expert parallelism over the `data`
mesh axis (EP group == DP group, as in Switch/DeepSpeed-MoE), tensor
parallelism of each expert's d_ff over the `model` axis (paper footnote 1),
pure extra data parallelism over `pod` (experts replicated across pods).

Numerically-identical implementations, selected via the execution-backend
registry (core/backend.py, DESIGN.md §6; ``MoEConfig.backend``):

  * ``moe_oracle``   -- pure jnp, `ep` *virtual* shards (vmap). Used on CPU,
                        on one chip, in tests, and as the ground truth for
                        the sharded path. Only routing, dispatch and combine
                        are vmapped; both Gate-Drop branches run the expert
                        FFN once over all experts, outside the vmap, since a
                        vmapped FFN transposes the weight gradient's layout
                        (see ``moe_oracle``).
  * ``moe_sharded``  -- shard_map over the real mesh; the dispatch/combine
                        all-to-alls are explicit ``jax.lax.all_to_all`` over
                        the `data` axis.
  * ``pallas``       -- (backend.py) compiled kernel pipeline: fused routing
                        tables + scalar-prefetch gathers + grouped-FFN.

All share the same per-shard routing pieces, so equality is by
construction. Gating Dropout is a per-step global decision:

  routed step : route over all E experts -> dispatch -> a2a -> expert FFN
                -> a2a -> combine                           (all-to-all paid)
  gate_drop   : route restricted to the local expert group -> local dispatch
                -> local expert FFN -> combine              (no all-to-all)
  gate_expert_drop : output = 0 (residual passthrough)      (no a2a, no FFN)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import Exchange, comm_zero, vcombine, vdispatch
from repro.configs.base import ModelConfig, MoEConfig
from repro.core import router as R

Params = Dict[str, Any]
Decision = Union[None, bool, jax.Array]


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Mesh + axis-name bundle threaded through the model."""
    mesh: Optional[jax.sharding.Mesh] = None
    ep_axis: str = "data"     # expert parallel == data parallel (paper layout)
    tp_axis: str = "model"
    pod_axis: str = "pod"

    @property
    def active(self) -> bool:
        return self.mesh is not None and self.mesh.size > 1

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        if self.mesh is not None and self.pod_axis in self.mesh.axis_names:
            return (self.pod_axis, self.ep_axis)
        return (self.ep_axis,)

    @property
    def ep(self) -> int:
        return self.mesh.shape[self.ep_axis] if self.active else 1

    @property
    def tp(self) -> int:
        if self.active and self.tp_axis in self.mesh.axis_names:
            return self.mesh.shape[self.tp_axis]
        return 1


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_moe_params(key: jax.Array, cfg: ModelConfig, *, dtype=None) -> Params:
    moe = cfg.moe
    assert moe is not None
    d = cfg.d_model
    dff = moe.d_ff(cfg.d_ff)
    E = moe.n_experts
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    k_r, k_i, k_g, k_o = jax.random.split(key, 4)
    std_in = d ** -0.5
    std_out = dff ** -0.5
    p: Params = {
        "router": {"w": jax.random.normal(k_r, (d, E), dtype) * std_in},
        "experts": {
            "w_in": jax.random.normal(k_i, (E, d, dff), dtype) * std_in,
            "w_out": jax.random.normal(k_o, (E, dff, d), dtype) * std_out,
        },
    }
    if cfg.gated_mlp:
        p["experts"]["w_gate"] = jax.random.normal(k_g, (E, d, dff), dtype) * std_in
    return p


def moe_param_specs(cfg: ModelConfig, ctx: ParallelContext) -> Params:
    """PartitionSpec tree matching init_moe_params."""
    ep = ctx.ep_axis
    tp = ctx.tp_axis if (ctx.mesh is None
                         or ctx.tp_axis in ctx.mesh.axis_names) else None
    if cfg.moe is not None and cfg.moe.ep_on_model and tp is not None:
        # beyond-paper layout: experts sharded over data x model, no TP
        # inside experts (each expert's full d_ff lives on one device)
        ep, tp = (ep, tp), None
    specs: Params = {
        "router": {"w": P(None, None)},
        "experts": {
            "w_in": P(ep, None, tp),
            "w_out": P(ep, tp, None),
        },
    }
    if cfg.gated_mlp:
        specs["experts"]["w_gate"] = P(ep, None, tp)
    return specs


# ---------------------------------------------------------------------------
# per-shard pieces (shared by oracle and shard_map paths)
# ---------------------------------------------------------------------------

def _act(h: jax.Array, name: str) -> jax.Array:
    return jax.nn.silu(h) if name == "silu" else jax.nn.gelu(h)


def _expert_ffn(experts: Params, buf: jax.Array, cfg: ModelConfig,
                tp_axis: Optional[str]) -> jax.Array:
    """Apply per-expert FFN to (E_loc, C, d) buffers.

    Expert d_ff is sliced over `tp_axis`; the output matmul produces a
    partial sum that is reduced with psum (tensor parallelism inside each
    expert — the paper's footnote-1 tensor slicing). With kernels enabled
    the grouped matmuls run through the Pallas grouped_matmul kernel."""
    from repro.kernels import ops as K
    w_in = experts["w_in"]
    w_out = experts["w_out"]
    x = buf.astype(w_in.dtype)
    if K.KERNELS_ENABLED:
        y = K.expert_ffn_op(x, w_in, experts.get("w_gate"), w_out, cfg.act)
    else:
        h = jnp.einsum("ecd,edf->ecf", x, w_in)
        if cfg.gated_mlp:
            g = jnp.einsum("ecd,edf->ecf", x, experts["w_gate"])
            h = _act(g, cfg.act) * h
        else:
            h = _act(h, cfg.act)
        y = jnp.einsum("ecf,efd->ecd", h, w_out)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y.astype(buf.dtype)


def _shard_rng(rng, my_shard):
    """Per-shard jitter key: fold the shard index so each 'machine' draws
    distinct routing noise (matches real per-worker noise)."""
    return None if rng is None else jax.random.fold_in(rng, my_shard)


def _routed_aux(rr, info, moe: MoEConfig, comm=None) -> Dict[str, jax.Array]:
    """Aux dict for a routed step — shared by every backend so metric
    semantics cannot desync (DESIGN.md §6). ``comm`` carries the layer's
    in-graph exchange telemetry (DESIGN.md §10); None = no wire (the
    ep=1 kernel pipelines)."""
    return {
        "balance": R.balance_loss(rr, moe) if moe.router_type != "hash"
                   else jnp.zeros(()),
        "router_z": R.router_z_loss(rr) if moe.router_type != "hash"
                    else jnp.zeros(()),
        "load": R.expert_load(rr, moe),
        "router_entropy": R.route_entropy(rr),
        "dropped_frac": 1.0 - info.keep.mean(),
        **(comm if comm is not None else comm_zero()),
    }


def _local_adjust(rr, moe: MoEConfig, lo, e_loc: int):
    """Gate-Drop local-path weight override + validity mask (shared)."""
    if moe.gating_dropout.local_combine == "one":
        rr = rr._replace(topk_w=jnp.full_like(rr.topk_w, 1.0 / moe.top_k))
    # entries that could not be satisfied locally (k > e_loc) are invalid
    valid = (rr.topk_idx >= lo) & (rr.topk_idx < lo + e_loc) & (rr.topk_w > 0)
    return rr, valid


def _local_aux(rr, info, moe: MoEConfig, T: int) -> Dict[str, jax.Array]:
    """Aux dict for a Gate-Drop local step (balance only on routed steps);
    ``rr`` must carry GLOBAL expert ids.

    Load counts ALL k slots, each weighted by ``info.keep`` (valid local
    pick that survived capacity) — matching the routed-step semantics of
    ``router.expert_load`` where ``load.sum() == top_k``; here the sum is
    <= top_k, short exactly by the dropped fraction. Counting only slot 0
    (the old behavior) misreported expert load for top_k > 1."""
    w = (info.keep.astype(jnp.float32) / T).reshape(-1)
    load = jnp.zeros((moe.n_experts,), jnp.float32).at[
        rr.topk_idx.reshape(-1)].add(w, mode="drop")
    return {"balance": jnp.zeros(()), "router_z": jnp.zeros(()),
            "load": load, "router_entropy": R.route_entropy(rr),
            "dropped_frac": 1.0 - info.keep.mean(),
            **comm_zero()}


def _token_valid_tk(token_valid, k: int):
    """(T,) bool token validity -> (T, k) dispatch validity (or None)."""
    if token_valid is None:
        return None
    return jnp.broadcast_to(token_valid.reshape(-1, 1),
                            (token_valid.size, k))


def make_transport(moe: MoEConfig, exchange: Exchange) -> Exchange:
    """The wire of one routed layer of ``moe_sharded``: ``exchange``
    itself. ``moe_sharded`` looks this name up when it traces, so a test
    can put a wire that leaves the exchange out in its place
    (``bench/tests/test_bench_faults.py``)."""
    return exchange


def _routed_shard(wr, experts, xf, moe: MoEConfig, cfg: ModelConfig, rng,
                  is_training, token_ids, my_shard, ep: int, tp_axis,
                  transport, token_valid=None):
    """Normal MoE step on one shard: route -> dispatch -> (wire) -> FFN ->
    (wire) -> combine. The wire is the expert exchange (DESIGN.md §10),
    bit-for-bit the historical inline all-to-all pair. ``token_valid``
    masks tokens (retired serving slots) out of capacity competition —
    they neither dispatch nor combine."""
    T = xf.shape[0]
    E = moe.n_experts
    cf = moe.capacity_factor if is_training else moe.eval_capacity_factor
    cap = min(R.capacity(T, E, moe.top_k, cf), T)
    rr = R.route(wr, xf, moe, rng=_shard_rng(rng, my_shard),
                 is_training=is_training, token_ids=token_ids)
    info = R.dispatch_info(rr, E, cap,
                           valid=_token_valid_tk(token_valid, moe.top_k))
    from repro.kernels import ops as K
    if K.KERNELS_ENABLED:
        # routing tables built once; the combine gather reuses them
        tables = K.routing_tables(info, E, cap)
        buf = K.moe_dispatch_op(xf, info, E, cap, tables=tables)
    else:
        tables = None
        buf = R.dispatch(xf, info, E, cap)                   # (E, cap, d)
    comm_t = transport.telemetry(E, cap, xf.shape[-1],
                                 jnp.dtype(buf.dtype).itemsize)
    # dispatch wire -> grouped FFN -> combine wire:
    # (E, cap, d) -> (E/ep, ep*cap, d) -> FFN -> (E, cap, d)
    out = transport.pipelined(
        buf, lambda b: _expert_ffn(experts, b, cfg, tp_axis))
    y = (K.moe_combine_op(out, info, tables=tables) if K.KERNELS_ENABLED
         else R.combine(out, info))
    return y, _routed_aux(rr, info, moe, comm=comm_t)


def _local_route(wr, xf, moe: MoEConfig, rng, is_training, token_ids,
                 my_shard, ep: int, token_valid=None):
    """Gate-Drop routing of one shard's tokens among its local expert group:
    route, the local-combine override, the validity mask and the dispatch
    info over shard-local expert ids. Returns ``(rr, info, cap)``; ``rr``
    keeps GLOBAL expert ids (for ``_local_aux``)."""
    T = xf.shape[0]
    e_loc = moe.n_experts // ep
    lo = my_shard * e_loc
    rr = R.route(wr, xf, moe, rng=_shard_rng(rng, my_shard),
                 is_training=is_training, token_ids=token_ids,
                 expert_lo=lo, n_local=e_loc)
    rr, valid = _local_adjust(rr, moe, lo, e_loc)
    if token_valid is not None:
        valid = valid & token_valid.reshape(-1, 1)
    rr_local = rr._replace(topk_idx=rr.topk_idx - lo)
    cf = moe.capacity_factor if is_training else moe.eval_capacity_factor
    cap = min(R.capacity(T, e_loc, moe.top_k, cf), T)
    info = R.dispatch_info(rr_local, e_loc, cap, valid=valid)
    return rr, info, cap


def _local_shard(wr, experts_loc, xf, moe: MoEConfig, cfg: ModelConfig, rng,
                 is_training, token_ids, my_shard, ep: int, tp_axis,
                 token_valid=None):
    """Gate-Drop local step: tokens stay on this shard, routed among the
    local expert group only. No collective over the data axis."""
    rr, info, cap = _local_route(wr, xf, moe, rng, is_training, token_ids,
                                 my_shard, ep, token_valid)
    buf = R.dispatch(xf, info, moe.n_experts // ep, cap)     # (e_loc, cap, d)
    out = _expert_ffn(experts_loc, buf, cfg, tp_axis)
    y = R.combine(out, info)
    return y, _local_aux(rr, info, moe, xf.shape[0])


def _zero_aux(E: int):
    return {"balance": jnp.zeros(()), "router_z": jnp.zeros(()),
            "load": jnp.zeros((E,), jnp.float32),
            "router_entropy": jnp.zeros(()),
            "dropped_frac": jnp.zeros(()), **comm_zero()}


# ---------------------------------------------------------------------------
# oracle (pure jnp, virtual shards)
# ---------------------------------------------------------------------------

def moe_oracle(params: Params, x: jax.Array, cfg: ModelConfig, *,
               ep: int = 1, rng: Optional[jax.Array] = None,
               decision: Decision = None, is_training: bool = True,
               token_ids: Optional[jax.Array] = None,
               token_valid: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict]:
    """Reference MoE with `ep` virtual machines. x: (B, L, d) or (T, d).

    Routing, dispatch and combine run per virtual shard under ``vmap``;
    the expert FFN runs once, outside it, on the shards' buffers laid out
    in expert order, in the routed branch and the Gate-Drop local branch
    alike. A vmapped expert FFN gives the expert weights' gradient a
    transposed layout, which Adam's update follows: on a TPU v5e that cost
    copies of every expert leaf of the train state (parameters and both
    moments) into and back out of that layout, 24 whole-leaf copies a
    dropped step at zcode-m3-base."""
    moe = cfg.moe
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    assert T % ep == 0 and moe.n_experts % ep == 0
    xs = xf.reshape(ep, T // ep, shape[-1])
    tok = None if token_ids is None else token_ids.reshape(ep, T // ep)
    tv = None if token_valid is None else token_valid.reshape(ep, T // ep)
    wr = params["router"]["w"]
    experts = params["experts"]
    E = moe.n_experts

    def routed():
        Tl = T // ep
        cf = moe.capacity_factor if is_training else moe.eval_capacity_factor
        cap = min(R.capacity(Tl, E, moe.top_k, cf), Tl)

        def shard_dispatch(my, xl, tl, tvl):
            rr = R.route(wr, xl, moe, rng=_shard_rng(rng, my),
                         is_training=is_training, token_ids=tl)
            info = R.dispatch_info(rr, E, cap,
                                   valid=_token_valid_tk(tvl, moe.top_k))
            return R.dispatch(xl, info, E, cap), info, rr

        bufs, infos, rrs = jax.vmap(
            shard_dispatch, in_axes=(0, 0, 0 if tok is not None else None,
                                     0 if tv is not None else None))(
            jnp.arange(ep), xs, tok, tv)
        # virtual wire: (ep, E, cap, d) -> (E, ep*cap, d) -> FFN ->
        # (ep, E, cap, d)
        outs = vcombine(_expert_ffn(experts, vdispatch(bufs), cfg, None),
                        ep)
        y = jax.vmap(R.combine)(outs, infos)
        aux = {
            "balance": jax.vmap(lambda r: R.balance_loss(r, moe))(rrs).mean()
                       if moe.router_type != "hash" else jnp.zeros(()),
            "router_z": jax.vmap(R.router_z_loss)(rrs).mean()
                        if moe.router_type != "hash" else jnp.zeros(()),
            "load": jax.vmap(lambda r: R.expert_load(r, moe))(rrs).mean(0),
            "router_entropy": jax.vmap(R.route_entropy)(rrs).mean(),
            "dropped_frac": 1.0 - infos.keep.mean(),
            **Exchange(ep).telemetry(E, cap, shape[-1],
                                     jnp.dtype(x.dtype).itemsize),
        }
        return y.reshape(ep * (T // ep), -1), aux

    def local():
        e_loc = E // ep

        def shard_dispatch(my, xl, tl, tvl):
            rr, info, cap = _local_route(wr, xl, moe, rng, is_training, tl,
                                         my, ep, token_valid=tvl)
            return R.dispatch(xl, info, e_loc, cap), info, rr

        bufs, infos, rrs = jax.vmap(
            shard_dispatch, in_axes=(0, 0, 0 if tok is not None else None,
                                     0 if tv is not None else None))(
            jnp.arange(ep), xs, tok, tv)
        # (ep, e_loc, cap, d) -> (E, cap, d): shard my's experts are rows
        # my*e_loc.., so this is expert order. One FFN over all experts,
        # outside the vmap, as in routed().
        outs = _expert_ffn(experts, bufs.reshape(E, *bufs.shape[2:]), cfg,
                           None).reshape(bufs.shape)
        ys = jax.vmap(R.combine)(outs, infos)
        auxs = jax.vmap(lambda r, i: _local_aux(r, i, moe, T // ep))(
            rrs, infos)
        return ys.reshape(T, -1), jax.tree.map(lambda a: a.mean(0), auxs)

    def expert_drop():
        return jnp.zeros((T, shape[-1]), x.dtype), _zero_aux(E)

    y, aux = _select_branch(moe, decision, routed, local, expert_drop)
    return y.reshape(shape), aux


def _select_branch(moe: MoEConfig, decision: Decision, routed, local,
                   expert_drop):
    """Pick the routed / dropped branch. Python-bool decision -> static
    branch (host_cond strategy: the collective is absent from the dropped
    executable). Traced decision -> lax.cond (traced_cond strategy)."""
    dropped = local if moe.gating_dropout.mode != "gate_expert_drop" else expert_drop
    if decision is None or (isinstance(decision, bool) and not decision):
        return routed()
    if isinstance(decision, bool):
        return dropped()
    return jax.lax.cond(decision, dropped, routed)


# ---------------------------------------------------------------------------
# shard_map (real mesh)
# ---------------------------------------------------------------------------

def moe_sharded(params: Params, x: jax.Array, cfg: ModelConfig,
                ctx: ParallelContext, *, rng: Optional[jax.Array] = None,
                decision: Decision = None, is_training: bool = True,
                token_ids: Optional[jax.Array] = None,
                token_valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict]:
    """MoE with real all-to-all over ctx.ep_axis. x: (B, L, d)."""
    moe = cfg.moe
    mesh = ctx.mesh
    E = moe.n_experts
    dp = ctx.dp_axes
    all_axes = tuple(mesh.axis_names)
    # beyond-paper layout (DESIGN.md §4): EP over data x model.
    # Each device holds E/(dp*tp) whole experts (full d_ff); tokens are
    # additionally sequence-sharded over `model`, so the all-to-all moves
    # 1/tp of the baseline bytes per device and the redundant
    # replicated-over-model dispatch disappears.
    ep_on_model = (moe.ep_on_model and ctx.tp > 1
                   and E % (ctx.ep * ctx.tp) == 0
                   and x.shape[1] % ctx.tp == 0)
    if ep_on_model:
        ep = ctx.ep * ctx.tp
        tp_axis = None
        # the ep group IS the (data x model) axis pair
        exchange = Exchange(ep, (ctx.ep_axis, ctx.tp_axis))
        x_spec = P(dp, ctx.tp_axis, None)
        tok_spec = P(dp, ctx.tp_axis)
    else:
        ep = ctx.ep
        tp_axis = ctx.tp_axis if ctx.tp > 1 else None
        exchange = Exchange(ep, ctx.ep_axis)
        x_spec = P(dp, None, None)
        tok_spec = P(dp, None)
    assert E % ep == 0, (E, ep)
    transport = make_transport(moe, exchange)

    # Python-bool / None decisions are baked into the executable (host_cond):
    # the dropped executable contains no all-to-all. Traced decisions are
    # passed as a replicated operand (traced_cond).
    static_dec = decision if (decision is None or isinstance(decision, bool)) \
        else None
    traced = static_dec is None and decision is not None

    def body(wr, experts, x_loc, rng_, dec, tok_loc, tv_loc):
        # the placeholder key below stands for "no rng": no router jitter,
        # as in the oracle and pallas backends
        rng_ = None if rng is None else rng_
        B_loc, L, d = x_loc.shape
        xf = x_loc.reshape(B_loc * L, d)
        tf = None if tok_loc is None else tok_loc.reshape(-1)
        tvf = None if tv_loc is None else tv_loc.reshape(-1)
        if ep_on_model:
            my = (jax.lax.axis_index(ctx.ep_axis) * ctx.tp
                  + jax.lax.axis_index(ctx.tp_axis))
        else:
            my = jax.lax.axis_index(ctx.ep_axis)

        def routed():
            return _routed_shard(wr, experts, xf, moe, cfg, rng_, is_training,
                                 tf, my, ep, tp_axis, transport,
                                 token_valid=tvf)

        def local():
            return _local_shard(wr, experts, xf, moe, cfg, rng_, is_training,
                                tf, my, ep, tp_axis, token_valid=tvf)

        def expert_drop():
            return jnp.zeros_like(xf), _zero_aux(E)

        y, aux = _select_branch(moe, dec, routed, local, expert_drop)
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(B_loc, L, d), aux

    in_specs = [
        P(),                                   # router weights: replicated
        moe_param_specs(cfg, ctx)["experts"],  # experts: EP (+TP) layout
        x_spec,                                # x: batch over (pod,) data
        P(),                                   # rng
    ]
    args = [params["router"]["w"], params["experts"], x,
            rng if rng is not None else jax.random.PRNGKey(0)]
    if traced:
        in_specs.append(P())
        args.append(jnp.asarray(decision))
    if token_ids is not None:
        in_specs.append(tok_spec)
        args.append(token_ids)
    if token_valid is not None:
        in_specs.append(tok_spec)
        args.append(token_valid)

    def wrapper(*ops):
        wr, experts, x_loc, rng_ = ops[:4]
        i = 4
        if traced:
            dec = ops[i]; i += 1
        else:
            dec = static_dec
        tok_loc = None
        if token_ids is not None:
            tok_loc = ops[i]; i += 1
        tv_loc = ops[i] if token_valid is not None else None
        return body(wr, experts, x_loc, rng_, dec, tok_loc, tv_loc)

    fn = jax.shard_map(wrapper, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=(x_spec, P()), check_vma=False)
    return fn(*args)


def moe_apply(params: Params, x: jax.Array, cfg: ModelConfig,
              ctx: Optional[ParallelContext] = None, *,
              rng: Optional[jax.Array] = None, decision: Decision = None,
              is_training: bool = True,
              token_ids: Optional[jax.Array] = None,
              token_valid: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Dict]:
    """Entry point used by the models. The execution path is chosen by
    ``cfg.moe.backend`` through the backend registry (DESIGN.md §6);
    the default "auto" keeps the historical behavior — sharded when a real
    mesh is active, oracle otherwise. ``token_valid`` (same leading shape
    as ``x``'s token dims) marks tokens from retired/empty serving slots:
    they are routed but never dispatched, so they cannot steal expert
    capacity from live tokens (DESIGN.md §9). Its ops carry the
    ``moe`` name scope, which a profiler trace reads back."""
    from repro.core import backend as B
    fn = B.get_backend(B.resolve_backend(cfg.moe, ctx))
    with jax.named_scope("moe"):
        return fn(params, x, cfg, ctx, rng=rng, decision=decision,
                  is_training=is_training, token_ids=token_ids,
                  token_valid=token_valid)
