"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) auto-detects the platform (DESIGN.md §6):
kernels compile on TPU and run under the Pallas interpreter on CPU.
Routing-table construction (slot maps) lives here: ``routing_tables`` turns
the router's DispatchInfo into the gather form the kernels consume, ONCE
per step — both the dispatch and the combine gather reuse the same tables.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

import contextlib

from repro.core.router import DispatchInfo
from repro.kernels.flash_decode import flash_decode
from repro.kernels.grouped_ffn import grouped_matmul
from repro.kernels.moe_dispatch import combine, dispatch
from repro.kernels.moe_megakernel import fused_moe_ffn
from repro.kernels.platform import (default_interpret, force_interpret,
                                    resolve_interpret)

# Global switch: when True the MoE layer routes its dispatch/FFN/combine
# through the Pallas kernels (interpret mode on CPU). Flip with
# use_kernels(); the `pallas` execution backend (core/backend.py) uses the
# kernels unconditionally.
KERNELS_ENABLED = False


@contextlib.contextmanager
def use_kernels(enabled: bool = True):
    global KERNELS_ENABLED
    prev = KERNELS_ENABLED
    KERNELS_ENABLED = enabled
    try:
        yield
    finally:
        KERNELS_ENABLED = prev


class RoutingTables(NamedTuple):
    """Gather-form routing state, built once per step from DispatchInfo.

    slot_token[e*C + c] = which token fills slot c of expert e (-1 empty);
    slot_valid[s]       = slot s is occupied;
    token_slot[t, k]    = flat slot index for the (t, k) routing choice.
    """
    slot_token: jax.Array    # (E*C,) int32
    slot_valid: jax.Array    # (E*C,) bool
    token_slot: jax.Array    # (T, K) int32


def routing_tables(info: DispatchInfo, n_experts: int,
                   cap: int) -> RoutingTables:
    """DispatchInfo -> RoutingTables. The fused builder: one scatter over
    (T*k,) produces both gather maps, so dispatch and combine share it."""
    t, k = info.topk_idx.shape
    flat_e = info.topk_idx.reshape(-1)
    flat_p = info.pos.reshape(-1)
    keep = info.keep.reshape(-1)
    flat_slot = jnp.where(keep, flat_e * cap + flat_p, n_experts * cap)
    token_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    slot_token = jnp.full((n_experts * cap + 1,), -1, jnp.int32
                          ).at[flat_slot].set(token_ids, mode="drop")[:-1]
    slot_valid = slot_token >= 0
    token_slot = jnp.where(keep, flat_e * cap + flat_p, 0).reshape(t, k)
    return RoutingTables(slot_token, slot_valid, token_slot)


def build_slot_maps(info: DispatchInfo, n_experts: int,
                    cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Back-compat alias of routing_tables (returns the same named tuple)."""
    return routing_tables(info, n_experts, cap)


def moe_dispatch_op(x: jax.Array, info: DispatchInfo, n_experts: int,
                    cap: int, *, interpret: Optional[bool] = None,
                    tables: Optional[RoutingTables] = None) -> jax.Array:
    """Kernel-backed equivalent of router.dispatch: (T,d) -> (E, C, d).

    Pass ``tables`` (from routing_tables) to reuse slot maps already built
    for this step instead of recomputing them."""
    if tables is None:
        tables = routing_tables(info, n_experts, cap)
    buf = dispatch(x, tables.slot_token, tables.slot_valid,
                   interpret=interpret)
    return buf.reshape(n_experts, cap, x.shape[-1])


def moe_combine_op(buf: jax.Array, info: DispatchInfo, *,
                   interpret: Optional[bool] = None,
                   tables: Optional[RoutingTables] = None) -> jax.Array:
    """Kernel-backed equivalent of router.combine: (E, C, d) -> (T, d)."""
    e, cap, d = buf.shape
    if tables is None:
        tables = routing_tables(info, e, cap)
    return combine(buf.reshape(e * cap, d), tables.token_slot, info.topk_w,
                   info.keep, interpret=interpret)


def fused_moe_op(x: jax.Array, info: DispatchInfo, w_in: jax.Array, w_gate,
                 w_out: jax.Array, n_experts: int, cap: int,
                 act: str = "silu", *, interpret: Optional[bool] = None,
                 tables: Optional[RoutingTables] = None) -> jax.Array:
    """ONE-launch fused equivalent of dispatch -> expert_ffn_op -> combine
    (kernels.moe_megakernel, DESIGN.md §11): (T, d) -> (T, d) without ever
    materializing the (E, C, d) buffer in HBM. ``tables`` drive the
    in-kernel gather/scatter and the custom VJP's slot-formulation
    backward."""
    if tables is None:
        tables = routing_tables(info, n_experts, cap)
    return fused_moe_ffn(x, w_in, w_gate, w_out, info.topk_w,
                         info.keep, tables.slot_token, tables.slot_valid,
                         tables.token_slot, act=act, interpret=interpret)


def expert_ffn_op(buf: jax.Array, w_in: jax.Array, w_gate, w_out: jax.Array,
                  act: str = "silu", *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Full gated expert FFN from grouped_matmul kernels."""
    h = grouped_matmul(buf, w_in, interpret=interpret)
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    if w_gate is not None:
        g = grouped_matmul(buf, w_gate, interpret=interpret)
        h = actf(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = actf(h.astype(jnp.float32)).astype(h.dtype)
    return grouped_matmul(h, w_out, interpret=interpret)


__all__ = ["RoutingTables", "build_slot_maps", "combine", "default_interpret",
           "dispatch", "expert_ffn_op", "flash_decode", "force_interpret",
           "fused_moe_ffn", "fused_moe_op", "grouped_matmul",
           "moe_combine_op", "moe_dispatch_op", "resolve_interpret",
           "routing_tables"]
