"""MoE token dispatch / combine Pallas kernels (scalar-prefetch gathers).

TPU adaptation of the scatter/gather around the all-to-all: instead of a
data-dependent scatter (expensive on TPU), routing is precomputed into a
slot->token map and the kernels become PURE GATHERS whose BlockSpec
index_maps read the prefetched scalar routing tables — each grid step DMAs
exactly one row from HBM to VMEM. This is the megablocks-style
TPU-idiomatic form: the MXU never sees routing logic, and the gather rides
the scalar-prefetch pipeline.

Rows travel as ``(rows, 1, d)`` views: Mosaic tiles the last two block
dims by (8, 128) unless a block dim equals the array dim, so a one-row
block of a ``(rows, d)`` array is refused while the ``(1, 1, bd)`` block
of the 3-D view is legal (its second-minor dim equals the array's 1).

  dispatch: buf[s] = x[slot_token[s]] * valid[s]       (S = E*C slots)
  combine : y[t]  = sum_k w[t,k] * buf[token_slot[t,k]]

``interpret=None`` (default) auto-detects the platform (DESIGN.md §6):
compiled on TPU, interpreter on CPU. The slot maps consumed here are
built once per step by ``repro.kernels.ops.routing_tables`` and shared by
both gathers.

Both ops are linear in their float inputs, so they carry custom VJPs whose
backwards are plain jnp scatter/gather (the transpose of a gather) — the
pallas backend is differentiable end-to-end inside the train step even
where Pallas itself cannot JVP through scalar-prefetch calls.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import fit_block, resolve_interpret


def _float0_like(a: jax.Array):
    """Zero cotangent for an integer/bool primal (custom_vjp contract)."""
    return np.zeros(np.shape(a), jax.dtypes.float0)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _row_block(d: int, bd: int) -> int:
    """Lane-dim block of a row: a multiple of 128 dividing ``d``, else the
    whole row (both satisfy Mosaic's tiling rule)."""
    b = fit_block(d, bd)
    return b if b % 128 == 0 else d


def _dispatch_kernel(idx_ref, valid_ref, x_ref, o_ref):
    s = pl.program_id(0)
    o_ref[...] = jnp.where(valid_ref[s] > 0, x_ref[...], 0).astype(o_ref.dtype)


def _dispatch_impl(x, idx, valid, bd, interpret):
    t, d = x.shape
    s = idx.shape[0]
    bd = _row_block(d, bd)
    grid = (s, d // bd)
    out = pl.pallas_call(
        _dispatch_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bd),
                             lambda si, dj, idx, val: (idx[si], 0, dj)),
            ],
            out_specs=pl.BlockSpec((1, 1, bd),
                                   lambda si, dj, idx, val: (si, 0, dj)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, 1, d), x.dtype),
        interpret=interpret,
    )(idx, valid, x.reshape(t, 1, d))
    return out.reshape(s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(x, idx, valid, bd, interpret):
    return _dispatch_impl(x, idx, valid, bd, interpret)


def _dispatch_fwd(x, idx, valid, bd, interpret):
    # zero-byte probe keeps x's (T, dtype) in the residuals as a JAX type
    # (raw shape/dtype objects would break scan-of-layers transposition)
    probe = jnp.zeros((x.shape[0], 0), x.dtype)
    return _dispatch_impl(x, idx, valid, bd, interpret), (idx, valid, probe)


def _dispatch_bwd(bd, interpret, res, dy):
    idx, valid, probe = res
    # transpose of the gather: scatter-add rows back onto their tokens
    dy = jnp.where(valid[:, None], dy.astype(jnp.float32), 0)
    dx = jnp.zeros((probe.shape[0], dy.shape[1]), jnp.float32).at[idx].add(dy)
    return dx.astype(probe.dtype), _float0_like(idx), _float0_like(valid)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def _dispatch_jit(x, slot_token, slot_valid, bd, interpret):
    idx = jnp.clip(slot_token, 0, x.shape[0] - 1).astype(jnp.int32)
    valid = slot_valid.astype(jnp.int32)
    return _dispatch(x, idx, valid, bd, interpret)


def dispatch(x: jax.Array, slot_token: jax.Array, slot_valid: jax.Array, *,
             bd: int = 512, interpret: Optional[bool] = None) -> jax.Array:
    """x: (T, d); slot_token/slot_valid: (S,). Returns (S, d) buffer rows.

    interpret resolves BEFORE the jit boundary so the cached executable is
    keyed on the concrete mode (force_interpret stays effective)."""
    return _dispatch_jit(x, slot_token, slot_valid, bd,
                         resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# combine
# ---------------------------------------------------------------------------

def _make_combine_kernel(k: int):
    def kernel(slot_ref, w_ref, *refs):
        # refs: k buffer views + o_ref
        o_ref = refs[-1]
        t = pl.program_id(0)
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for kk in range(k):
            acc = acc + w_ref[t * k + kk] * refs[kk][...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)
    return kernel


def _combine_impl(buf, slots, w, bd, interpret):
    s, d = buf.shape
    t, k = slots.shape
    bd = _row_block(d, bd)
    grid = (t, d // bd)
    in_specs = [
        pl.BlockSpec((1, 1, bd),
                     functools.partial(
                         lambda kk, ti, dj, slot, w_: (slot[ti * k + kk], 0,
                                                       dj),
                         kk))
        for kk in range(k)
    ]
    out = pl.pallas_call(
        _make_combine_kernel(k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bd),
                                   lambda ti, dj, slot, w_: (ti, 0, dj)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, 1, d), buf.dtype),
        interpret=interpret,
    # flat (T*k,) tables: SMEM pads a 2-D table's minor dim to 128 words
    )(slots.reshape(-1), w.reshape(-1), *([buf.reshape(s, 1, d)] * k))
    return out.reshape(t, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(buf, slots, w, bd, interpret):
    return _combine_impl(buf, slots, w, bd, interpret)


def _combine_fwd(buf, slots, w, bd, interpret):
    return _combine_impl(buf, slots, w, bd, interpret), (buf, slots, w)


def _combine_bwd(bd, interpret, res, dy):
    buf, slots, w = res
    t, k = slots.shape
    dyf = dy.astype(jnp.float32)
    # dbuf[s] = sum_{(t,k)->s} w[t,k] * dy[t]   (transpose of the gather)
    contrib = (w[..., None] * dyf[:, None, :]).reshape(t * k, -1)
    dbuf = jnp.zeros(buf.shape, jnp.float32).at[slots.reshape(-1)].add(contrib)
    # dw[t,k] = <dy[t], buf[slots[t,k]]>
    rows = jnp.take(buf, slots.reshape(-1), axis=0).reshape(t, k, -1)
    dw = jnp.einsum("td,tkd->tk", dyf, rows.astype(jnp.float32))
    return dbuf.astype(buf.dtype), _float0_like(slots), dw.astype(w.dtype)


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def _combine_jit(buf, token_slot, weights, keep, bd, interpret):
    slots = jnp.clip(token_slot, 0, buf.shape[0] - 1).astype(jnp.int32)
    w = (weights * keep).astype(jnp.float32)   # grads reach weights here
    return _combine(buf, slots, w, bd, interpret)


def combine(buf: jax.Array, token_slot: jax.Array, weights: jax.Array,
            keep: jax.Array, *, bd: int = 512,
            interpret: Optional[bool] = None) -> jax.Array:
    """buf: (S, d); token_slot: (T, K); weights/keep: (T, K) -> y (T, d)."""
    return _combine_jit(buf, token_slot, weights, keep, bd,
                        resolve_interpret(interpret))
