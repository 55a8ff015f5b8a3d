"""Fused MoE megakernel: dispatch + two-layer expert FFN + combine in ONE
Pallas launch (DESIGN.md §11).

The three-kernel pipeline (scalar-prefetch dispatch gather ->
grouped_matmul x2/x3 -> weighted combine gather) pays five kernel
launches per MoE layer and materializes the (E, C, d) expert buffer twice
in HBM. This kernel is the EXPERT-MAJOR fusion of that pipeline:

  grid (E, F/bf); for expert e and f-block j, the prologue gathers the
  expert's C token rows in-kernel through its slice of the slot-token
  table (``x`` stays resident in VMEM; the (E, C, d) buffer never exists
  in HBM), the two matmuls run back to back with the gated activation
  fused between them in f32, and the epilogue scatter-accumulates
  ``wslot[e, c] * out_c`` into each source token's output row through a
  (T, d) VMEM accumulator — the combine gather transposed into the same
  launch. The grid is O(E * F/bf) steps, NOT O(T): per-step work is
  dense matmul over the capacity block, which is what keeps the fused
  kernel ahead of the pipeline's O(slots + T) step counts in both
  interpret timing and compiled occupancy.

Index-table contract (DESIGN.md §11): capacity truncation, Gate-Drop
local validity, and serving ``token_valid`` slot masking all arrive
PRE-FOLDED into ``wcomb = topk_w * keep`` (computed inside the jit
wrapper so gradients reach the router weights, exactly like
``moe_dispatch._combine_jit``), then scattered onto slots as ``wslot``:
an unoccupied or dropped slot still runs through the expert FFN (its
gather index is clipped) but contributes with weight 0 — bit-compatible
with the buffer formulation where the row arrives zeroed.

The kernel carries a custom VJP: Pallas cannot JVP through
scalar-prefetch calls, and the backward of a fused gather-FFN-scatter is
the transpose pair ``_dispatch_bwd``/``_combine_bwd`` around the FFN
backward. Rather than hand-chaining those, the backward takes ``jax.vjp``
of the pure-jnp SLOT formulation (dispatch_ref-style gather -> einsum FFN
-> combine_ref-style weighted gather), which is algebraically that exact
chain — the slot tables ride along as integer (float0-cotangent) primals.
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


def _act_f32(act: str):
    return jax.nn.silu if act == "silu" else jax.nn.gelu


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _sublanes(dtype) -> int:
    """Rows per (sublane x lane) tile: 8 for 32-bit, 16 for bf16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row_block(ref, r):
    """The tile-aligned row block of ``ref`` holding row ``r`` and a mask
    selecting that row: Mosaic loads and stores only at sublane offsets
    it can prove tile-aligned, so one dynamic row is read and written
    through its aligned block."""
    sub = _sublanes(ref.dtype)
    base = pl.multiple_of((r // sub) * sub, sub)
    sel = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0) == r - base
    return pl.ds(base, sub), sel


def _make_kernel(gated: bool, act: str, c: int):
    actf = _act_f32(act)

    def kernel(st_ref, ws_ref, x_ref, *refs):
        # st_ref/ws_ref: flat (E*C,) slot-token / slot-weight tables in SMEM
        # refs: w_in, [w_gate], w_out, o_ref, acc_ref, rows_ref, out_ref
        w_in_ref = refs[0]
        w_gate_ref = refs[1] if gated else None
        w_out_ref = refs[2] if gated else refs[1]
        o_ref, acc_ref, rows_ref, out_ref = refs[-4:]
        e_i = pl.program_id(0)
        j = pl.program_id(1)
        t = o_ref.shape[0]

        @pl.when((e_i == 0) & (j == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def token(cc):
            return jnp.clip(st_ref[e_i * c + cc], 0, t - 1)

        def gather(cc, carry):                                 # (C, d) rows
            xs, xsel = _row_block(x_ref, token(cc))
            row = jnp.sum(jnp.where(xsel, x_ref[xs, :].astype(jnp.float32),
                                    0.0), axis=0, keepdims=True)
            rs, rsel = _row_block(rows_ref, cc)
            rows_ref[rs, :] = jnp.where(rsel, row, rows_ref[rs, :])
            return carry

        jax.lax.fori_loop(0, c, gather, 0)
        rows = rows_ref[...]
        h = jnp.dot(rows, w_in_ref[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32)        # (C, bf)
        if gated:
            g = jnp.dot(rows, w_gate_ref[0].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
            h = actf(g) * h
        else:
            h = actf(h)
        out_ref[...] = jnp.dot(h, w_out_ref[0].astype(jnp.float32),
                               preferred_element_type=jnp.float32)  # (C, d)

        def scatter(cc, carry):                                # (T, d) acc
            os_, osel = _row_block(out_ref, cc)
            row = jnp.sum(jnp.where(osel, out_ref[os_, :], 0.0), axis=0,
                          keepdims=True) * ws_ref[e_i * c + cc]
            acs, asel = _row_block(acc_ref, token(cc))
            acc_ref[acs, :] += jnp.where(asel, row, 0.0)
            return carry

        jax.lax.fori_loop(0, c, scatter, 0)

        @pl.when((e_i == pl.num_programs(0) - 1)
                 & (j == pl.num_programs(1) - 1))
        def _done():
            o_ref[...] = acc_ref[:t].astype(o_ref.dtype)

    return kernel


def _vmem_limit(tp: int, d: int, bf: int, cp: int, n_w: int, itemsize: int
                ) -> int:
    """Scoped-VMEM request for the resident (T, d) blocks: double-buffered
    x and output, the f32 accumulator, double-buffered weight blocks and
    the f32 (C, d) row/output scratch, plus 25% headroom."""
    need = (2 * tp * d * itemsize * 2 + tp * d * 4
            + 2 * n_w * d * bf * itemsize + 2 * cp * d * 4)
    return int(need * 1.25) + (4 << 20)


def _fused_impl(x, w_in, w_gate, w_out, wcomb, slot_token, token_slot, act,
                bf, interpret):
    t, d = x.shape
    e, _, f = w_in.shape
    s = slot_token.shape[0]
    c = s // e
    gated = w_gate is not None
    bf = fit_block(f, bf)
    grid = (e, f // bf)
    # per-slot combine weight: every kept (t, k) owns exactly one slot;
    # dropped entries scatter-add their (clipped) index with weight 0
    wslot = jnp.zeros((s,), jnp.float32).at[token_slot.reshape(-1)].add(
        wcomb.reshape(-1))
    # rows are read and written through whole tiles (_row_block), so the
    # resident x and the scratch pad their row counts to the tile height
    tp = _round_up(t, _sublanes(x.dtype))
    cp = _round_up(c, 8)
    xp = jnp.pad(x, ((0, tp - t), (0, 0))) if tp != t else x

    in_specs = [pl.BlockSpec((tp, d), lambda e_, j, st, ws: (0, 0)),
                pl.BlockSpec((1, d, bf), lambda e_, j, st, ws: (e_, 0, j))]
    operands = [xp, w_in]
    if gated:
        in_specs += [pl.BlockSpec((1, d, bf),
                                  lambda e_, j, st, ws: (e_, 0, j))]
        operands += [w_gate]
    in_specs += [pl.BlockSpec((1, bf, d), lambda e_, j, st, ws: (e_, j, 0))]
    operands += [w_out]
    itemsize = jnp.dtype(x.dtype).itemsize

    return pl.pallas_call(
        _make_kernel(gated, act, c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((t, d), lambda e_, j, st, ws: (0, 0)),
            scratch_shapes=[pltpu.VMEM((_round_up(t, 8), d), jnp.float32),
                            pltpu.VMEM((cp, d), jnp.float32),
                            pltpu.VMEM((cp, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tp, d, bf, cp, 3 if gated else 2,
                                         itemsize)),
        interpret=interpret,
    )(slot_token, wslot, *operands)


def _ref_forward(x, w_in, w_gate, w_out, wcomb, slot_token, slot_valid,
                 token_slot, act: str, out_dtype):
    """Pure-jnp SLOT formulation of the fused kernel — the VJP oracle.

    dispatch_ref-style gather -> einsum FFN (activation in f32, matching
    ops.expert_ffn_op) -> combine_ref-style weighted gather. Algebraically
    equal to the token-major kernel: kept entries read their token's row
    from the buffer, dropped entries carry wcomb == 0.
    """
    t = x.shape[0]
    e, _, f = w_in.shape
    s = slot_token.shape[0]
    actf = _act_f32(act)
    rows = jnp.take(x, jnp.clip(slot_token, 0, t - 1), axis=0)
    buf = jnp.where(slot_valid[:, None], rows, 0)              # (S, d)
    bufe = buf.reshape(e, s // e, -1).astype(w_in.dtype)
    h = jnp.einsum("ecd,edf->ecf", bufe, w_in)
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", bufe, w_gate)
        h = actf(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = actf(h.astype(jnp.float32)).astype(h.dtype)
    out = jnp.einsum("ecf,efd->ecd", h, w_out).reshape(s, -1)
    picked = jnp.take(out, jnp.clip(token_slot, 0, s - 1).reshape(-1),
                      axis=0).reshape(token_slot.shape + (out.shape[-1],))
    y = jnp.einsum("tkd,tk->td", picked.astype(jnp.float32), wcomb)
    return y.astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _fused(x, w_in, w_gate, w_out, wcomb, slot_token, slot_valid,
           token_slot, act, bf, interpret):
    return _fused_impl(x, w_in, w_gate, w_out, wcomb, slot_token,
                       token_slot, act, bf, interpret)


def _fused_fwd(x, w_in, w_gate, w_out, wcomb, slot_token, slot_valid,
               token_slot, act, bf, interpret):
    y = _fused(x, w_in, w_gate, w_out, wcomb, slot_token, slot_valid,
               token_slot, act, bf, interpret)
    return y, (x, w_in, w_gate, w_out, wcomb, slot_token, slot_valid,
               token_slot)


def _fused_bwd(act, bf, interpret, res, dy):
    (x, w_in, w_gate, w_out, wcomb, slot_token, slot_valid,
     token_slot) = res
    _, vjp = jax.vjp(
        lambda x_, wi, wg, wo, wc: _ref_forward(
            x_, wi, wg, wo, wc, slot_token, slot_valid, token_slot, act,
            dy.dtype),
        x, w_in, w_gate, w_out, wcomb)
    dx, dw_in, dw_gate, dw_out, dwcomb = vjp(dy)
    return (dx, dw_in, dw_gate, dw_out, dwcomb,
            _float0_like(slot_token), _float0_like(slot_valid),
            _float0_like(token_slot))


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnames=("act", "bf", "interpret"))
def _fused_jit(x, w_in, w_gate, w_out, topk_w, keep, slot_token,
               slot_valid, token_slot, act, bf, interpret):
    s = slot_token.shape[0]
    # weights folded INSIDE the jit so gradients reach topk_w (router), and
    # capacity/validity drops (keep) zero their contribution — the fused
    # analogue of _combine_jit's `w = weights * keep`
    wcomb = (topk_w * keep).astype(jnp.float32)
    st = slot_token.astype(jnp.int32)
    sv = slot_valid
    ts = jnp.clip(token_slot, 0, s - 1).astype(jnp.int32)
    xw = x.astype(w_in.dtype)
    y = _fused(xw, w_in, w_gate, w_out, wcomb, st, sv, ts, act, bf,
               interpret)
    return y.astype(x.dtype)


def fused_moe_ffn(x: jax.Array, w_in: jax.Array, w_gate: Optional[jax.Array],
                  w_out: jax.Array, topk_w: jax.Array,
                  keep: jax.Array, slot_token: jax.Array,
                  slot_valid: jax.Array, token_slot: jax.Array, *,
                  act: str = "silu", bf: int = 512,
                  interpret: Optional[bool] = None) -> jax.Array:
    """One-launch fused MoE layer: gather + expert FFN + weighted scatter.

    x: (T, d); w_in/w_gate: (E, d, f); w_out: (E, f, d);
    topk_w/keep: (T, k) routing weights and keep mask (keep already folds
    capacity, local validity, and token_valid — see DispatchInfo);
    slot_token/slot_valid: (E*C,), token_slot: (T, k) — the RoutingTables
    gather maps that drive the in-kernel gather/scatter and the VJP's
    slot-formulation backward. Returns (T, d) in x.dtype. interpret
    resolves BEFORE the jit boundary (force_interpret stays effective,
    like every kernel in this package).
    """
    return _fused_jit(x, w_in, w_gate, w_out, topk_w, keep,
                      slot_token, slot_valid, token_slot, act, bf,
                      resolve_interpret(interpret))
