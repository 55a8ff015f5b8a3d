"""Flash decode-attention Pallas kernel: one query token vs a long KV cache.

The decode shapes (decode_32k, long_500k) are memory-bound: the whole KV
cache streams HBM->VMEM once per step. Grid (B, S/bs) walks KV blocks of
all KV heads at once with a running online-softmax (m, l, acc) per head
in VMEM scratch; the GQA group's `rep` query heads share each KV block
read (the factor that makes GQA decode HBM-efficient). A block spans the
full (KV, hd) minor dims, which Mosaic accepts at any head count.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import fit_block, resolve_interpret

NEG_INF = -1e30


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bs: int, scale: float):
    # one grid step holds a (bs, KV, hd) block of every KV head: a block of
    # the full (KV, hd) minor dims is what Mosaic tiles without padding
    # rules, and the per-head loop below reads each head's (bs, hd) slice
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    live = pos <= idx_ref[bi]
    for g in range(k_ref.shape[2]):
        q = q_ref[0, g].astype(jnp.float32) * scale           # (rep, hd)
        k = k_ref[0, :, g, :].astype(jnp.float32)              # (bs, hd)
        v = v_ref[0, :, g, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(live, s, NEG_INF)                        # (rep, bs)
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))  # (rep, 1)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[g] = l_ref[g] * corr + p.sum(-1, keepdims=True)
        acc_ref[g] = acc_ref[g] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[g] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _scratch(kv: int, rep: int, hd: int):
    return [pltpu.VMEM((kv, rep, 1), jnp.float32),
            pltpu.VMEM((kv, rep, 1), jnp.float32),
            pltpu.VMEM((kv, rep, hd), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def _flash_decode_jit(q, k, v, index, bs, interpret):
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    rep = h // kv
    bs = fit_block(s, bs)
    qg = q.reshape(b, kv, rep, hd)
    grid = (b, s // bs)
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kv, rep, hd), lambda bi, j, idx: (bi, 0, 0, 0)),
                pl.BlockSpec((1, bs, kv, hd), lambda bi, j, idx: (bi, j, 0, 0)),
                pl.BlockSpec((1, bs, kv, hd), lambda bi, j, idx: (bi, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, kv, rep, hd),
                                   lambda bi, j, idx: (bi, 0, 0, 0)),
            scratch_shapes=_scratch(kv, rep, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, hd), q.dtype),
        interpret=interpret,
    )(jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
      qg, k, v)
    return out.reshape(b, h, hd)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, index: jax.Array,
                 *, bs: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, hd); k, v: (B, S, KV, hd); index: scalar int32 OR (B,) —
    positions > index (per row) are masked; the per-row form serves the
    slot-pool decode path where every request sits at its own depth
    (DESIGN.md §9). Returns (B, H, hd). interpret=None -> platform
    (resolved before the jit boundary so the cached executable is keyed on
    the concrete mode)."""
    return _flash_decode_jit(q, k, v, index, bs, resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# paged variant: block-table gather in the kernel prologue (DESIGN.md §13)
# ---------------------------------------------------------------------------

def _paged_kernel(idx_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, ps: int, scale: float):
    # bt_ref is consumed by the BlockSpec index maps: grid step (b, j)
    # DMAs physical page bt[b, j] of the arena into VMEM, so the kernel
    # body is the plain online-softmax update over one page — logical
    # position j*ps + i maps 1:1 onto the slot-row kernel's j*bs + i.
    del bt_ref
    _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            bs=ps, scale=scale)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flash_decode_paged_jit(q, k, v, block_tables, index, interpret):
    b, h, hd = q.shape
    ps, kv = k.shape[1], k.shape[2]
    nb = block_tables.shape[1]
    rep = h // kv
    qg = q.reshape(b, kv, rep, hd)
    grid = (b, nb)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kv, rep, hd),
                             lambda bi, j, idx, bt: (bi, 0, 0, 0)),
                pl.BlockSpec((1, ps, kv, hd),
                             lambda bi, j, idx, bt: (bt[bi * nb + j], 0, 0, 0)),
                pl.BlockSpec((1, ps, kv, hd),
                             lambda bi, j, idx, bt: (bt[bi * nb + j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, kv, rep, hd),
                                   lambda bi, j, idx, bt: (bi, 0, 0, 0)),
            scratch_shapes=_scratch(kv, rep, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, hd), q.dtype),
        interpret=interpret,
    )(jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (b,)),
      # flat (B*nb,) table: SMEM pads a 2-D table's minor dim to 128 words
      jnp.asarray(block_tables, jnp.int32).reshape(-1), qg, k, v)
    return out.reshape(b, h, hd)


def flash_decode_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                       block_tables: jax.Array, index: jax.Array, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Paged flash decode: q (B, H, hd); k, v are the PHYSICAL PAGE ARENA
    (n_pages + 1, page_size, KV, hd); ``block_tables`` (B, n_blocks) int32
    maps each row's logical block j to its arena page; ``index`` (B,) is
    each row's absolute position. The table rides the scalar-prefetch
    channel, so the gather happens in the DMA prologue: grid step
    (b, j) fetches page ``block_tables[b, j]`` of every KV head — no
    materialized per-row contiguous copy. Masking is the same
    ``pos <= index`` predicate as the slot-row kernel with logical
    ``pos = j * page_size + offset``, so pages past a row's depth
    (scratch page, shared-tail bytes) contribute exact-zero probability.
    The KV block equals one page; its minor (KV, hd) dims are whole, so
    any ``page_size`` tiles. Returns (B, H, hd)."""
    return _flash_decode_paged_jit(q, k, v, block_tables, index,
                                   resolve_interpret(interpret))
