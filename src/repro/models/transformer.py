"""Unified transformer assembly for every assigned architecture.

Layers are organised into SEGMENTS — contiguous repeats of a (possibly
multi-layer) pattern of LayerSpecs — and executed with ``jax.lax.scan``
over the stacked per-repeat parameters (MaxText-style). This keeps the
HLO size O(#segments), not O(#layers): essential for the 100-layer VLM
and 61-layer DeepSeek dry-runs on a 512-device mesh.

Modes:
  train   -- full sequence, logits for every position, MoE aux losses.
  prefill -- full sequence + returns a decode cache.
  decode  -- one token against the cache.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.moe import ParallelContext, init_moe_params, moe_apply
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as M
from repro.models import ssm as S

Params = Dict[str, Any]


def constrain(x: jax.Array, ctx, spec_dims) -> jax.Array:
    """Best-effort sharding constraint (no-op without an active mesh).
    spec_dims: tuple where 'dp'/'tp' resolve to mesh axes; None kept."""
    if ctx is None or not getattr(ctx, "active", False):
        return x
    import numpy as _np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = ctx.mesh
    dims = []
    for dim, s in zip(x.shape, spec_dims):
        if s == "dp":
            size = int(_np.prod([mesh.shape[a] for a in ctx.dp_axes]))
            dims.append(ctx.dp_axes if dim % size == 0 else None)
        elif s == "tp":
            tp = ctx.tp_axis if ctx.tp_axis in mesh.axis_names else None
            dims.append(tp if tp and dim % mesh.shape[tp] == 0 else None)
        else:
            dims.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*dims)))


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerSpec:
    mixer: str = "gqa"        # gqa | mla | ssm | hybrid | none (cross-only)
    cross: bool = False       # cross-attention sub-layer
    gated_cross: bool = False # VLM: tanh-gated cross-attn layer (no self-attn)
    moe: bool = False
    window: int = 0           # sliding window (0 = full)
    causal: bool = True


@dataclass(frozen=True)
class Segment:
    pattern: Tuple[LayerSpec, ...]
    repeats: int


def _compress(specs: List[LayerSpec]) -> List[Segment]:
    """Compress a per-layer spec list into segments: whole-list periodic
    pattern if one exists (period <= 8), else maximal identical runs."""
    n = len(specs)
    for p in range(1, 9):
        if n % p == 0 and n // p > 1:
            if all(specs[i] == specs[i % p] for i in range(n)):
                return [Segment(tuple(specs[:p]), n // p)]
    segs: List[Segment] = []
    i = 0
    while i < n:
        j = i
        while j < n and specs[j] == specs[i]:
            j += 1
        segs.append(Segment((specs[i],), j - i))
        i = j
    return segs


def layer_plan(cfg: ModelConfig, *, encoder: bool = False) -> List[Segment]:
    specs: List[LayerSpec] = []
    if encoder:
        assert cfg.encdec is not None
        for i in range(cfg.encdec.n_encoder_layers):
            specs.append(LayerSpec(
                mixer="gqa", causal=cfg.encdec.encoder_causal,
                moe=cfg.moe is not None and cfg.moe.is_moe_layer(i)))
        return _compress(specs)

    for i in range(cfg.n_layers):
        moe = cfg.moe is not None and cfg.moe.is_moe_layer(i)
        if cfg.family == "ssm":
            specs.append(LayerSpec(mixer="ssm", moe=moe))
        elif cfg.family == "hybrid":
            is_global = i in cfg.hybrid.global_attn_layers
            specs.append(LayerSpec(
                mixer="hybrid", moe=moe,
                window=0 if is_global else cfg.sliding_window))
        elif cfg.family == "encdec":
            specs.append(LayerSpec(mixer="gqa", cross=True, moe=moe))
        else:
            specs.append(LayerSpec(
                mixer="mla" if cfg.mla is not None else "gqa",
                moe=moe, window=cfg.sliding_window))
    if cfg.family == "vlm":
        v = cfg.vlm
        out: List[LayerSpec] = []
        for i, s in enumerate(specs):
            if i % v.cross_attn_period == 0:
                out.append(LayerSpec(mixer="none", gated_cross=True, cross=True))
            else:
                out.append(s)
        specs = out
    return _compress(specs)


def plan_layer_indices(segs: List[Segment]):
    """Yield (seg_idx, repeat, pos, global_layer_idx)."""
    g = 0
    for si, seg in enumerate(segs):
        for r in range(seg.repeats):
            for pi in range(len(seg.pattern)):
                yield si, r, pi, g
                g += 1


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_layer(key: jax.Array, spec: LayerSpec, cfg: ModelConfig,
                dtype, n_total: int) -> Params:
    ks = jax.random.split(key, 8)
    out_scale = (2 * max(n_total, 1)) ** -0.5
    p: Params = {}
    if spec.mixer != "none":
        p["ln1"] = L.init_norm(cfg, cfg.d_model, dtype)
    if spec.mixer == "gqa":
        p["attn"] = A.init_attn(ks[0], cfg, dtype, out_scale)
    elif spec.mixer == "mla":
        p["attn"] = M.init_mla(ks[0], cfg, dtype, out_scale)
    elif spec.mixer == "ssm":
        p["ssm"] = S.init_ssm(ks[1], cfg, dtype, out_scale)
    elif spec.mixer == "hybrid":
        p["attn"] = A.init_attn(ks[0], cfg, dtype, out_scale)
        p["ssm"] = S.init_ssm(ks[1], cfg, dtype, out_scale)
        p["mix_norm_attn"] = jnp.ones((cfg.d_model,), dtype)
        p["mix_norm_ssm"] = jnp.ones((cfg.d_model,), dtype)
    if spec.cross:
        kv_dim = None
        p["ln_cross"] = L.init_norm(cfg, cfg.d_model, dtype)
        p["cross"] = A.init_cross_attn(ks[2], cfg, dtype, kv_dim, out_scale)
        if spec.gated_cross:
            p["gate_attn"] = jnp.zeros((), dtype)
            p["gate_ffn"] = jnp.zeros((), dtype)
    p["ln2"] = L.init_norm(cfg, cfg.d_model, dtype)
    if spec.moe:
        p["moe"] = init_moe_params(ks[3], cfg, dtype=dtype)
        if cfg.moe.n_shared_experts > 0:
            dffs = cfg.moe.d_ff(cfg.d_ff) * cfg.moe.n_shared_experts
            p["shared"] = L.init_ffn(ks[4], cfg.d_model, dffs, cfg, dtype,
                                     out_scale)
    elif cfg.d_ff > 0 or spec.gated_cross:
        dff = cfg.d_ff if cfg.d_ff > 0 else 4 * cfg.d_model
        p["ffn"] = L.init_ffn(ks[4], cfg.d_model, dff, cfg, dtype, out_scale)
    return p


# ---------------------------------------------------------------------------
# per-layer cache init
# ---------------------------------------------------------------------------

def _init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                      max_seq: int, n_cross: int, dtype) -> Params:
    c: Params = {}
    if spec.mixer in ("gqa", "hybrid"):
        if spec.window > 0:
            c["attn"] = A.init_ring_cache(cfg, batch, spec.window, dtype)
        else:
            c["attn"] = A.init_kv_cache(cfg, batch, max_seq, dtype)
    elif spec.mixer == "mla":
        c["attn"] = M.init_mla_cache(cfg, batch, max_seq, dtype)
    if spec.mixer in ("ssm", "hybrid"):
        c["ssm"] = S.init_ssm_cache(cfg, batch, dtype)
    if spec.cross:
        h, hd = cfg.n_heads, cfg.head_dim_
        c["cross"] = {"k": jnp.zeros((batch, n_cross, h, hd), dtype),
                      "v": jnp.zeros((batch, n_cross, h, hd), dtype)}
    return c


# ---------------------------------------------------------------------------
# per-layer apply
# ---------------------------------------------------------------------------

def _moe_or_ffn(p: Params, spec: LayerSpec, h: jax.Array, cfg: ModelConfig,
                ctx, rng, decision, is_training, token_ids,
                token_valid=None):
    if spec.moe:
        y, aux = moe_apply(p["moe"], h, cfg, ctx, rng=rng, decision=decision,
                           is_training=is_training, token_ids=token_ids,
                           token_valid=token_valid)
        if "shared" in p:
            y = y + L.ffn_apply(p["shared"], h, cfg)
        return y, aux
    # shared zero-aux (core/moe.py) so every branch of every cond keeps
    # the same aux pytree keys — a locally-maintained copy would desync
    from repro.core.moe import _zero_aux
    zero = _zero_aux(cfg.moe.n_experts if cfg.moe is not None else 1)
    if "ffn" in p:
        return L.ffn_apply(p["ffn"], h, cfg), zero
    return jnp.zeros_like(h), zero


def _layer_apply(spec: LayerSpec, p: Params, x: jax.Array, cfg: ModelConfig,
                 ctx, *, mode: str, cache: Optional[Params],
                 index, rng, decision, is_training: bool,
                 cross_src: Optional[jax.Array], token_ids,
                 token_valid=None,
                 flash_decode: bool = False,
                 block_tables=None) -> Tuple[jax.Array,
                                             Optional[Params], Dict]:
    """One transformer layer. Returns (x, new_cache, aux)."""
    new_cache: Params = {}
    b, l, d = x.shape
    # ---- mixer (self-attention / ssm / hybrid) ----
    if spec.mixer != "none":
        h = L.norm_apply(p["ln1"], x, cfg)
        outs = []
        if spec.mixer in ("gqa", "hybrid"):
            with jax.named_scope("attention"):
                if mode == "decode":
                    # windowed layers keep their slot-addressed ring cache;
                    # only full-cache layers read through the page table
                    o, nc = A.decode_self_attention(
                        p["attn"], h, cache["attn"], cfg, index,
                        window=spec.window, flash=flash_decode,
                        block_tables=(None if spec.window > 0
                                      else block_tables))
                    new_cache["attn"] = nc
                else:
                    q, k, v = A.attn_qkv(p["attn"], h)
                    pos = jnp.arange(l)
                    q = L.apply_rope(q, pos, cfg.rope_theta)
                    k = L.apply_rope(k, pos, cfg.rope_theta)
                    if (cfg.banded_swa and spec.window > 0 and spec.causal
                            and l > 2 * spec.window):
                        from repro.models.flash import banded_flash_attention
                        qc = 1024 if l % 1024 == 0 or l > 4096 else 512
                        o = banded_flash_attention(
                            q, k, v, spec.window, q_chunk=qc, kv_chunk=512,
                            use_full=not cfg.scan_layers)
                    else:
                        o = A.flash_attention(q, k, v, causal=spec.causal,
                                              window=spec.window)
                    o = A.attn_out(p["attn"], o, x.dtype)
                    if mode == "prefill":
                        new_cache["attn"] = _fill_kv_cache(
                            spec, cfg, cache["attn"], k, v)
            outs.append(o)
        if spec.mixer == "mla":
            with jax.named_scope("attention"):
                if mode == "decode":
                    o, nc = M.mla_decode(p["attn"], h, cache["attn"], cfg,
                                         index, block_tables=block_tables)
                    new_cache["attn"] = nc
                else:
                    o, (c_kv, k_rope) = M.mla_attention(p["attn"], h, cfg,
                                                        return_cache=True)
                    if mode == "prefill":
                        smax = cache["attn"]["c_kv"].shape[1]
                        cdt = cache["attn"]["c_kv"].dtype
                        new_cache["attn"] = {
                            "c_kv": _pad_to(c_kv.astype(cdt), smax, 1),
                            "k_rope": _pad_to(k_rope.astype(cdt), smax, 1),
                        }
            outs.append(o)
        if spec.mixer in ("ssm", "hybrid"):
            if mode == "decode":
                o, nc = S.ssm_decode(p["ssm"], h, cache["ssm"], cfg)
                new_cache["ssm"] = nc
            else:
                o = S.ssm_apply(p["ssm"], h, cfg)
                if mode == "prefill":
                    new_cache["ssm"] = _fill_ssm_cache(p["ssm"], h, cfg)
            outs.append(o)
        if spec.mixer == "hybrid":
            oa = _rms_scale(outs[0], p["mix_norm_attn"])
            os_ = _rms_scale(outs[1], p["mix_norm_ssm"])
            mixed = 0.5 * (oa + os_)
        else:
            mixed = outs[0]
        x = x + mixed
    # ---- cross attention ----
    if spec.cross:
        h = L.norm_apply(p["ln_cross"] if "ln_cross" in p else p["ln1"], x, cfg)
        with jax.named_scope("attention"):
            if mode == "decode" or cross_src is None:
                ck, cv = cache["cross"]["k"], cache["cross"]["v"]
            else:
                ck, cv = A.make_cross_kv(p["cross"], cross_src)
                if mode == "prefill":
                    cdt = cache["cross"]["k"].dtype
                    new_cache["cross"] = {"k": ck.astype(cdt),
                                          "v": cv.astype(cdt)}
            o = A.cross_attention_kv(p["cross"], h, ck, cv)
            if spec.gated_cross:
                gate = jnp.tanh(p["gate_attn"].astype(jnp.float32))
                o = gate.astype(o.dtype) * o
        x = x + o
        if mode in ("prefill", "decode") and "cross" not in new_cache:
            new_cache["cross"] = cache["cross"]   # carried through unchanged
    # ---- FFN / MoE ----
    h = L.norm_apply(p["ln2"], x, cfg)
    y, aux = _moe_or_ffn(p, spec, h, cfg, ctx, rng, decision, is_training,
                         token_ids, token_valid)
    if spec.gated_cross:
        y = jnp.tanh(p["gate_ffn"].astype(jnp.float32)).astype(y.dtype) * y
    x = x + y
    return x, (new_cache if mode in ("prefill", "decode") else None), aux


def _rms_scale(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _pad_to(x, n, axis):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad)


def _fill_kv_cache(spec: LayerSpec, cfg: ModelConfig, cache, k, v):
    b, l = k.shape[0], k.shape[1]
    if spec.window > 0 and cache["k"].shape[1] == spec.window:
        w = spec.window
        if l >= w:
            kk, vv = k[:, l - w:], v[:, l - w:]
            pos = jnp.arange(l - w, l, dtype=jnp.int32)
        else:
            kk, vv = _pad_to(k, w, 1), _pad_to(v, w, 1)
            pos = jnp.where(jnp.arange(w) < l, jnp.arange(w), -1).astype(jnp.int32)
        # ring layout: slot = pos % w
        slots = jnp.where(pos >= 0, pos % w, jnp.arange(w))
        ck = jnp.zeros_like(cache["k"]).at[:, slots].set(kk.astype(cache["k"].dtype))
        cv = jnp.zeros_like(cache["v"]).at[:, slots].set(vv.astype(cache["v"].dtype))
        cpos = jnp.full((w,), -1, jnp.int32).at[slots].set(pos)
        return {"k": ck, "v": cv, "pos": cpos}
    smax = cache["k"].shape[1]
    return {"k": _pad_to(k.astype(cache["k"].dtype), smax, 1),
            "v": _pad_to(v.astype(cache["v"].dtype), smax, 1)}


def _fill_ssm_cache(prm, h, cfg: ModelConfig):
    """Recompute the SSM final state for the prefix (prefill)."""
    s = cfg.ssm
    b, l, d = h.shape
    din = s.d_inner(d)
    nh = din // s.head_dim
    gn = s.n_groups * s.d_state
    xc = h.astype(prm["w_z"].dtype)
    xbc = jnp.concatenate([xc @ prm["w_x"], xc @ prm["w_B"], xc @ prm["w_C"]], -1)
    conv_tail = xbc[:, -(s.conv_kernel - 1):]
    if l < s.conv_kernel - 1:
        conv_tail = jnp.pad(xbc, ((0, 0), (s.conv_kernel - 1 - l, 0), (0, 0)))
    xbc_c = jax.nn.silu(S._causal_conv(xbc, prm["conv_w"], prm["conv_b"]))
    xs = xbc_c[..., :din].reshape(b, l, nh, s.head_dim)
    bs = xbc_c[..., din:din + gn].reshape(b, l, s.n_groups, s.d_state)
    cs = xbc_c[..., din + gn:].reshape(b, l, s.n_groups, s.d_state)
    dt = jax.nn.softplus((xc @ prm["w_dt"]).astype(jnp.float32)
                         + prm["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(prm["A_log"].astype(jnp.float32))
    pad = (-l) % s.chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bs = jnp.pad(bs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cs = jnp.pad(cs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    _, hfin = S.ssd_chunked(xs, dt, a, bs, cs, s.chunk)
    return {"conv": conv_tail, "h": hfin}


# ---------------------------------------------------------------------------
# stack init / apply
# ---------------------------------------------------------------------------

def init_stack(key: jax.Array, segs: List[Segment], cfg: ModelConfig,
               dtype, n_total: int) -> List[Params]:
    params: List[Params] = []
    for si, seg in enumerate(segs):
        seg_p: Params = {}
        for pi, spec in enumerate(seg.pattern):
            kk = jax.random.fold_in(key, si * 100 + pi)
            keys = jax.random.split(kk, seg.repeats)
            seg_p[f"p{pi}"] = jax.vmap(
                lambda k: _init_layer(k, spec, cfg, dtype, n_total))(keys)
        params.append(seg_p)
    return params


def init_stack_cache(segs: List[Segment], cfg: ModelConfig, batch: int,
                     max_seq: int, n_cross: int, dtype) -> List[Params]:
    caches: List[Params] = []
    for seg in segs:
        seg_c: Params = {}
        for pi, spec in enumerate(seg.pattern):
            one = _init_layer_cache(spec, cfg, batch, max_seq, n_cross, dtype)
            seg_c[f"p{pi}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (seg.repeats,) + a.shape), one)
        caches.append(seg_c)
    return caches


def apply_stack(params: List[Params], segs: List[Segment], x: jax.Array,
                cfg: ModelConfig, ctx, *, mode: str,
                caches: Optional[List[Params]] = None,
                index=None, rng=None, decision=None, is_training=True,
                cross_src=None, token_ids=None, token_valid=None,
                flash_decode=False, block_tables=None):
    """Run all segments. Returns (x, new_caches, aux_sum)."""
    new_caches: List[Params] = []
    aux_total = None
    layer_base = 0

    for si, (seg, seg_p) in enumerate(zip(segs, params)):
        npat = len(seg.pattern)

        def pattern_body(x_in, slice_p, slice_c, rep_idx):
            nc_out: Params = {}
            aux_acc = None
            h = x_in
            for pi, spec in enumerate(seg.pattern):
                lrng = (None if rng is None else
                        jax.random.fold_in(rng, layer_base + rep_idx * npat + pi))
                h, nc, aux = _layer_apply(
                    spec, slice_p[f"p{pi}"], h, cfg, ctx, mode=mode,
                    cache=None if slice_c is None else slice_c[f"p{pi}"],
                    index=index, rng=lrng, decision=decision,
                    is_training=is_training, cross_src=cross_src,
                    token_ids=token_ids, token_valid=token_valid,
                    flash_decode=flash_decode, block_tables=block_tables)
                if nc is not None:
                    nc_out[f"p{pi}"] = nc
                aux_acc = aux if aux_acc is None else jax.tree.map(
                    jnp.add, aux_acc, aux)
            return h, nc_out, aux_acc

        if cfg.remat and mode == "train":
            pattern_body = jax.checkpoint(
                pattern_body, static_argnums=(), policy=None)

        seg_c = None if caches is None else caches[si]

        def scan_body(carry, xs):
            x_c = carry
            if cfg.seq_parallel and mode == "train":
                # Megatron-style sequence parallelism: layer-boundary (and
                # remat-saved) activations sharded over the model axis.
                x_c = constrain(x_c, ctx, ("dp", "tp", None))
            if seg_c is not None:
                sp, sc, ri = xs
            else:
                sp, ri = xs
                sc = None
            h, nc, aux = pattern_body(x_c, sp, sc, ri)
            return h, (nc, aux)

        reps = jnp.arange(seg.repeats)
        xs = (seg_p, caches[si], reps) if seg_c is not None else (seg_p, reps)
        if cfg.scan_layers:
            x, (ncs, auxs) = jax.lax.scan(scan_body, x, xs)
        else:
            # unrolled (exact XLA cost_analysis: scan bodies are counted
            # once, not x trip-count — the dry-run unrolls for true costs)
            ys = []
            for r in range(seg.repeats):
                xs_r = jax.tree.map(lambda a: a[r], xs)
                x, y_r = scan_body(x, xs_r)
                ys.append(y_r)
            ncs, auxs = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        if mode in ("prefill", "decode"):
            new_caches.append(ncs)
        aux_sum = jax.tree.map(lambda a: a.sum(0), auxs)
        aux_total = aux_sum if aux_total is None else jax.tree.map(
            jnp.add, aux_total, aux_sum)
        layer_base += seg.repeats * npat

    return x, (new_caches if mode in ("prefill", "decode") else None), aux_total
