"""Top-level model: embeddings + (optional encoder) + decoder stack + head.

Public API:
  init_model(key, cfg)                          -> params
  model_apply(params, batch, cfg, ctx, ...)     -> (logits, aux)        [train]
  prefill(params, batch, cfg, ctx, max_seq)     -> (logits, caches)
  decode_step(params, caches, token, index,...) -> (logits, caches)
  init_cache(cfg, batch, max_seq, dtype)        -> caches

``batch`` keys: "tokens" (B, L) always; plus per family:
  vlm    : "img_embeds"  (B, n_img, d_image)   [stub vision encoder output]
  encdec : "frames" (B, S_enc, d_model) for audio (stub conv frontend), or
           "enc_tokens" (B, S_enc) for text enc-dec (the paper's MT models).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.moe import ParallelContext
from repro.models import layers as L
from repro.models import transformer as T

Params = Dict[str, Any]


from repro.models.transformer import constrain as _constrain


def _zero_aux(cfg: ModelConfig):
    from repro.core.moe import _zero_aux as moe_zero_aux
    return moe_zero_aux(cfg.moe.n_experts if cfg.moe is not None else 1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_model(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    segs = T.layer_plan(cfg)
    n_total = cfg.n_layers + (cfg.encdec.n_encoder_layers if cfg.encdec else 0)
    ks = jax.random.split(key, 10)
    p: Params = {
        "embed": L.init_embed(ks[0], cfg.vocab, cfg.d_model, dtype),
        "decoder": T.init_stack(ks[1], segs, cfg, dtype, n_total),
        "final_norm": L.init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            ks[2], (cfg.d_model, cfg.vocab), dtype) * (cfg.d_model ** -0.5)
    if cfg.encdec is not None:
        enc_segs = T.layer_plan(cfg, encoder=True)
        p["encoder"] = T.init_stack(ks[3], enc_segs, cfg, dtype, n_total)
        p["enc_final_norm"] = L.init_norm(cfg, cfg.d_model, dtype)
    if cfg.vlm is not None:
        p["img_proj"] = jax.random.normal(
            ks[4], (cfg.vlm.d_image, cfg.d_model), dtype) * (cfg.vlm.d_image ** -0.5)
    if cfg.hybrid is not None:
        p["meta"] = jax.random.normal(
            ks[5], (cfg.hybrid.n_meta_tokens, cfg.d_model), dtype) * 0.02
    if cfg.mtp:
        spec = T.LayerSpec(mixer="mla" if cfg.mla is not None else "gqa",
                           moe=False)
        p["mtp"] = {
            "proj": jax.random.normal(ks[6], (2 * cfg.d_model, cfg.d_model),
                                      dtype) * ((2 * cfg.d_model) ** -0.5),
            "norm_h": L.init_norm(cfg, cfg.d_model, dtype),
            "norm_e": L.init_norm(cfg, cfg.d_model, dtype),
            "block": T._init_layer(ks[7], spec, cfg, dtype, n_total),
            "norm_out": L.init_norm(cfg, cfg.d_model, dtype),
        }
    return p


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

def _encode(params: Params, batch: Dict, cfg: ModelConfig, ctx, *,
            rng, decision, is_training, enc_embed=None):
    """``enc_embed``: the encoder tokens' embedding rows when the caller
    already looked them up (``model_apply``)."""
    enc_segs = T.layer_plan(cfg, encoder=True)
    if "frames" in batch:                      # audio stub frontend output
        x = batch["frames"].astype(cfg.dtype)
        x = x + L.sinusoidal_pos(x.shape[1], cfg.d_model, x.dtype)[None]
        tok = None
    else:
        tok = batch["enc_tokens"]
        if enc_embed is None:
            enc_embed = L.embed_apply(params["embed"], tok)
        x = enc_embed.astype(cfg.dtype)
        x = x + L.sinusoidal_pos(x.shape[1], cfg.d_model, x.dtype)[None]
    x, _, aux = T.apply_stack(params["encoder"], enc_segs, x, cfg, ctx,
                              mode="train", rng=rng, decision=decision,
                              is_training=is_training, token_ids=tok)
    return L.norm_apply(params["enc_final_norm"], x, cfg), aux


def _cross_source(params: Params, batch: Dict, cfg: ModelConfig, ctx, *,
                  rng, decision, is_training, enc_embed=None):
    """Returns (cross_src, aux) for families that cross-attend."""
    if cfg.encdec is not None:
        return _encode(params, batch, cfg, ctx, rng=rng, decision=decision,
                       is_training=is_training, enc_embed=enc_embed)
    if cfg.vlm is not None:
        img = batch["img_embeds"].astype(cfg.dtype)
        return (img.astype(params["img_proj"].dtype) @ params["img_proj"]
                ).astype(cfg.dtype), None
    return None, None


# ---------------------------------------------------------------------------
# forward (train) / prefill / decode
# ---------------------------------------------------------------------------

def _logits(params: Params, x: jax.Array, cfg: ModelConfig,
            ctx: Optional[ParallelContext] = None) -> jax.Array:
    x = x.astype(jnp.dtype(cfg.param_dtype))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    # keep logits vocab-sharded over `model`: the (B, L, V) f32 tensor is by
    # far the largest activation for big-vocab archs
    return _constrain(logits, ctx, ("dp", None, "tp"))


def model_apply(params: Params, batch: Dict, cfg: ModelConfig,
                ctx: Optional[ParallelContext] = None, *,
                rng: Optional[jax.Array] = None, decision=None,
                is_training: bool = True,
                return_hidden: bool = False) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward, logits for every position.

    ``return_hidden=True`` returns the final-norm hidden states instead of
    logits (the training loss computes a CHUNKED cross-entropy so the full
    (B, L, V) f32 logits tensor never materializes)."""
    tokens = batch["tokens"]
    segs = T.layer_plan(cfg)
    enc_embed = None
    if cfg.encdec is not None and "enc_tokens" in batch:
        # both streams share the table: look them up in ONE gather. Two
        # gathers give two gradient scatters that XLA merges into one
        # concatenated scatter, which the TPU partitioner re-shards with
        # all-to-alls, in the Gate-Drop step too
        n_enc = batch["enc_tokens"].shape[1]
        both = L.embed_apply(params["embed"], jnp.concatenate(
            [batch["enc_tokens"], tokens], axis=1))
        enc_embed, x = both[:, :n_enc], both[:, n_enc:]
    else:
        x = L.embed_apply(params["embed"], tokens)
    x = _constrain(x.astype(cfg.dtype), ctx, ("dp", None, None))
    n_meta = 0
    if cfg.hybrid is not None:
        n_meta = cfg.hybrid.n_meta_tokens
        meta = jnp.broadcast_to(params["meta"].astype(cfg.dtype)[None],
                                (x.shape[0],) + params["meta"].shape)
        x = jnp.concatenate([meta, x], axis=1)
    cross_src, enc_aux = _cross_source(params, batch, cfg, ctx, rng=rng,
                                       decision=decision,
                                       is_training=is_training,
                                       enc_embed=enc_embed)
    x, _, aux = T.apply_stack(params["decoder"], segs, x, cfg, ctx,
                              mode="train", rng=rng, decision=decision,
                              is_training=is_training, cross_src=cross_src,
                              token_ids=tokens if n_meta == 0 else None)
    if n_meta:
        x = x[:, n_meta:]
    x = L.norm_apply(params["final_norm"], x, cfg)
    if enc_aux is not None:
        aux = jax.tree.map(jnp.add, aux, enc_aux)
    if cfg.mtp and is_training:
        aux = dict(aux)
        aux["mtp_hidden"] = _mtp_hidden(params, x, tokens, cfg, ctx, rng,
                                        decision, is_training)
    if return_hidden:
        return x, aux
    return _logits(params, x, cfg, ctx), aux


def _mtp_hidden(params, h, tokens, cfg, ctx, rng, decision, is_training):
    """DeepSeek-V3 multi-token prediction (depth 1): predict token t+2 from
    the main trunk state at t and the embedding of token t+1. Returns the
    MTP hidden states (head applied chunked in the loss)."""
    mtp = params["mtp"]
    emb_next = L.embed_apply(params["embed"],
                             jnp.roll(tokens, -1, axis=1)).astype(cfg.dtype)
    hh = L.norm_apply(mtp["norm_h"], h, cfg)
    ee = L.norm_apply(mtp["norm_e"], emb_next, cfg)
    z = jnp.concatenate([hh, ee], axis=-1)
    z = (z.astype(mtp["proj"].dtype) @ mtp["proj"]).astype(cfg.dtype)
    spec = T.LayerSpec(mixer="mla" if cfg.mla is not None else "gqa", moe=False)
    z, _, _ = T._layer_apply(spec, mtp["block"], z, cfg, ctx, mode="train",
                             cache=None, index=None, rng=rng,
                             decision=decision, is_training=is_training,
                             cross_src=None, token_ids=None)
    return L.norm_apply(mtp["norm_out"], z, cfg)


def head_matrix(params: Params, cfg: ModelConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> List[Params]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    segs = T.layer_plan(cfg)
    n_meta = cfg.hybrid.n_meta_tokens if cfg.hybrid is not None else 0
    n_cross = 0
    if cfg.encdec is not None:
        n_cross = cfg.encdec.encoder_seq
    elif cfg.vlm is not None:
        n_cross = cfg.vlm.n_image_tokens
    return T.init_stack_cache(segs, cfg, batch, max_seq + n_meta, n_cross,
                              dtype)


def prefill(params: Params, batch: Dict, cfg: ModelConfig,
            ctx: Optional[ParallelContext] = None, *,
            max_seq: Optional[int] = None,
            rng: Optional[jax.Array] = None,
            last_index: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, List[Params]]:
    """``last_index`` (B,) selects the per-row position whose logits are
    returned instead of the default last column — the bucketed-prefill path
    (serve/scheduler.py) right-pads prompts to a shared length and reads
    each row's logits at its true last prompt token. Causal masking keeps
    positions < last_index[b] independent of the padding."""
    tokens = batch["tokens"]
    b, l = tokens.shape
    max_seq = max_seq or cfg.max_seq
    segs = T.layer_plan(cfg)
    caches = init_cache(cfg, b, max_seq)
    x = L.embed_apply(params["embed"], tokens).astype(cfg.dtype)
    n_meta = 0
    if cfg.hybrid is not None:
        n_meta = cfg.hybrid.n_meta_tokens
        meta = jnp.broadcast_to(params["meta"].astype(cfg.dtype)[None],
                                (b,) + params["meta"].shape)
        x = jnp.concatenate([meta, x], axis=1)
    cross_src, _ = _cross_source(params, batch, cfg, ctx, rng=rng,
                                 decision=False, is_training=False)
    x, caches, _ = T.apply_stack(params["decoder"], segs, x, cfg, ctx,
                                 mode="prefill", caches=caches, rng=rng,
                                 decision=False, is_training=False,
                                 cross_src=cross_src,
                                 token_ids=tokens if n_meta == 0 else None)
    if n_meta:
        x = x[:, n_meta:]
    x = L.norm_apply(params["final_norm"], x, cfg)
    if last_index is not None:
        x_last = jnp.take_along_axis(
            x, last_index.astype(jnp.int32)[:, None, None], axis=1)
    else:
        x_last = x[:, -1:]
    return _logits(params, x_last, cfg, ctx), caches


def decode_step(params: Params, caches: List[Params], token: jax.Array,
                index, cfg: ModelConfig,
                ctx: Optional[ParallelContext] = None, *,
                rng: Optional[jax.Array] = None,
                local_routing: bool = False,
                token_valid: Optional[jax.Array] = None,
                flash_decode: bool = False,
                block_tables: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, List[Params]]:
    """token: (B, 1) int32; index: absolute position of this token — scalar,
    or (B,) for slot-pool decode where every row sits at its own position.
    Gating Dropout is off at inference (paper §3: p=0, no rescaling), but
    ``local_routing=True`` reuses its LOCAL routing path as a static
    decision: MoE tokens route within the local expert group only, so the
    sharded backend's decode executable contains no all-to-all (DESIGN.md
    §9). ``token_valid`` (B,) masks rows (retired/empty pool slots) out of
    expert-capacity competition. ``flash_decode=True`` routes full-cache
    attention reads through the kernels.flash_decode Pallas kernel.
    ``block_tables`` (B, n_blocks) switches full-length attention caches
    to paged (page-arena) addressing (DESIGN.md §13); positions in the
    table are META-INCLUSIVE logical positions — the same space as ``idx``
    below — so callers build tables over ``max_seq + n_meta`` positions."""
    segs = T.layer_plan(cfg)
    x = L.embed_apply(params["embed"], token).astype(cfg.dtype)
    n_meta = cfg.hybrid.n_meta_tokens if cfg.hybrid is not None else 0
    idx = index + n_meta
    if token_valid is not None and token_valid.ndim == 1:
        token_valid = token_valid[:, None]            # (B,) -> (B, L=1)
    x, caches, _ = T.apply_stack(params["decoder"], segs, x, cfg, ctx,
                                 mode="decode", caches=caches, index=idx,
                                 rng=rng, decision=bool(local_routing),
                                 is_training=False, token_ids=token,
                                 token_valid=token_valid,
                                 flash_decode=flash_decode,
                                 block_tables=block_tables)
    x = L.norm_apply(params["final_norm"], x, cfg)
    return _logits(params, x, cfg, ctx), caches
