"""Seeded weights, made by the benchmark and handed to the program.

The weights are a canonical tree of per-layer dicts (``enc`` and ``dec``
lists, in layer order) with the leaf names of the program's layers, so
that the plain reference reads them as they are and the harness packs
them into the program's stacked layout (``train_cell.pack``). Each leaf
is drawn from its own key, folded from the seed and the leaf's path, in
one jitted call on the device, in float32 (the parameter type the
configurations state).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ModelSpec:
    """The sizes of an encoder-decoder sparse-expert configuration file."""
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_experts: int
    top_k: int
    moe_layer_period: int
    n_encoder_layers: int
    n_decoder_layers: int
    capacity_factor: float
    jitter_eps: float
    balance_coef: float
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, conf: Dict) -> "ModelSpec":
        return cls(**{k: conf[k] for k in cls.__dataclass_fields__})

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_moe(self, i: int) -> bool:
        return i % self.moe_layer_period == 0

    @property
    def n_moe_layers(self) -> int:
        return (sum(map(self.is_moe, range(self.n_encoder_layers)))
                + sum(map(self.is_moe, range(self.n_decoder_layers))))


Leaf = Tuple[Tuple[int, ...], Any]      # (shape, "ones" | "zeros" | std)


def _norm(d: int) -> Dict[str, Leaf]:
    return {"scale": ((d,), "ones"), "bias": ((d,), "zeros")}


def _attn(s: ModelSpec, out_scale: float) -> Dict[str, Leaf]:
    d, h, hd = s.d_model, s.n_heads, s.head_dim
    return {"wq": ((d, h, hd), d ** -0.5), "wk": ((d, h, hd), d ** -0.5),
            "wv": ((d, h, hd), d ** -0.5),
            "wo": ((h, hd, d), (h * hd) ** -0.5 * out_scale)}


def _layer(s: ModelSpec, i: int, cross: bool) -> Dict[str, Any]:
    d, f, e = s.d_model, s.d_ff, s.n_experts
    out_scale = (2 * (s.n_encoder_layers + s.n_decoder_layers)) ** -0.5
    p: Dict[str, Any] = {"ln1": _norm(d), "attn": _attn(s, out_scale)}
    if cross:
        p["ln_cross"] = _norm(d)
        p["cross"] = _attn(s, out_scale)
    p["ln2"] = _norm(d)
    if s.is_moe(i):
        p["moe"] = {"router": {"w": ((d, e), d ** -0.5)},
                    "experts": {"w_in": ((e, d, f), d ** -0.5),
                                "w_out": ((e, f, d), f ** -0.5)}}
    else:
        p["ffn"] = {"w_in": ((d, f), d ** -0.5),
                    "w_out": ((f, d), f ** -0.5 * out_scale)}
    return p


def layout(s: ModelSpec) -> Dict[str, Any]:
    """Canonical tree of (shape, init) leaves."""
    d, v = s.d_model, s.vocab
    return {
        "embed": ((v, d), d ** -0.5),
        "lm_head": ((d, v), d ** -0.5),
        "final_norm": _norm(d),
        "enc_final_norm": _norm(d),
        "enc": [_layer(s, i, False) for i in range(s.n_encoder_layers)],
        "dec": [_layer(s, i, True) for i in range(s.n_decoder_layers)],
    }


def is_leaf(x) -> bool:
    """A (shape, init) leaf of ``layout``."""
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for one use of ``seed`` (any non-negative int, 64 bits
    included): both 32-bit halves are folded in."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def leaf_names(tree: Any) -> List[str]:
    """Dotted path of each leaf of a canonical tree, in leaf order."""
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in paths]


def make_canonical(s: ModelSpec, key: jax.Array) -> Dict[str, Any]:
    """Traceable: the canonical weight tree from ``key``."""
    tree = layout(s)
    names = leaf_names(tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_leaf)
    out = []
    for name, (shape, init) in zip(names, leaves):
        if init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif init == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out.append(jax.random.normal(k, shape, jnp.float32) * init)
    return jax.tree_util.tree_unflatten(treedef, out)


def n_params(s: ModelSpec) -> int:
    leaves = jax.tree_util.tree_leaves(layout(s), is_leaf=is_leaf)
    total = 0
    for shape, _ in leaves:
        n = 1
        for x in shape:
            n *= x
        total += n
    return total
