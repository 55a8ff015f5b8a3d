"""Partition-spec derivation for params / optimizer state / batches / caches.

Axes (DESIGN.md §4):
  data  -- batch sharding AND expert parallelism (EP group == DP group)
  model -- tensor parallelism (heads, d_ff, vocab)
  pod   -- extra pure data parallelism (multi-pod)

Rules are name-based over the pytree paths produced by the model inits.
A dimension is sharded over an axis only when divisible by its size;
otherwise it is replicated on that axis (keeps every (arch x mesh)
combination lowerable, e.g. 25 heads on a 16-way model axis).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.moe import ParallelContext


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


class SpecBuilder:
    def __init__(self, cfg: ModelConfig, ctx: ParallelContext):
        self.cfg = cfg
        self.ctx = ctx
        self.mesh = ctx.mesh
        self.tp = ctx.tp_axis if ctx.tp_axis in self.mesh.axis_names else None
        self.ep = ctx.ep_axis
        self.dp = ctx.dp_axes  # ("pod","data") or ("data",)

    def div(self, axis, size: int):
        """axis if it divides size, else None."""
        if axis is None:
            return None
        return axis if size % _axis_size(self.mesh, axis) == 0 else None

    def fsdp(self, size: int):
        if not self.cfg.fsdp:
            return None
        return self.div(self.ep, size)

    # ---- parameter rules ---------------------------------------------------
    # keyed by (leaf name, in-experts?); each rule states its BASE ndim so a
    # stacked (per-segment) leaf with one extra leading repeats dim is
    # disambiguated correctly (e.g. expert w_in (E,d,f) vs dense w_in (d,f)).
    def param_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
        name = path[-1]
        in_experts = "experts" in path
        in_router = "router" in path
        b = self
        tp = self.tp
        if in_router:
            return P() if len(shape) <= 2 else P(None)

        def rule(name, in_experts):
            """-> (base_ndim, fn(shape)->P) or None"""
            if in_experts:
                if self.cfg.moe is not None and self.cfg.moe.ep_on_model \
                        and tp is not None:
                    eaxes = (self.ep, tp)   # EP over data x model, no TP
                    if name in ("w_in", "w_gate"):
                        return 3, lambda s: P(b.div(eaxes, s[0]), None, None)
                    if name == "w_out":
                        return 3, lambda s: P(b.div(eaxes, s[0]), None, None)
                    return None
                if name in ("w_in", "w_gate"):
                    return 3, lambda s: P(b.div(b.ep, s[0]), None, b.div(tp, s[2]))
                if name == "w_out":
                    return 3, lambda s: P(b.div(b.ep, s[0]), b.div(tp, s[1]), None)
                return None
            table = {
                "wq":   (3, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]), None)),
                "wk":   (3, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]), None)),
                "wv":   (3, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]), None)),
                "wo":   (3, lambda s: P(b.div(tp, s[0]), None, b.fsdp(s[2]))),
                "w_in": (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_gate": (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_out": (2, lambda s: P(b.div(tp, s[0]), b.fsdp(s[1]))),
                "w_dq": (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_uq": (3, lambda s: P(None, b.div(tp, s[1]), None)),
                "w_dkv": (2, lambda s: P(b.fsdp(s[0]), None)),
                "w_ukv": (3, lambda s: P(None, b.div(tp, s[1]), None)),
                "w_z":  (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_x":  (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_B":  (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_C":  (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "w_dt": (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "conv_w": (2, lambda s: P(None, b.div(tp, s[1]))),
                "embed": (2, lambda s: P(b.div(tp, s[0]), None)),
                "lm_head": (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
                "img_proj": (2, lambda s: P(None, b.div(tp, s[1]))),
                "proj": (2, lambda s: P(b.fsdp(s[0]), b.div(tp, s[1]))),
            }
            return table.get(name)

        r = rule(name, in_experts)
        if r is None:
            return P()  # norms, scalars, biases, A_log, D, meta, ...
        base_ndim, fn = r
        if len(shape) == base_ndim:
            return fn(shape)
        if len(shape) == base_ndim + 1:       # stacked over segment repeats
            return P(None, *fn(shape[1:]))
        return P()

    # ---- cache rules -------------------------------------------------------
    def cache_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
        """Cache leaves are stacked: (repeats, B, ...). Prefer batch sharding;
        fall back to sequence sharding over `data` for batch=1 decode."""
        name = path[-1]
        if name == "pos":                         # (repeats, W)
            return P()
        if len(shape) < 3:
            return P()
        bdim = shape[1]
        dp = self.dp if bdim % _axis_size(self.mesh, self.dp) == 0 else None
        if name in ("k", "v"):                    # (r, B, S, KV, hd)
            seq = None if dp is not None else self.div(self.ep, shape[2])
            return P(None, dp, seq, self.div(self.tp, shape[3]), None)
        if name == "c_kv":                        # (r, B, S, c)
            seq = None if dp is not None else self.div(self.ep, shape[2])
            return P(None, dp, seq, None)
        if name == "k_rope":                      # (r, B, S, dr)
            seq = None if dp is not None else self.div(self.ep, shape[2])
            return P(None, dp, seq, None)
        if name == "conv":                        # (r, B, k, ch)
            return P(None, dp, None, self.div(self.tp, shape[3]))
        if name == "h":                           # (r, B, H, P, N)
            return P(None, dp, self.div(self.tp, shape[2]), None, None)
        return P(None, dp) if dp else P()

    # ---- batch rules -------------------------------------------------------
    def batch_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
        bdim = shape[0]
        dp = self.dp if bdim % _axis_size(self.mesh, self.dp) == 0 else None
        return P(dp, *([None] * (len(shape) - 1)))


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def tree_specs(tree_shape: Any, fn) -> Any:
    """Map (path names, shape) -> spec over a ShapeDtypeStruct tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(_path_names(path), leaf.shape), tree_shape)


def param_specs(cfg: ModelConfig, ctx: ParallelContext, params_shape) -> Any:
    return tree_specs(params_shape, SpecBuilder(cfg, ctx).param_spec)


def state_specs(cfg: ModelConfig, ctx: ParallelContext, state_shape) -> Any:
    b = SpecBuilder(cfg, ctx)
    ps = tree_specs(state_shape["params"], b.param_spec)
    return {
        "params": ps,
        "opt": {
            "m": tree_specs(state_shape["opt"]["m"], b.param_spec),
            "v": tree_specs(state_shape["opt"]["v"], b.param_spec),
            "step": P(),
        },
        "step": P(),
    }


def batch_specs(cfg: ModelConfig, ctx: ParallelContext, batch_shape) -> Any:
    return tree_specs(batch_shape, SpecBuilder(cfg, ctx).batch_spec)


def cache_specs(cfg: ModelConfig, ctx: ParallelContext, cache_shape) -> Any:
    return tree_specs(cache_shape, SpecBuilder(cfg, ctx).cache_spec)


def to_shardings(mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
