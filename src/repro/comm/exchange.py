"""The expert exchange (DESIGN.md §10): the all-to-all pair that carries
a routed MoE layer's capacity buffers to the shards holding their
experts and back.

  * ``dispatch``/``combine``   -- the real collectives, inside shard_map,
                                  under the ``exchange`` name scope;
  * ``vdispatch``/``vcombine`` -- the oracle backend's virtual emulation:
                                  the same permutation as transposes over
                                  the stacked (ep, E, cap, d) tensor, the
                                  reference the sharded path is tested
                                  against.

Telemetry: ``Exchange.telemetry`` returns the layer's all-to-all call
count, payload bytes and per-device wire bytes as in-graph constants,
computed by ``exchange_cost``, the same model the lint gate's
no-collectives pass and ``tests/test_comm.py`` hold equal to the
collectives parsed from compiled HLO. Its conventions match
``analysis/hlo.py::parse_collectives``:

  * ``calls``       -- number of all-to-all ops;
  * ``bytes``       -- sum over those ops of the per-device RESULT bytes
                       (an all-to-all keeps its element count, so this is
                       also the per-device send buffer);
  * ``wire_bytes``  -- per-device traffic on the wire: ``bytes *
                       (ep - 1) / ep`` per op (a device keeps its own
                       chunk).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

__all__ = ["Exchange", "combine", "comm_zero", "dispatch", "exchange_cost",
           "layer_cost", "routed_capacity", "step_cost", "vcombine",
           "vdispatch"]


def dispatch(buf: jax.Array, axis: Any) -> jax.Array:
    """Per shard (E, cap, ...) -> (E/ep, ep*cap, ...): each shard's slots
    for expert e go to the shard that holds e. ``axis`` is the shard_map
    axis name, or a tuple of them (the ep_on_model layout)."""
    with jax.named_scope("exchange"):
        return jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                  tiled=True)


def combine(buf: jax.Array, axis: Any) -> jax.Array:
    """The inverse of ``dispatch``: (E/ep, ep*cap, ...) -> (E, cap, ...)."""
    with jax.named_scope("exchange"):
        return jax.lax.all_to_all(buf, axis, split_axis=1, concat_axis=0,
                                  tiled=True)


def vdispatch(bufs: jax.Array) -> jax.Array:
    """``dispatch`` over the oracle's stacked virtual shards:
    (ep, E, cap, ...) -> (E, ep*cap, ...)."""
    ep, E = bufs.shape[:2]
    b = bufs.reshape((ep, ep, E // ep) + bufs.shape[2:])
    b = jnp.moveaxis(b, 0, 2)                      # (dst, e_loc, src, cap,..)
    return b.reshape((E, ep * bufs.shape[2]) + bufs.shape[3:])


def vcombine(buf: jax.Array, ep: int) -> jax.Array:
    """The inverse of ``vdispatch``: (E, ep*cap, ...) -> (ep, E, cap, ...)."""
    E = buf.shape[0]
    cap = buf.shape[1] // ep
    b = buf.reshape((ep, E // ep, ep, cap) + buf.shape[2:])
    b = jnp.moveaxis(b, 2, 0)                      # (src, dst, e_loc, cap,..)
    return b.reshape((ep, E, cap) + buf.shape[2:])


def exchange_cost(*, ep: int, n_experts: int, cap: int, d_model: int,
                  itemsize: int) -> Dict[str, float]:
    """Calls and bytes of ONE routed layer's exchange (dispatch + combine)
    per device. At ep=1 the group-of-one all-to-all moves nothing and XLA
    deletes it from the executable, so nothing is counted."""
    if ep == 1:
        return {"calls": 0.0, "bytes": 0.0, "wire_bytes": 0.0}
    b = float(n_experts * cap * d_model * itemsize)
    wire = b * (ep - 1) / ep
    return {"calls": 2.0, "bytes": 2 * b, "wire_bytes": 2 * wire}


def comm_zero() -> Dict[str, jax.Array]:
    """Telemetry of a step that moves nothing (Gate-Drop local /
    expert-drop / dense-FFN layers, and every layer at ep=1)."""
    return {"comm_a2a_calls": jnp.zeros((), jnp.float32),
            "comm_bytes": jnp.zeros((), jnp.float32),
            "comm_wire_bytes": jnp.zeros((), jnp.float32)}


@dataclasses.dataclass(frozen=True)
class Exchange:
    """One routed layer's exchange over ``ep`` shards on the shard_map
    axis ``axis`` (None for the oracle's virtual shards)."""
    ep: int
    axis: Any = None

    def pipelined(self, buf: jax.Array, fn: Callable) -> jax.Array:
        """dispatch -> ``fn`` (the grouped expert FFN) -> combine:
        (E, cap, d) -> (E/ep, ep*cap, d) -> (E, cap, d)."""
        return combine(fn(dispatch(buf, self.axis)), self.axis)

    def telemetry(self, n_experts: int, cap: int, d_model: int,
                  itemsize: int) -> Dict[str, jax.Array]:
        """The layer's counters as in-graph constants, from
        ``exchange_cost``."""
        c = exchange_cost(ep=self.ep, n_experts=n_experts, cap=cap,
                          d_model=d_model, itemsize=itemsize)
        return {"comm_a2a_calls": jnp.asarray(c["calls"], jnp.float32),
                "comm_bytes": jnp.asarray(c["bytes"], jnp.float32),
                "comm_wire_bytes": jnp.asarray(c["wire_bytes"],
                                               jnp.float32)}


def routed_capacity(cfg: ModelConfig, tokens_per_shard: int, *,
                    is_training: bool = True) -> int:
    """Per-shard expert buffer capacity of a routed step — the same
    formula every backend uses (core/moe.py::_routed_shard)."""
    from repro.core.router import capacity
    moe = cfg.moe
    cf = moe.capacity_factor if is_training else moe.eval_capacity_factor
    return min(capacity(tokens_per_shard, moe.n_experts, moe.top_k, cf),
               tokens_per_shard)


def layer_cost(cfg: ModelConfig, *, tokens_per_shard: int, ep: int,
               is_training: bool = True) -> Dict[str, float]:
    """Exchange cost of one routed MoE layer for a model config."""
    assert cfg.moe is not None
    return exchange_cost(
        ep=ep, n_experts=cfg.moe.n_experts,
        cap=routed_capacity(cfg, tokens_per_shard, is_training=is_training),
        d_model=cfg.d_model, itemsize=jnp.dtype(cfg.dtype).itemsize)


def step_cost(cfg: ModelConfig, *, tokens_per_shard: int, ep: int,
              is_training: bool = True,
              backward: bool = False) -> Dict[str, float]:
    """Exchange cost of one ROUTED model step: ``layer_cost`` x the
    number of MoE layers; ``backward=True`` doubles everything (the VJP
    of each all-to-all is the reverse all-to-all — exact when ``remat``
    is off; remat recomputes the forward inside the backward, adding one
    more forward's worth of collectives on top)."""
    from repro.training.steps import n_moe_layers
    per = layer_cost(cfg, tokens_per_shard=tokens_per_shard, ep=ep,
                     is_training=is_training)
    mult = n_moe_layers(cfg) * (2 if backward else 1)
    return {k: v * mult for k, v in per.items()}
