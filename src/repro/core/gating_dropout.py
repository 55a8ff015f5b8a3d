"""Gating Dropout (Liu et al., ICML 2022) — the paper's core mechanism.

At each training iteration, with probability ``rate`` ALL machines skip the
MoE all-to-all and route tokens to their machine-local experts (Gate-Drop)
or skip the MoE sub-layer entirely (Gate-Expert-Drop).

Consensus. The paper appoints a coordinator rank that draws the Bernoulli
and broadcasts one bit per step. On TPU/JAX we use *deterministic consensus*
instead: every host folds the (replicated) training step into the same PRNG
seed — identical inputs give identical draws on every host, so consensus
costs zero communication and is bitwise reproducible. Documented in
DESIGN.md §2.

Execution strategies:
  traced_cond -- one executable; ``jax.lax.cond`` on a traced decision bit.
  host_cond   -- two executables (routed / dropped); the host draws the bit
                 and dispatches. The dropped executable contains NO
                 all-to-all at all (asserted in tests). Paper-faithful.

Inference: decision is constant False (p=0); no weight rescaling is needed
because Gating Dropout alters routing, not activation magnitudes (paper §3).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GatingDropoutConfig


def decision_key(seed: int, step: Union[int, jax.Array]) -> jax.Array:
    """The consensus PRNG key for a step (same on every host by construction)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x6A7E_D0), step)


def drop_decision(cfg: GatingDropoutConfig, seed: int,
                  step: Union[int, jax.Array], *,
                  is_training: bool = True) -> jax.Array:
    """Traced (or concrete) boolean: True => this step drops the all-to-all."""
    if not is_training or not cfg.enabled:
        return jnp.asarray(False)
    return jax.random.bernoulli(decision_key(seed, step), cfg.rate)


def drop_decision_host(cfg: GatingDropoutConfig, seed: int, step: int, *,
                       is_training: bool = True) -> bool:
    """Concrete python bool for the host_cond strategy (same draw as above)."""
    if not is_training or not cfg.enabled:
        return False
    return bool(jax.device_get(
        jax.random.bernoulli(decision_key(seed, step), cfg.rate)))


# steps per batched draw: every span is drawn in blocks of this length, so
# one compiled program serves spans of any length
DRAW_BLOCK = 256


@functools.partial(jax.jit, static_argnums=(3,))
def _decisions_batch(seed: jax.Array, start: jax.Array, rate: jax.Array,
                     n: int) -> jax.Array:
    key = jax.random.PRNGKey(seed ^ 0x6A7E_D0)
    return jax.vmap(
        lambda s: jax.random.bernoulli(jax.random.fold_in(key, s), rate)
    )(start + jnp.arange(n, dtype=jnp.int32))


def drop_decisions_host(cfg: Optional[GatingDropoutConfig], seed: int,
                        start: int, stop: int, *,
                        is_training: bool = True) -> np.ndarray:
    """Concrete bools for steps [start, stop), drawn on the device in
    blocks of ``DRAW_BLOCK`` steps and fetched by ONE ``device_get`` —
    bitwise the per-step ``drop_decision_host`` draws (same (seed, step)
    fold, vmapped). The scan-fused Trainer's host_cond path draws a whole
    ``run()``'s bits with it, before the first step; the fixed block
    length means a span of a new length compiles nothing. ``cfg`` None
    (no Gating Dropout) draws all False, as a disabled ``cfg`` does."""
    n = max(stop - start, 0)
    if not is_training or cfg is None or not cfg.enabled or n == 0:
        return np.zeros(n, bool)
    blocks = [_decisions_batch(seed, lo, cfg.rate, DRAW_BLOCK)
              for lo in range(start, stop, DRAW_BLOCK)]
    # explicit sync: drawing the bits host-side IS the strategy
    return np.concatenate(jax.device_get(blocks))[:n]


def expected_alltoall_fraction(cfg: GatingDropoutConfig) -> float:
    """Fraction of steps that still pay the all-to-all: 1 - p (both modes)."""
    return 1.0 - (cfg.rate if cfg.enabled else 0.0)


def expected_expert_flop_fraction(cfg: GatingDropoutConfig) -> float:
    """Fraction of expert FLOPs still paid. Gate-Expert-Drop also skips the
    expert computation on dropped steps (paper §3.1)."""
    if cfg.mode == "gate_expert_drop" and cfg.enabled:
        return 1.0 - cfg.rate
    return 1.0
