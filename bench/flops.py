"""Operations a training step requires, counted from shapes.

For one sentence pair with ``s`` source and ``t`` target positions that
are not padding, on an encoder-decoder of width ``d``, ``h`` heads of
size ``d / h``, feed-forward width ``f``, ``E`` experts (top-1) on every
``period``-th layer, and vocabulary ``V``, the forward pass needs, in
multiply-adds counted as 2 operations:

  encoder layer   s * (8 d^2 + 4 d f) + 4 s^2 d          (+ 2 s d E router)
  decoder layer   t * (8 d^2 + 4 d f) + 2 t (t + 1) d    (self, causal)
                  + 4 t d^2 + 4 s d^2 + 4 t s d           (cross)
                                                          (+ 2 t d E router)
  LM head         2 t d V

Each token meets one expert, the width of a dense feed-forward. Padding,
expert capacity left empty or dropped, and recomputation under remat are
not counted. The backward pass needs twice the forward, so a step needs
3 times the forward. Positions are the source row's tag, sentence and
EOS (``s = n + 2``) and the target positions the loss counts
(``t = min(n, seq - 1) + 1``).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable

from weights import ModelSpec

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def forward_flops(spec: ModelSpec, s: int, t: int) -> float:
    """Forward operations for one sentence pair of s + t positions."""
    d, f, E, V = spec.d_model, spec.d_ff, spec.n_experts, spec.vocab
    total = 0.0
    for i in range(spec.n_encoder_layers):
        total += s * (8 * d * d + 4 * d * f) + 4 * s * s * d
        if spec.is_moe(i):
            total += 2 * s * d * E
    for i in range(spec.n_decoder_layers):
        total += t * (8 * d * d + 4 * d * f) + 2 * t * (t + 1) * d
        total += 4 * t * d * d + 4 * s * d * d + 4 * t * s * d
        if spec.is_moe(i):
            total += 2 * t * d * E
    return total + 2 * t * d * V


def pair_positions(n: int, seq: int) -> tuple:
    """(source, target) positions a sentence of ``n`` tokens fills."""
    return n + 2, min(n, seq - 1) + 1


def train_flops_per_token(spec: ModelSpec, lengths: Iterable[int],
                          seq: int) -> float:
    """Forward + backward operations per real token over a set of
    sentence lengths (the traffic's length cycle)."""
    ops = toks = 0.0
    for n in lengths:
        s, t = pair_positions(int(n), seq)
        ops += 3 * forward_flops(spec, s, t)
        toks += s + t
    return ops / toks


def peak(device_kind: str) -> Dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]
