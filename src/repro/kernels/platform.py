"""Platform detection for the Pallas kernels (DESIGN.md §6).

Every kernel wrapper takes ``interpret: bool | None``. ``None`` (the
default) means *auto*: compile for real on TPU, run the Pallas
interpreter on the CPU backend (where the differential tests run), and
refuse every other backend — a GPU host running the TPU kernels in the
interpreter would report interpreter numbers under a device's name.

``force_interpret`` exists for tests and benchmarks that want to pin the
mode regardless of platform (e.g. measuring interpreter overhead).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

# None = follow the platform; True/False = forced via force_interpret().
_FORCED: Optional[bool] = None


def default_interpret() -> bool:
    """False on TPU (the kernels compile), True on CPU (interpreter);
    any other backend raises."""
    if _FORCED is not None:
        return _FORCED
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and are interpreted on CPU; "
        f"backend {backend!r} is neither")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Kernel-wrapper helper: ``None`` -> platform default."""
    return default_interpret() if interpret is None else bool(interpret)


@contextlib.contextmanager
def force_interpret(value: bool):
    """Pin interpret mode inside the context (tests/benchmarks)."""
    global _FORCED
    prev = _FORCED
    _FORCED = bool(value)
    try:
        yield
    finally:
        _FORCED = prev


def fit_block(n: int, requested: int) -> int:
    """Largest divisor of ``n`` that is <= ``requested``.

    Production shapes are multiples of 128 so the MXU-aligned request wins;
    toy/test shapes degrade to a smaller exact tile instead of asserting.
    """
    b = max(1, min(requested, n))
    while n % b:
        b -= 1
    return b
