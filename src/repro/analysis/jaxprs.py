"""Jaxpr walker (DESIGN.md §12): the pre-lowering half of the analyzer.

Compiled HLO is the truth for collectives, but XLA:CPU rewrites every
bf16 matmul into convert->f32-dot — at the compiled level a deliberate
f32 upcast and a legitimate bf16 dot are indistinguishable (and CSE can
merge them). The jaxpr preserves the dtypes the program was WRITTEN
with, so the dtype-flow pass and the pallas launch/VMEM accounting walk
it instead.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["PallasLaunch", "count_primitive", "f32_upcast_dots",
           "pallas_launches", "walk_eqns"]


def _sub_jaxprs(eqn) -> Iterator[Any]:
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for u in vs:
            if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                yield u.jaxpr               # ClosedJaxpr
            elif hasattr(u, "eqns"):
                yield u                      # raw Jaxpr


def walk_eqns(jaxpr, path: Tuple[str, ...] = ()
              ) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield (eqn, path) over a jaxpr and every nested sub-jaxpr
    (pjit/scan/while/cond bodies, custom_vjp calls, ...). ``path`` is the
    chain of enclosing primitive names — the structured location the
    findings carry."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)   # accept ClosedJaxpr
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in _sub_jaxprs(eqn):
            yield from walk_eqns(sub, path + (eqn.primitive.name,))


def count_primitive(jaxpr, name: str) -> int:
    """Number of eqns binding ``name`` anywhere in the jaxpr. NOTE a
    scan/while body counts ONCE (static launch count per traced step),
    which is exactly the invariant the launch-count pass gates."""
    return sum(1 for eqn, _ in walk_eqns(jaxpr)
               if eqn.primitive.name == name)


# --------------------------------------------------------------------------
# dtype flow
# --------------------------------------------------------------------------

_F16 = ("bfloat16", "float16")


def _def_map(jaxpr) -> Dict[Any, Any]:
    """var -> defining eqn, across every nesting level (jax Vars are
    unique objects, so one flat map is sound)."""
    defs: Dict[Any, Any] = {}
    for eqn, _ in walk_eqns(jaxpr):
        for v in eqn.outvars:
            defs[v] = eqn
    return defs


@dataclasses.dataclass(frozen=True)
class UpcastDot:
    path: Tuple[str, ...]
    out_shape: Tuple[int, ...]
    out_elems: int
    src_dtypes: Tuple[str, ...]   # 16-bit dtypes the operands came from


def f32_upcast_dots(jaxpr, *, min_elems: int = 4096) -> List[UpcastDot]:
    """Find dot_general eqns computing in f32 over operands that were
    CONVERTED from a 16-bit dtype — the "unexpected upcast" shape: the
    matmul's FLOPs and its operand reads run at 2x the width the model
    declared. Whitelisted f32 accumulators (router logits, attention
    probabilities, ``preferred_element_type=f32`` over 16-bit inputs)
    stay legal: small outputs (< min_elems) are skipped, and a dot whose
    operands are STILL 16-bit never matches regardless of its
    accumulation dtype."""
    defs = _def_map(jaxpr)
    hits: List[UpcastDot] = []
    for eqn, path in walk_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        out = eqn.outvars[0].aval
        if str(out.dtype) != "float32":
            continue
        elems = 1
        for d in out.shape:
            elems *= int(d)
        if elems < min_elems:
            continue
        srcs = []
        for v in eqn.invars:
            if str(getattr(v.aval, "dtype", "")) != "float32":
                srcs = []
                break
            src = defs.get(v)
            if (src is not None
                    and src.primitive.name == "convert_element_type"
                    and str(src.invars[0].aval.dtype) in _F16):
                srcs.append(str(src.invars[0].aval.dtype))
        if srcs:   # at least one operand is a widened 16-bit tensor
            hits.append(UpcastDot(path=path, out_shape=tuple(out.shape),
                                  out_elems=elems, src_dtypes=tuple(srcs)))
    return hits


# --------------------------------------------------------------------------
# pallas launches + block footprints
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockBuffer:
    name: str                 # in0 / in1 / ... / out0 / scratch0
    block_shape: Tuple[int, ...]
    dtype: str
    bytes: int                # ONE buffer copy
    grid_varying: bool        # block smaller than the array -> pipelined


@dataclasses.dataclass(frozen=True)
class PallasLaunch:
    kernel_name: str
    path: Tuple[str, ...]
    grid: Tuple[int, ...]
    buffers: Tuple[BlockBuffer, ...]

    def vmem_bytes(self, *, double_buffer: bool = True) -> int:
        """Estimated VMEM residency: grid-varying blocks are double-
        buffered by the pipeline (x2), grid-invariant blocks and scratch
        stay resident once."""
        total = 0
        for b in self.buffers:
            mult = 2 if (double_buffer and b.grid_varying) else 1
            total += mult * b.bytes
        return total


def _np_bytes(shape, dtype) -> int:
    import numpy as np
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def _block_dim(b) -> int:
    """One grid_mapping block dim as an int: ``Blocked``/``Element``/
    ``BoundedSlice`` carry ``block_size``; ``Squeezed`` is one row."""
    return b if isinstance(b, int) else int(getattr(b, "block_size", 1))


def pallas_launches(jaxpr) -> List[PallasLaunch]:
    """Extract every pallas_call in a jaxpr with its grid and per-operand
    block footprint, read from the REAL lowered grid_mapping (not a
    re-derivation of the block-spec math)."""
    out: List[PallasLaunch] = []
    for eqn, path in walk_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        name_info = eqn.params.get("name_and_src_info")
        kname = getattr(name_info, "name", None) or str(name_info or "pallas")
        buffers: List[BlockBuffer] = []
        n_in = len(eqn.invars)
        for i, bm in enumerate(gm.block_mappings):
            sd = bm.array_aval
            block = tuple(_block_dim(b) for b in bm.block_shape)
            varying = tuple(sd.shape) != block
            tag = f"in{i}" if i < n_in else f"out{i - n_in}"
            buffers.append(BlockBuffer(
                name=tag, block_shape=block, dtype=str(sd.dtype),
                bytes=_np_bytes(block, sd.dtype), grid_varying=varying))
        # scratch operands: trailing refs of the kernel jaxpr
        n_scratch = int(getattr(gm, "num_scratch_operands", 0))
        if n_scratch:
            kjaxpr = eqn.params["jaxpr"]
            for j, v in enumerate(kjaxpr.invars[-n_scratch:]):
                aval = getattr(v.aval, "inner_aval", v.aval)
                shape = tuple(int(d) for d in aval.shape)
                buffers.append(BlockBuffer(
                    name=f"scratch{j}", block_shape=shape,
                    dtype=str(aval.dtype),
                    bytes=_np_bytes(shape, aval.dtype), grid_varying=False))
        out.append(PallasLaunch(kernel_name=kname, path=path,
                                grid=tuple(int(g) for g in gm.grid),
                                buffers=tuple(buffers)))
    return out
