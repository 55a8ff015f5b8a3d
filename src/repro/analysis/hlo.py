"""HLO-text IR walker (DESIGN.md §12).

Parses the ``compiled.as_text()`` dump into a small typed IR —
computations, instructions, result shapes (tuple results included),
operands, attributes (``replica_groups`` in both list and iota form,
``channel_id``, ``calls=``) — that the lint passes and the roofline
walk instead of ad-hoc regexes. Subsumes the seed-era
``launch/hlo_analysis.py::parse_collectives`` (same output schema,
kept as a function here so every caller migrated without changing
its numbers) and fixes its two latent bugs:

  * unknown payload dtypes silently priced at 4 bytes — ``shape_bytes``
    now raises, and the table covers the int8/fp8/pred wire dtypes;
  * ``get-tuple-element`` lines were excluded only because layout braces
    ``{2,1,0}`` happened to break the old shape regex — the walker
    matches opcodes structurally, so textual noise like an operand named
    ``%all-to-all.1`` can never be miscounted as a collective.

Import-safe: never touches jax device state.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["DTYPE_BYTES", "HloComputation", "HloInstr", "HloModule",
           "HloShape", "UnknownDtypeError", "collectives_summary",
           "parse_collectives", "parse_hlo", "shape_bytes"]

# wire width of every dtype XLA prints in shapes: THE dtype table of the
# repo.
DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}


class UnknownDtypeError(KeyError):
    """A shape used a dtype missing from DTYPE_BYTES — extend the table
    instead of silently pricing it wrong (the seed parser defaulted to 4
    bytes, which under-priced f64/c128 and over-priced every 8-bit wire
    dtype by 4x)."""


def shape_bytes(dtype: str, dims) -> int:
    """Bytes of an array shape. ``dims`` is an int iterable or the
    comma-joined string XLA prints. Raises UnknownDtypeError on a dtype
    missing from DTYPE_BYTES."""
    if dtype not in DTYPE_BYTES:
        raise UnknownDtypeError(
            f"dtype {dtype!r} not in analysis.hlo.DTYPE_BYTES")
    n = 1
    if isinstance(dims, str):
        dims = [int(d) for d in dims.split(",") if d.strip()]
    for d in dims:
        n *= int(d)
    return n * DTYPE_BYTES[dtype]


@dataclasses.dataclass(frozen=True)
class HloShape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def bytes(self) -> int:
        return shape_bytes(self.dtype, self.dims)


@dataclasses.dataclass(frozen=True)
class HloInstr:
    name: str                     # %-less instruction name
    opcode: str                   # normalized: "-start" folded, no "-done"
    shapes: Tuple[HloShape, ...]  # result shapes (>=1; tuples flattened)
    operands: Tuple[str, ...]     # %-less operand instruction names
    attrs: Dict[str, str]         # raw attr text by key (channel_id, ...)
    replica_groups: Optional[Tuple[Tuple[int, ...], ...]]
    channel_id: Optional[int]
    called: Tuple[str, ...]       # computations from calls={...}/to_apply=
    computation: str              # owning computation name
    is_root: bool
    raw: str                      # the source line (stripped)

    @property
    def result_bytes(self) -> int:
        return sum(s.bytes for s in self.shapes)

    @property
    def group_size(self) -> int:
        if not self.replica_groups:
            return 1
        return max(len(g) for g in self.replica_groups)


@dataclasses.dataclass
class HloComputation:
    name: str
    instrs: List[HloInstr]
    is_entry: bool


@dataclasses.dataclass
class HloModule:
    computations: Dict[str, HloComputation]
    entry: Optional[str]

    def instructions(self) -> Iterator[HloInstr]:
        for comp in self.computations.values():
            yield from comp.instrs

    def find(self, opcode: str) -> List[HloInstr]:
        return [i for i in self.instructions() if i.opcode == opcode]

    def called_by(self, instr: HloInstr) -> List[HloComputation]:
        """Fusion/call/custom-call bodies of an instruction."""
        return [self.computations[c] for c in instr.called
                if c in self.computations]


# --------------------------------------------------------------------------
# parsing
# --------------------------------------------------------------------------

# computation header: `ENTRY %main.42 (...) -> ... {` / `%fused (...) {`
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\{\s*$")
# instruction: `  [ROOT ]%name = <rhs>`
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
# one shape token: dtype[dims]{layout}? — layout/tiling braces skipped
_SHAPE_TOK = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]"
                        r"(?:\{[^}]*\})?")
# opcode after the result shape(s): letters and dashes, then `(`
_OPCODE_RE = re.compile(r"\s*([a-z][a-z0-9\-]*)\s*\(")
_OPERAND_NAME = re.compile(r"%([\w\.\-]+)")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_LIST = re.compile(r"replica_groups=(\{\{[0-9,\{\}\s]*\}\})")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CALLS_RE = re.compile(r"(?:calls|to_apply|fused_computation)="
                       r"%?([\w\.\-]+)")
_ATTR_RE = re.compile(r"([a-z_]+)=")

COLLECTIVE_OPS = ("all-to-all", "all-gather", "all-reduce",
                  "reduce-scatter", "collective-permute")


def _parse_result_shapes(rhs: str) -> Tuple[Tuple[HloShape, ...], int]:
    """Leading shape spec of an instruction rhs -> (shapes, end offset).
    Handles single shapes and tuple results `(f32[..]{..}, u8[..])`."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        span = rhs[1:i]
        shapes = tuple(HloShape(d, tuple(int(x) for x in dims.split(",")
                                         if x.strip()))
                       for d, dims in _SHAPE_TOK.findall(span))
        return shapes, i + 1
    m = _SHAPE_TOK.match(rhs)
    if not m:
        return (), 0
    dims = tuple(int(x) for x in m.group(2).split(",") if x.strip())
    return (HloShape(m.group(1), dims),), m.end()


def _matching_paren(s: str, start: int) -> int:
    depth = 0
    for i in range(start, len(s)):
        depth += s[i] == "("
        depth -= s[i] == ")"
        if depth == 0:
            return i
    return len(s) - 1


def _normalize_opcode(op: str) -> Optional[str]:
    """Fold async `-start` into the base op; drop `-done`/`-update`
    halves so async pairs count once."""
    if op.endswith("-done") or op.endswith("-update"):
        return None
    if op.endswith("-start"):
        return op[:-len("-start")]
    return op


def _parse_instr(line: str, comp: str) -> Optional[HloInstr]:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    is_root, name, rhs = bool(m.group(1)), m.group(2), m.group(3)
    shapes, off = _parse_result_shapes(rhs)
    if not shapes:
        return None
    rest = rhs[off:]
    om = _OPCODE_RE.match(rest)
    if not om:
        return None
    raw_op = om.group(1)
    open_paren = om.end() - 1
    close = _matching_paren(rest, open_paren)
    operand_span = rest[open_paren + 1:close]
    tail = rest[close + 1:]
    opcode = _normalize_opcode(raw_op)
    if opcode is None:
        return None
    operands = tuple(_OPERAND_NAME.findall(
        _SHAPE_TOK.sub("", operand_span)))
    attrs = {}
    for am in _ATTR_RE.finditer(tail):
        attrs[am.group(1)] = ""          # presence map; values below
    cm = _CHANNEL_RE.search(tail)
    channel_id = int(cm.group(1)) if cm else None
    groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    gl = _GROUPS_LIST.search(tail)
    if gl:
        groups = tuple(tuple(int(x) for x in g.split(",") if x.strip())
                       for g in re.findall(r"\{([0-9,\s]+)\}", gl.group(1)))
    else:
        gi = _GROUPS_IOTA.search(tail)
        if gi:
            n_groups, size = int(gi.group(1)), int(gi.group(2))
            groups = tuple(tuple(range(g * size, (g + 1) * size))
                           for g in range(n_groups))
    called = tuple(_CALLS_RE.findall(tail))
    return HloInstr(name=name, opcode=opcode, shapes=shapes,
                    operands=operands, attrs=attrs, replica_groups=groups,
                    channel_id=channel_id, called=called, computation=comp,
                    is_root=is_root, raw=line.strip())


def parse_hlo(text: str) -> HloModule:
    """Parse a compiled-HLO text dump into an HloModule."""
    comps: Dict[str, HloComputation] = {}
    cur: Optional[HloComputation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        cm = _COMP_RE.match(stripped)
        if cm and "=" not in stripped.split("(", 1)[0]:
            cur = HloComputation(name=cm.group(2), instrs=[],
                                 is_entry=bool(cm.group(1)))
            comps[cur.name] = cur
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is not None and "=" in stripped:
            instr = _parse_instr(line, cur.name)
            if instr is not None:
                cur.instrs.append(instr)
    entry = next((n for n, c in comps.items() if c.is_entry), None)
    return HloModule(computations=comps, entry=entry)


# --------------------------------------------------------------------------
# collective accounting (the PR-5 telemetry==HLO contract)
# --------------------------------------------------------------------------

def _wire_bytes(op: str, payload: float, g: int) -> float:
    """Per-device ring-model wire traffic of one collective op."""
    if op == "all-gather":
        return payload * (g - 1) / max(g, 1)
    if op == "all-reduce":
        return 2 * payload * (g - 1) / max(g, 1)
    if op == "reduce-scatter":
        return payload * (g - 1)          # result is the scattered shard
    if op == "all-to-all":
        return payload * (g - 1) / max(g, 1)
    return float(payload)                  # collective-permute


def collectives_summary(module: HloModule) -> Dict[str, Dict[str, float]]:
    """Per-kind collective counts/bytes over a parsed module — the
    numbers comm/exchange.py's cost model and telemetry are pinned to.
    ``bytes`` sums per-device RESULT bytes (tuple results summed),
    ``wire_bytes`` applies the ring model per op."""
    out: Dict[str, Dict[str, float]] = {}
    for instr in module.instructions():
        if instr.opcode not in COLLECTIVE_OPS:
            continue
        payload = instr.result_bytes
        g = instr.group_size
        rec = out.setdefault(instr.opcode, {"count": 0, "bytes": 0.0,
                                            "wire_bytes": 0.0,
                                            "max_group": 1})
        rec["count"] += 1
        rec["bytes"] += payload
        rec["max_group"] = max(rec["max_group"], g)
        rec["wire_bytes"] += _wire_bytes(instr.opcode, payload, g)
    return out


def parse_collectives(hlo: str) -> Dict[str, Dict[str, float]]:
    """Back-compat entry (old launch/hlo_analysis.py signature): HLO text
    -> per-kind collective summary, now through the IR walker."""
    return collectives_summary(parse_hlo(hlo))
