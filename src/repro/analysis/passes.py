"""Lint pass registry (DESIGN.md §12).

Mirrors the backend registry (core/backend.py): passes register
under an id via ``@register_pass`` and run against every executable in
the registry (``analysis/executables.py``) whose spec opts in by
carrying an expectation for that pass. A pass returns Findings — never
raises on a violation — so one broken invariant doesn't mask the rest
of the report; the gate aggregates afterwards.

Suppression: a spec can carry ``ignore=("pass-id", ...)`` (written in
the registry as a trailing ``# lint: ignore[pass-id]`` comment on the
registration line — ``register_executable`` parses it from source).
Suppressed findings stay in the report flagged ``suppressed`` but do
not fail the gate.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

__all__ = ["Finding", "LintPass", "available_passes", "get_pass",
           "register_pass", "run_pass"]

SEVERITIES = ("error", "warning", "info")


@dataclasses.dataclass(frozen=True)
class Finding:
    pass_id: str
    severity: str            # error | warning | info
    executable: str
    location: str            # "computation/%instr", "jaxpr:scan/pjit", ...
    message: str
    suppressed: bool = False

    def as_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class LintPass:
    pass_id: str
    doc: str
    fn: Callable              # fn(spec, artifacts) -> List[Finding]
    needs: Tuple[str, ...]    # artifact kinds: "hlo" | "jaxpr" | "scenario"


_REGISTRY: Dict[str, LintPass] = {}


def register_pass(pass_id: str, *, needs: Tuple[str, ...]
                  ) -> Callable[[Callable], Callable]:
    """Decorator: add a lint pass under ``pass_id``. ``needs`` declares
    which artifacts the pass consumes — ``--lint-table`` (pure lowering)
    runs only passes whose needs exclude "scenario"."""
    def deco(fn: Callable) -> Callable:
        _REGISTRY[pass_id] = LintPass(pass_id=pass_id,
                                      doc=(fn.__doc__ or "").strip(),
                                      fn=fn, needs=needs)
        return fn
    return deco


def available_passes() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_pass(pass_id: str) -> LintPass:
    try:
        return _REGISTRY[pass_id]
    except KeyError:
        raise KeyError(f"unknown lint pass {pass_id!r}; available: "
                       f"{', '.join(available_passes())}") from None


def run_pass(pass_id: str, spec, art) -> List[Finding]:
    """Run one pass over one executable, applying the spec's
    suppressions. Inapplicable passes (no expectation in the spec)
    return []."""
    p = get_pass(pass_id)
    findings = p.fn(spec, art)
    if pass_id in spec.ignore:
        findings = [dataclasses.replace(f, suppressed=True)
                    for f in findings]
    return findings


# --------------------------------------------------------------------------
# the five shipped passes
# --------------------------------------------------------------------------

def _finding(spec, pass_id, sev, loc, msg, **kw) -> Finding:
    return Finding(pass_id=pass_id, severity=sev, executable=spec.name,
                   location=loc, message=msg, **kw)


@register_pass("no-collectives", needs=("hlo",))
def no_collectives_pass(spec, art) -> List[Finding]:
    """Zero-communication / bytes-equality gate: Gate-Drop LOCAL,
    dropped-chunk, and local-routing executables must compile to ZERO
    all-to-alls (the paper's §3 structural claim); routed executables'
    all-to-all count/bytes must equal the comm/exchange.py bytes model
    (the telemetry == cost == HLO contract, DESIGN.md §10, through the
    IR walker)."""
    from repro.analysis.hlo import collectives_summary
    exp = spec.expect.get("no-collectives")
    if exp is None:
        return []
    module = art.hlo
    summary = collectives_summary(module)
    a2a = summary.get("all-to-all", {"count": 0, "bytes": 0.0,
                                     "wire_bytes": 0.0})
    out: List[Finding] = []
    if exp.get("zero"):
        if a2a["count"]:
            sites = [f"{i.computation}/%{i.name}" for i in
                     module.find("all-to-all")][:4]
            out.append(_finding(
                spec, "no-collectives", "error", ";".join(sites),
                f"expected ZERO all-to-alls, found {int(a2a['count'])} "
                f"moving {a2a['bytes']:.0f} B"))
        return out
    if exp.get("nonzero") and not a2a["count"]:
        out.append(_finding(
            spec, "no-collectives", "error", module.entry or "entry",
            "expected a routed executable (all-to-alls present), found "
            "none — the expert exchange was silently elided"))
    cost = exp.get("cost")
    if cost is not None:
        if int(a2a["count"]) != int(cost["calls"]):
            out.append(_finding(
                spec, "no-collectives", "error", module.entry or "entry",
                f"all-to-all count {int(a2a['count'])} != cost model "
                f"{int(cost['calls'])}"))
        if float(a2a["bytes"]) != float(cost["bytes"]):
            out.append(_finding(
                spec, "no-collectives", "error", module.entry or "entry",
                f"all-to-all payload {a2a['bytes']:.0f} B != cost model "
                f"{cost['bytes']:.0f} B"))
        if abs(float(a2a["wire_bytes"]) - float(cost["wire_bytes"])) >= 1:
            out.append(_finding(
                spec, "no-collectives", "error", module.entry or "entry",
                f"all-to-all wire {a2a['wire_bytes']:.1f} B != cost model "
                f"{cost['wire_bytes']:.1f} B"))
    return out


@register_pass("dtype-flow", needs=("jaxpr",))
def dtype_flow_pass(spec, art) -> List[Finding]:
    """No f32 leakage in 16-bit paths: flags dot_generals whose operands
    were CONVERTED from bf16/f16 to f32 (2x FLOP/read width vs the
    declared model dtype). Walks the jaxpr, not compiled HLO — XLA:CPU
    legalizes every bf16 dot to convert+f32-dot, which would make the
    violation indistinguishable post-compile. Whitelisted f32
    accumulators (router logits, attention probabilities, f32
    ``preferred_element_type`` over 16-bit operands) don't match: they
    are either below ``min_elems`` or keep 16-bit operands."""
    exp = spec.expect.get("dtype-flow")
    if exp is None:
        return []
    from repro.analysis.jaxprs import f32_upcast_dots
    hits = f32_upcast_dots(art.jaxpr,
                           min_elems=exp.get("min_elems", 4096))
    return [
        _finding(spec, "dtype-flow", "error",
                 "jaxpr:" + ("/".join(h.path) or "top"),
                 f"f32 dot_general over operands widened from "
                 f"{'/'.join(sorted(set(h.src_dtypes)))}; output "
                 f"{h.out_shape} ({h.out_elems} elems) — cast back or "
                 f"use preferred_element_type for f32 accumulation")
        for h in hits]


@register_pass("vmem-budget", needs=("jaxpr",))
def vmem_budget_pass(spec, art) -> List[Finding]:
    """Megakernel VMEM residency: estimates each pallas_call's on-chip
    footprint from its REAL lowered block mappings (grid-varying blocks
    double-buffered, grid-invariant blocks + scratch resident once) and
    fails any launch over the spec's budget (default 16 MiB — TPU v4
    VMEM per core)."""
    exp = spec.expect.get("vmem-budget")
    if exp is None:
        return []
    from repro.analysis.jaxprs import pallas_launches
    budget = exp.get("budget_bytes", 16 << 20)
    out: List[Finding] = []
    for launch in pallas_launches(art.jaxpr):
        used = launch.vmem_bytes()
        if used > budget:
            brk = ", ".join(
                f"{b.name}{list(b.block_shape)}:{b.dtype}"
                f"{'x2' if b.grid_varying else ''}={b.bytes >> 10}KiB"
                for b in launch.buffers)
            out.append(_finding(
                spec, "vmem-budget", "error",
                f"pallas:{launch.kernel_name}",
                f"estimated VMEM {used / 2**20:.2f} MiB > budget "
                f"{budget / 2**20:.2f} MiB (grid {launch.grid}; {brk})"))
    return out


@register_pass("launch-count", needs=("jaxpr",))
def launch_count_pass(spec, art) -> List[Finding]:
    """Kernel-launch budget: pallas_fused must stay a SINGLE pallas_call
    per step (the §11 fusion claim), the unfused pipeline within its
    dispatch/FFN/combine budget. Counted in the jaxpr — a scan body
    counts once, matching per-traced-step launches."""
    exp = spec.expect.get("launch-count")
    if exp is None:
        return []
    from repro.analysis.jaxprs import pallas_launches
    launches = pallas_launches(art.jaxpr)
    budget = exp["max"]
    if len(launches) <= budget:
        return []
    names = ", ".join(l.kernel_name for l in launches)
    return [_finding(
        spec, "launch-count", "error", f"pallas:{names}",
        f"{len(launches)} pallas_call launches > budget {budget}")]


@register_pass("host-sync", needs=("scenario",))
def host_sync_pass(spec, art) -> List[Finding]:
    """No hidden device->host transfers inside steady-state Trainer
    chunks / scheduler ticks (explicit jax.device_get is sanctioned),
    and no jit cache misses across ticks (a growth means a tick
    re-traced — a shape leak re-compiling in the serving loop)."""
    if spec.scenario is None:
        return []
    res = spec.scenario()
    out: List[Finding] = []
    for ev in res.get("events", ()):
        if ev.sanctioned or ev.internal:
            continue
        out.append(_finding(
            spec, "host-sync", "error", ev.origin,
            f"implicit device->host transfer via {ev.method} inside a "
            f"steady-state tick; use jax.device_get if the sync is "
            f"intentional"))
    for label, before, after in res.get("cache_sizes", ()):
        if after > before:
            out.append(_finding(
                spec, "host-sync", "error", f"jit:{label}",
                f"jit cache grew {before} -> {after} across warmed-up "
                f"ticks: a tick re-traced (shape/dtype leak)"))
    return out
