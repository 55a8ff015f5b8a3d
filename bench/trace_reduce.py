"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers
the per-layer metrics read.

From each chip's plane (``/device:TPU:<n>``) it takes the ``XLA Ops``
line (every device operation) and the ``XLA Modules`` line (every
program execution); from the host plane it takes the window's own
annotation, which puts the host's clock (``time.perf_counter``) and the
trace's clock side by side, so that the program's ``Tracer`` spans can
name what the host was doing in each idle gap.

    busy      union of the op intervals inside the window, per chip
    steps     the executions of the step program in the window, in order
    a2a       all-to-all op time inside each step execution
    breakdown the ops that took most time, and idle time by host span
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start s, end s)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files in {logdir}")
    return paths[0]


def load(path: str) -> Dict:
    """{"host": [Interval], "devices": {id: {"ops": [...],
    "modules": [...]}}}, times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Interval] = []
    devices: Dict[int, Dict[str, List[Interval]]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)), {"ops": [],
                                                     "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    d[key].extend((e.name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events)
    return {"host": host, "devices": devices}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv if e > lo and s < hi]


def _host_name(spans: Sequence[Interval], t: float) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best: Optional[Interval] = None
    for sp in spans:
        if sp[1] <= t <= sp[2] and (best is None
                                    or sp[2] - sp[1] < best[2] - best[1]):
            best = sp
    return best[0] if best else "no host span"


def reduce(trace: Dict, window: str, host_t0: float,
           spans: Sequence[Interval], module_substr: str,
           expect_steps: int) -> Dict:
    """Numbers of the traced window. ``window`` names the host annotation
    around it, which began at ``host_t0`` on the host's clock; ``spans``
    are the program's Tracer spans on that clock. Every chip has to show
    ``expect_steps`` executions of the step program (a module whose name
    holds ``module_substr``) inside the window: a trace that does not is
    refused, never read in part."""
    wins = [h for h in trace["host"] if h[0] == window]
    if not wins:
        raise ValueError(f"no host event {window!r} in the trace")
    if not trace["devices"]:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    _, ws, we = wins[0]
    shift = ws - host_t0
    host = [(n, s + shift, e + shift) for n, s, e in spans]
    per_busy, per_steps, per_a2a = [], [], []
    op_time: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    devs = sorted(trace["devices"])
    for d in devs:
        ops = _clip(trace["devices"][d]["ops"], ws, we)
        busy = union([(s, e) for _, s, e in ops])
        per_busy.append(sum(e - s for s, e in busy))
        for n, s, e in ops:
            op_time[n] += (e - s) / len(devs)
        edges = [ws] + [x for iv in busy for x in iv] + [we]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                idle_by[_host_name(host, (gs + ge) / 2)] += (ge - gs) / len(devs)
        mods = sorted((m for m in trace["devices"][d]["modules"]
                       if module_substr in m[0] and m[1] >= ws and m[2] <= we),
                      key=lambda m: m[1])
        per_steps.append([e - s for _, s, e in mods])
        per_a2a.append([sum(oe - os_ for n, os_, oe in ops
                            if "all-to-all" in n and os_ >= s and oe <= e)
                        for _, s, e in mods])
    found = [len(x) for x in per_steps]
    if any(n != expect_steps for n in found):
        raise ValueError(f"{expect_steps} step executions expected on every "
                         f"chip, found {found}")
    steps = [sum(x[i] for x in per_steps) / len(devs)
             for i in range(expect_steps)]
    a2a = [sum(x[i] for x in per_a2a) / len(devs)
           for i in range(expect_steps)]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": we - ws,
        "busy_s": sum(per_busy) / max(len(devs), 1),
        "chips": len(devs),
        "step_s": steps,
        "a2a_s": a2a,
        "breakdown": {"device_ops": [[n, v] for n, v in top_ops],
                      "idle_gaps": [[n, v] for n, v in top_idle]},
    }
