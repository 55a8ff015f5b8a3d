"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the train
step's layers, on the trace's own clock.

    scopes  device time of each step execution under each of the program's
            name scopes (``SCOPES``), per chip, the rest under ``other``
    idle    device idle time inside the window, each instant of it put
            down to the innermost span (``SPANS``) that the driving thread
            was in then, or to ``no host span``

An op's scope is read from its ``op_name`` metadata, which the TPU
profiler carries in the ``tf_op`` stat of the op's event metadata, as in
``jit(chunk_fn)/while/body/closed_call/attention/convert_element_type:``.
A scope matches a path component equal to its name, bare or wrapped by a
transform: ``moe``, ``jvp(moe)``, ``transpose(jvp(lm_head))``; the
innermost match wins, and an op that names none (XLA's own copies carry
no ``op_name``) is ``other``. The driving thread is the line of the host
plane that holds the window's own annotation; the program's ``Tracer``
spans are written there as profiler annotations (``obs/trace.py``), so
no offset between clocks is taken.

The profiler's Python reader (``jax.profiler.ProfileData``) does not
expose the stats of event metadata, so this module reads the protobuf
itself, with the message layout of ``tsl/profiler/protobuf/xplane.proto``
declared below (fields it does not need are skipped).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, find_xplane,
                          union)

SCOPES = ("moe", "attention", "lm_head", "optimizer")
SPANS = ("train_chunk", "chunk.decide", "chunk.put", "chunk.execute",
         "chunk.fetch", "chunk.record", "prefetch.wait")
OTHER, NO_SPAN = "other", "no host span"

Op = Tuple[str, float, float, str]           # (name, start s, end s, op_name)
Interval = Tuple[str, float, float]          # (name, start s, end s)

_XPLANE = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    # a map<int64, V> field is a repeated entry of key 1 and value 2
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
}


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_layer_reduce_xplane.proto", package="bench_xplane",
        syntax="proto3")
    for msg, fields in _XPLANE.items():
        m = fdp.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def load_bytes(data: bytes) -> Dict:
    """{"host": [[Interval] per host line], "devices": {id: {"ops": [Op],
    "modules": [Interval]}}}, times in seconds on the trace's clock."""
    space = _xspace_class()()
    space.ParseFromString(data)
    host: List[List[Interval]] = []
    devices: Dict[int, Dict[str, list]] = {}
    for plane in space.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and not plane.name.startswith("/host:CPU"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        tf_op = {k: next((s.str_value for s in m.stats
                          if stat_names.get(s.metadata_id) == "tf_op"), "")
                 for k, m in meta.items()}
        d = (devices.setdefault(int(dev.group(1)),
                                {"ops": [], "modules": []})
             if dev else None)
        for line in plane.lines:
            t0 = line.timestamp_ns * 1e-9
            evs = [(meta[e.metadata_id].name if e.metadata_id in meta else "",
                    t0 + e.offset_ps * 1e-12,
                    t0 + (e.offset_ps + e.duration_ps) * 1e-12,
                    tf_op.get(e.metadata_id, "")) for e in line.events]
            if d is None:
                host.append([ev[:3] for ev in evs])
            elif line.name == OPS_LINE:
                d["ops"].extend(evs)
            elif line.name == MODULES_LINE:
                d["modules"].extend(ev[:3] for ev in evs)
    return {"host": host, "devices": devices}


def load(path: str) -> Dict:
    with open(path, "rb") as f:
        return load_bytes(f.read())


def scope_of(op_name: str) -> str:
    """The innermost scope an op's ``op_name`` names, or ``other``."""
    for comp in reversed(re.split(r"[/;]", op_name)):
        bare = re.sub(r"^(?:[\w.\-]+\()+|\)+$", "", comp)
        if bare in SCOPES and comp.count("(") == comp.count(")"):
            return bare
    return OTHER


def _owners(spans: Sequence[Interval], lo: float, hi: float
            ) -> List[Interval]:
    """[lo, hi] cut where a span begins or ends, each piece named by the
    innermost span that covers it (the latest begun, then the shortest)."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = (max(cover, key=lambda sp: (sp[1], sp[1] - sp[2]))[0]
                if cover else NO_SPAN)
        out.append((name, a, b))
    return out


def _charge(gaps: Sequence[Tuple[float, float]],
            owners: Sequence[Interval], into: Dict[str, float],
            weight: float) -> None:
    """Adds each gap's overlap with each owner piece to its name."""
    i = 0
    for gs, ge in gaps:
        while i < len(owners) and owners[i][2] <= gs:
            i += 1
        j = i
        while j < len(owners) and owners[j][1] < ge:
            name, a, b = owners[j]
            into[name] += max(0.0, min(b, ge) - max(a, gs)) * weight
            j += 1


def reduce(trace: Dict, window: str, module_substr: str,
           expect_steps: int) -> Dict:
    """Layer numbers of the traced window named ``window`` (a host
    annotation). Every chip has to show ``expect_steps`` executions of the
    step program (a module whose name holds ``module_substr``) inside it;
    a trace that does not is refused, never read in part."""
    lines = [ln for ln in trace["host"] if any(h[0] == window for h in ln)]
    if not lines:
        raise ValueError(f"no host event {window!r} in the trace")
    if not trace["devices"]:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    driving = lines[0]
    _, ws, we = next(h for h in driving if h[0] == window)
    host = [(n, max(s, ws), min(e, we)) for n, s, e in driving
            if n in SPANS and e > ws and s < we]
    owners = _owners(host, ws, we)
    devs = sorted(trace["devices"])
    w = 1.0 / len(devs)
    scope_s = [defaultdict(float) for _ in range(expect_steps)]
    op_s = [0.0] * expect_steps
    idle: Dict[str, float] = defaultdict(float)
    seen = set()
    for d in devs:
        ops = [o for o in trace["devices"][d]["ops"]
               if o[2] > ws and o[1] < we]
        busy = union([(max(s, ws), min(e, we)) for _, s, e, _ in ops])
        edges = [ws] + [x for iv in busy for x in iv] + [we]
        _charge([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a],
                owners, idle, w)
        mods = sorted((m for m in trace["devices"][d]["modules"]
                       if module_substr in m[0]
                       and m[1] >= ws and m[2] <= we),
                      key=lambda m: m[1])
        if len(mods) != expect_steps:
            raise ValueError(f"{expect_steps} step executions expected on "
                             f"chip {d}, found {len(mods)}")
        for i, (_, ms, me) in enumerate(mods):
            for _, s, e, op in ops:
                if not ms <= (s + e) / 2 <= me:
                    continue
                sc = scope_of(op)
                seen.add(sc)
                scope_s[i][sc] += (e - s) * w
                op_s[i] += (e - s) * w
    named = [s for s in SCOPES if s in seen]
    return {
        "window_s": we - ws,
        "chips": len(devs),
        "steps": expect_steps,
        "scopes_seen": named,
        "scope_s": [{s: st.get(s, 0.0) for s in named + [OTHER]}
                    for st in scope_s],
        "op_s": op_s,
        "spans_seen": sorted({n for n, _, _ in host}),
        "idle_s": dict(idle),
    }


def read(logdir: str, expect_steps: int) -> Dict:
    """``reduce`` of the one trace under ``logdir``, as a traced window of
    ``bench/cells/train_mt.py`` lays it out."""
    return reduce(load(find_xplane(logdir)), "bench.window", "chunk_fn",
                  expect_steps)


def scope_ms(layers: Optional[Dict], scope: str,
             decisions: Sequence[bool] = (),
             dropped: Optional[bool] = None) -> Optional[float]:
    """Device time under ``scope`` per step execution, mean over the
    steps whose consensus bit is ``dropped`` (over every step where it
    is None), in ms. None where the trace names no such scope or holds
    no such step; 0.0 where the scope is there and took no time."""
    if not layers or scope not in layers["scopes_seen"]:
        return None
    xs = [s[scope] for i, s in enumerate(layers["scope_s"])
          if dropped is None or decisions[i] is dropped]
    return 1e3 * sum(xs) / len(xs) if xs else None


def idle_ms(layers: Optional[Dict], spans: Sequence[str]
            ) -> Optional[float]:
    """Device idle time under any of ``spans`` per step of the window, in
    ms. None where none of them was entered in the window."""
    if not layers or not set(spans) & set(layers["spans_seen"]):
        return None
    return 1e3 * sum(layers["idle_s"].get(s, 0.0)
                     for s in spans) / layers["steps"]
