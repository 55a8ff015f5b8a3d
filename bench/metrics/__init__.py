"""Per-layer metric readers, one file each: ``<metric name>.py`` defines
``read(ctx) -> float | None``. A reader returns None where it finds
nothing to read. A metric that BENCHMARK.json gives a cell has something
to read there, so None in such a cell is an error, not a gap in the
result line."""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Callable, Dict

from harness import applies

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name: str) -> Callable[[Dict[str, Any]], Any]:
    path = os.path.join(HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(cell, ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        if not applies(m, cell.name):
            continue
        v = reader(m["name"])(ctx)
        if v is None:
            raise RuntimeError(f"bench: metric {m['name']} found nothing to "
                               f"read in cell {cell.name}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
