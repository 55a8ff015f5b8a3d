"""What every kind of cell shares: a cell's files, found by the names
BENCHMARK.json gives, the module that runs its kind of traffic, and the
metrics its result line carries.

A cell's configuration is ``bench/configs/<config>.json`` (the file its
``configs`` entry names), its traffic mix ``bench/traffic/<traffic>.json``,
and the mix's ``kind`` names the module that runs it,
``bench/cells/<kind>.py``. Such a module defines ``END_TO_END``, the
end-to-end metrics it measures, and ``run(cell, seed, seconds, trace,
...)``, which returns the result line's object.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell named ``name`` with its configuration and traffic files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    w = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, conf_entry["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), conf=conf,
                traffic=traffic, end_to_end=bench["end_to_end"],
                per_layer=bench["per_layer"])


def kind_module(kind: str):
    """``bench/cells/<kind>.py``, the module that runs a traffic kind."""
    return importlib.import_module(f"cells.{kind}")


def applies(metric: Dict, cell: str) -> bool:
    """Whether a metric of BENCHMARK.json is reported in ``cell``: in
    every cell, unless the metric lists its cells."""
    return cell in metric.get("workloads", [cell])


def end_to_end(cell: Cell, values: Dict[str, float]) -> Dict[str, Any]:
    """The cell's end-to-end metrics from what the run measured. A metric
    that BENCHMARK.json gives this cell and the run did not measure is an
    error, never a silent gap."""
    out = {}
    for m in cell.end_to_end:
        if not applies(m, cell.name):
            continue
        if m["name"] not in values:
            raise RuntimeError(f"bench: cell {cell.name} reports "
                               f"{m['name']}, which its kind does not "
                               f"measure")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out
