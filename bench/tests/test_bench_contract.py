"""BENCHMARK.json against the benchmark's contract: every cell finds its
configuration, traffic and metric files by name, names and units use
only the allowed characters, and every per-layer metric moves an
end-to-end metric that each cell it lists reports."""
import json
import os
import re

from benchpaths import BENCH, ROOT  # bench/ and src/ on the path

import pytest


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
# widths, heads and experts per token: never cut
WIDTHS = {"d_model", "d_ff", "n_heads", "top_k"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level(bench):
    assert set(bench) == TOP_KEYS
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in bench["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and k in conf
            assert not k.endswith(("_dim", "_rank")) and k not in WIDTHS
        assert conf["reduced"] == c["reduced"]
        for k, published in conf.get("published", {}).items():
            assert k in c["reduced"] and conf[k] != published
        for text in (c["why"], c["source"]):
            assert 1 <= len(text) <= 200 and "\n" not in text


def test_cells(bench):
    seen = set()
    confs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def _reports(bench, metric, cell):
    return cell in metric.get("workloads", [w["name"]
                                            for w in bench["workloads"]])


def test_metrics(bench):
    names = set()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        target = e2e[m["moves"]]
        for w in bench["workloads"]:
            if _reports(bench, m, w["name"]):
                assert _reports(bench, target, w["name"])
    for w in bench["workloads"]:
        assert _reports(bench, e2e["setup_s"], w["name"])
        assert any(_reports(bench, m, w["name"]) for m in bench["per_layer"])
        assert any(_reports(bench, m, w["name"]) for m in bench["end_to_end"]
                   if m["name"] != "setup_s")


def test_each_cell_kind_measures_its_end_to_end_metrics(bench):
    import harness
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(BENCH, "cells", kind + ".py"))
        measured = set(harness.kind_module(kind).END_TO_END)
        for m in bench["end_to_end"]:
            if harness.applies(m, w["name"]):
                assert m["name"] in measured, (w["name"], m["name"])


def _cell(bench, **kw):
    import harness
    args = dict(name="c", chips=1, conf={}, traffic={},
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
    args.update(kw)
    return harness.Cell(**args)


def test_a_metric_the_run_did_not_measure_is_an_error(bench):
    import harness
    e2e = [{"name": "a", "unit": "s"}, {"name": "b", "unit": "s"},
           {"name": "c_only", "unit": "s", "workloads": ["other"]}]
    cell = _cell(bench, end_to_end=e2e)
    assert harness.end_to_end(cell, {"a": 1.0, "b": 2.0}) == {
        "a": {"value": 1.0, "unit": "s"}, "b": {"value": 2.0, "unit": "s"}}
    with pytest.raises(RuntimeError, match="does not measure"):
        harness.end_to_end(cell, {"a": 1.0})


def test_a_per_layer_metric_with_nothing_to_read_is_an_error(bench):
    import metrics as M
    m = next(m for m in bench["per_layer"] if m["name"] == "step_ms.routed")
    cell = _cell(bench, name=m["workloads"][0], per_layer=[m])
    trace = {"step_s": [0.1, 0.2], "decisions": [False, True]}
    assert M.read_all(cell, {"trace": trace}) == {
        "step_ms.routed": {"value": pytest.approx(100.0), "unit": "ms"}}
    trace["decisions"] = [True, True]
    with pytest.raises(RuntimeError, match="nothing to read"):
        M.read_all(cell, {"trace": trace})
