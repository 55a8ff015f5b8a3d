"""The four-chip cell ``train.big.gd30.ep4``: its files as the harness
reads them, the ``a2a_ms.routed`` reader and the reductions under it on a
hand-made trace, and a run of the harness on four virtual CPU devices
with the cell's architecture at a small size, sound and with the
exchange between chips left out."""
import json
import os
import subprocess
import sys

from benchpaths import BENCH, ROOT  # bench/ and src/ on the path

import pytest

import harness
import layer_reduce as LR
import metrics as M
import trace_reduce as TR
from cells import train_mt
from mt_traffic import MTTraffic

CELL = "train.big.gd30.ep4"
JIT = "jit(chunk_fn)/while/body/closed_call"


def test_cell_files_parse_to_the_published_big_model():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.traffic["mesh"] == {"data": 4}
    cfg = train_mt.program_config(cell.conf, cell.traffic)
    assert cfg.arch_id == "zcode-m3-big"
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (
        1024, 16, 4096, 128_000)
    assert (cfg.moe.n_experts, cfg.moe.top_k,
            cfg.moe.moe_layer_period) == (64, 1, 2)
    assert (cfg.encdec.n_encoder_layers, cfg.n_layers) == (2, 2)
    assert cell.conf["published"] == {"n_encoder_layers": 24,
                                      "n_decoder_layers": 12}
    gd = cfg.moe.gating_dropout
    assert (gd.mode, gd.rate, gd.strategy) == ("gate_drop", 0.3, "host_cond")
    t = MTTraffic(cell.traffic, cell.conf["vocab"], 2**33 + 7)
    # 4,096 positions per side per chip, over the four chips
    assert {s: r * s for s, r in t.rows.items()} == {
        s: 4 * 4096 for s in (32, 64, 128, 256)}
    # the same 26-step cycle as mt.gd30.tok2048: 8, 11, 6, 1 batches
    assert [t.cycle_buckets.count(s) for s in t.buckets] == [8, 11, 6, 1]


def test_each_configuration_has_its_own_source_or_cuts():
    # a configuration that repeats another's source and cut keys would be
    # the same model under a new name
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        configs = json.load(f)["configs"]
    keys = [(c["source"], tuple(sorted(c["reduced"]))) for c in configs]
    assert len(set(keys)) == len(keys)
    big = {c["name"]: c for c in configs}["zcode-m3-big.e2d2.ep4"]
    assert big["source"] == "https://arxiv.org/abs/2205.14336"


def test_a2a_reader_averages_routed_steps_only():
    read = M.reader("a2a_ms.routed")
    trace = {"a2a_s": [0.002, 0.0, 0.004, 0.0], "step_s": [0.1] * 4,
             "decisions": [False, True, False, True]}
    assert read({"trace": trace}) == pytest.approx(3.0)
    # no routed step traced, or a trace of another length: nothing to read
    assert read({"trace": dict(trace, decisions=[True] * 4)}) is None
    assert read({"trace": dict(trace, decisions=[False] * 3)}) is None


def hand_trace():
    """Window 10.0-11.0 s on two chips; a routed step whose all-to-alls
    sit under ``moe/exchange``, then a dropped step without them."""
    window = [("bench.window", 10.0, 11.0)]
    ops = [("fusion.1", 10.10, 10.20, f"{JIT}/jvp()/moe/shard_map/dot"),
           ("all-to-all.1", 10.20, 10.23,
            f"{JIT}/jvp()/moe/shard_map/exchange/all_to_all"),
           ("all-to-all.2", 10.25, 10.27, f"{JIT}/transpose(jvp())/"
            f"checkpoint/moe/shard_map/exchange/all_to_all"),
           ("fusion.2", 10.27, 10.30, f"{JIT}/optimizer/sub"),
           ("fusion.1", 10.60, 10.70, f"{JIT}/jvp()/moe/shard_map/dot")]
    mods = [("jit_chunk_fn(1)", 10.1, 10.3), ("jit_chunk_fn(2)", 10.6, 10.7)]
    return {"host": [window],
            "devices": {d: {"ops": ops, "modules": mods} for d in (0, 1)}}


def test_exchange_ops_count_under_moe_and_as_all_to_all():
    trace = hand_trace()
    layers = LR.reduce(trace, "bench.window", "chunk_fn", expect_steps=2)
    # the nested exchange scope is the MoE layer's time
    assert LR.scope_ms(layers, "moe", [False, True],
                       dropped=False) == pytest.approx(150.0)
    assert LR.scope_ms(layers, "moe", [False, True],
                       dropped=True) == pytest.approx(100.0)
    flat = {"host": trace["host"][0],
            "devices": {d: {"ops": [op[:3] for op in v["ops"]],
                            "modules": v["modules"]}
                        for d, v in trace["devices"].items()}}
    r = TR.reduce(flat, "bench.window", 10.0, [], "chunk_fn", 2)
    assert r["a2a_s"] == pytest.approx([0.05, 0.0])
    ctx = {"trace": dict(r, decisions=[False, True])}
    assert M.reader("a2a_ms.routed")(ctx) == pytest.approx(50.0)


EP4 = r'''
import dataclasses, json, sys
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import repro.core.moe as moe
from test_bench_faults import tiny_cell, run

class Local:
    """The wire left out: each chip's slots for expert e meet the expert
    in slot e mod (E / ep) of its own group."""
    def __init__(self, t, ep):
        self.t, self.ep = t, ep
    def telemetry(self, *a, **k):
        return self.t.telemetry(*a, **k)
    def pipelined(self, buf, fn):
        E, c, d = buf.shape
        n = E // self.ep
        x = buf.reshape(self.ep, n, c, d).transpose(1, 0, 2, 3)
        y = fn(x.reshape(n, self.ep * c, d)).reshape(n, self.ep, c, d)
        return y.transpose(1, 0, 2, 3).reshape(E, c, d)

cell = dataclasses.replace(tiny_cell(chips=4, data=4), name={cell!r})
# the cell's architecture at the CPU test size: 4 experts per device
cell.conf.update(arch="zcode-m3-big", n_experts=16)
cell.traffic["batching"]["tokens_per_side_per_chip"] = 32
sound = run(cell)
real = moe.make_transport
moe.make_transport = lambda comm, env: Local(real(comm, env), env.ep)
broken = run(cell)
print(json.dumps({{"sound": sound["correct"], "sound_checks": sound["checks"],
                   "broken": broken["correct"],
                   "broken_checks": broken["checks"]}}))
'''


def test_ep4_harness_run_is_correct_and_not_without_the_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EP4.format(src=os.path.join(ROOT, "src"), bench=BENCH,
                      tests=os.path.join(BENCH, "tests"), cell=CELL)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sound"] is True and out["broken"] is False, out
