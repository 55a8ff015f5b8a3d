"""A run of the harness at a small size on the CPU, with no look for a
chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false. The faults are those a training
cell can have: a step that returns its state unchanged, half of the
batch left out (the mean taken over the rest), and, on four devices,
the exchange between chips left out."""
import json
import os
import subprocess
import sys
import tempfile
import time

from benchpaths import BENCH, ROOT  # bench/ and src/ on the path

import pytest

import harness
from cells import train_mt

DATA = os.path.join(BENCH, "tests", "data")


def tiny_cell(chips: int = 1, data: int = 1) -> harness.Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(DATA, "tiny_config.json")) as f:
        conf = json.load(f)
    with open(os.path.join(DATA, "tiny_traffic.json")) as f:
        traffic = json.load(f)
    traffic["mesh"] = {"data": data}
    return harness.Cell("train.base.gd30", chips, conf, traffic,
                           bench["end_to_end"], bench["per_layer"])


def run(cell, fault=None, seed=2**31 + 5, variants=()):
    return train_mt.run(cell, seed, 0.2, False,
                        t_process=time.perf_counter(),
                        out_dir=tempfile.gettempdir(), require_tpu=False,
                        fault=fault, variants=variants)


def state_unchanged(trainer):
    import jax
    import jax.numpy as jnp
    inner = trainer.chunk_fn

    def chunk_fn(state, batches, decision):
        kept = jax.tree.map(jnp.copy, state)
        _, metrics = inner(state, batches, decision)
        return kept, metrics

    trainer.chunk_fn = chunk_fn


def half_batch(trainer):
    inner = trainer.chunk_fn

    def chunk_fn(state, batches, decision):
        b = next(iter(batches.values())).shape[1]
        return inner(state, {k: v[:, : b // 2] for k, v in batches.items()},
                     decision)

    trainer.chunk_fn = chunk_fn


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    # one persistent compile cache for this module's runs, off the
    # checkout's: each run builds its programs anew
    import jax
    d = str(tmp_path_factory.mktemp("jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    jax.config.update("jax_compilation_cache_dir", d)
    yield tiny_cell()
    jax.config.update("jax_compilation_cache_dir", None)
    del os.environ["JAX_COMPILATION_CACHE_DIR"]


def test_sound_run_is_correct_and_control_is_not(cell):
    r = run(cell, variants=[("control", "fp8", "")])
    assert r["correct"] is True, r["checks"]
    # the control, the reference computed in float8_e4m3, fails a limit
    limits = cell.traffic["limits"]
    assert any(v > limits[k] for k, v in r["variants"]["control"].items())
    assert set(r["metrics"]) == {"train_tokens_per_s", "peak_hbm_gb",
                                 "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(cell, fault):
    r = run(cell, fault)
    assert r["correct"] is False, r["checks"]


NO_EXCHANGE = r'''
import json, sys, time
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import repro.core.moe as moe
from test_bench_faults import tiny_cell, run

class Local:
    """The wire left out: each chip's slots for expert e meet the
    expert in slot e mod (E / ep) of its own group."""
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

cell = tiny_cell(chips=4, data=4)
cell.traffic["batching"]["tokens_per_side_per_chip"] = 32
sound = run(cell)
real = moe.make_transport
moe.make_transport = lambda comm, env: Local(real(comm, env), env.ep)
broken = run(cell)
print(json.dumps({{"sound": sound["correct"], "broken": broken["correct"],
                   "checks": broken["checks"]}}))
'''


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = NO_EXCHANGE.format(src=os.path.join(ROOT, "src"), bench=BENCH,
                              tests=os.path.join(BENCH, "tests"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sound"] is True and out["broken"] is False, out
