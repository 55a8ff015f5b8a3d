"""Shared benchmark utilities: hardware profiles, timers, subprocess runner."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from dataclasses import dataclass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
ART = os.path.join(REPO, "benchmarks", "artifacts")


@dataclass(frozen=True)
class HwProfile:
    name: str
    flops: float          # peak FLOP/s per chip (bf16/fp16)
    hbm_bw: float         # bytes/s per chip
    link_bw: float        # bytes/s per chip interconnect (all-to-all usable)

    @property
    def desc(self):
        return (f"{self.name}: {self.flops/1e12:.0f} TFLOP/s, "
                f"{self.hbm_bw/1e9:.0f} GB/s HBM, "
                f"{self.link_bw/1e9:.1f} GB/s link")


# the TARGET for the roofline (per the spec): TPU v5e
TPU_V5E = HwProfile("tpu-v5e", 197e12, 819e9, 50e9)
# the paper's two clusters (approximate public specs)
V100_IB = HwProfile("v100-100Gb-IB", 112e12, 900e9, 12.5e9 / 8)   # IB shared per GPU
A100_IB = HwProfile("a100-1.6Tb-IB", 312e12, 2039e9, 200e9 / 8)


def timeit(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        r = fn(*args)
    _block(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    _block(r)
    return (time.perf_counter() - t0) / iters


def _block(r):
    import jax
    jax.tree.map(lambda a: a.block_until_ready()
                 if hasattr(a, "block_until_ready") else a, r)


def run_subprocess(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run ``code`` in a child with ``n_devices`` simulated CPU devices
    (pinned to the CPU backend: a parent on a chip host may hold it)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    row = f"{name},{us_per_call:.1f},{derived}"
    print(row)
    return row


def decode_bleu(params, cfg, task, **kw) -> float:
    """Corpus BLEU of greedy decodes on a validation batch (MT task).

    The paper's actual Table-2/4 metric. Thin alias for the ONE
    corpus-BLEU-via-engine helper (launch/train.py::greedy_bleu) so
    train-time eval and the benchmarks can never drift apart."""
    from repro.launch.train import greedy_bleu
    return greedy_bleu(params, cfg, task, **kw)


def run_trainer(cfg, tc, *, batch, task=None, chunk=8,
                strategy="traced_cond", seq=32, n_langs=8, prefetch=True):
    """Train via the scan-fused Trainer (DESIGN.md §8) on the synthetic MT
    task — THE train-loop helper for quality/throughput benchmarks, so
    they measure the production loop rather than a hand-rolled one.

    Returns (state, task, history)."""
    from repro.data import MTTaskConfig, MultilingualMT
    from repro.training import Trainer
    if task is None:
        task = MultilingualMT(MTTaskConfig(vocab=cfg.vocab, n_langs=n_langs,
                                           max_len=seq))
    trainer = Trainer(cfg, tc, task.train_batches(batch), chunk=chunk,
                      strategy=strategy, prefetch=prefetch, log=None)
    state, history = trainer.run()
    return state, task, history
