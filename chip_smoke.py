#!/usr/bin/env python3
"""Smoke run of the Gating-Dropout trainer and decoder on a TPU.

Trains zcode-m3-base, the paper's WMT-10 model, at its published widths
(d=512, 8 heads, d_ff=2048, 128 top-1 experts on every other FFN, vocab
64,000, bf16 activations, f32 params), cut by depth only to 2 encoder + 2
decoder layers. Gate-Drop runs at rate 0.3 with the host_cond strategy
through ``repro.training.Trainer``, then the trained params greedy-decode
through ``repro.serve.generate``. Weights and data are random from
``--seed``.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chips  # four chips: expert parallelism only

One chip, in order: every main-path Pallas kernel against its jnp
reference (the MoE layer of ``pallas`` and ``pallas_fused`` against
``moe_oracle``, flash decode contiguous and paged); training with the
default backend (``oracle`` on one chip), ``pallas`` and
``pallas_fused``, whose finite per-step losses must agree within
``LOSS_RTOL``; greedy decoding with the ``pallas`` MoE layers and flash
decode. Four chips: a data=4 mesh (32 experts per chip), the sharded MoE
layer against ``moe_oracle(ep=4)``, one routed and one dropped chunk, and
the routed executable must hold all-to-alls that the dropped one lacks.

Exits 2 unless JAX's platform is ``tpu``. Every failed check raises, so
the script exits 0 only when all phases passed; its last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# 8 sentences x 256 tokens per side: 2,048 routed tokens per MoE layer.
# compiled.memory_analysis() of the train chunk on a described v5e chip
# puts the largest step (oracle, dropped) at 12.8 GB of the 16 GB: 7.4 GB
# of f32 params + Adam state and 5.4 GB of temporaries, which grow with
# the tokens, so doubling the sentences does not fit.
BATCH, SEQ = 8, 256
TRAIN_STEPS = 6          # chunk=1: each step is one host_cond dispatch
DECODE_TOKENS = 8
# Each limit sits a few times above what the chip gave (v5e, jax 0.9.0).
# Per-step loss agreement between the default and the kernel backends:
# the f32 losses differed by at most 2.04e-5 relative, from how the MoE
# matmuls round (XLA einsum vs Mosaic kernels) inside the bf16 model. At
# the warmup lr a zeroed MoE output moves the loss by only 1.1e-4, so
# the limit stays close; it also guards the kernels' backward passes,
# which the forward layer checks below do not reach.
LOSS_RTOL = 5e-5
# Pallas MoE layers vs moe_oracle in f32, as the largest error over the
# output's largest magnitude: 3.5e-7 read. A bf16 pass anywhere in the
# layer errs by ~2^-9 of a value, a lost or misrouted row by its size.
MOE_RTOL = 1e-5
# Flash decode, contiguous and paged, vs the jnp reference on bf16
# inputs and outputs: 6.7e-3 read, under two bf16 steps (2^-8) of the max.
FLASH_RTOL = 2e-2
# Sharded MoE layer vs moe_oracle(ep=4): the same XLA math split over
# devices, both at float32 matmul precision.
SHARDED_RTOL = 1e-4
PAGE = 16                # paged flash decode: tokens per page


def smoke_config():
    """zcode-m3-base cut by depth only to 2 encoder + 2 decoder layers:
    one MoE and one dense FFN on each side."""
    from repro.configs import get_config
    cfg = get_config("zcode-m3-base")
    return dataclasses.replace(
        cfg, n_layers=2,
        encdec=dataclasses.replace(cfg.encdec, n_encoder_layers=2))


def with_backend(cfg, backend: str):
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, backend=backend))


def window_seed(gd, steps: int, start: int, min_each: int) -> int:
    """The first seed >= ``start`` whose consensus bits over steps
    [0, steps) hold each decision at least ``min_each`` times."""
    from repro.core.gating_dropout import drop_decisions_host
    for seed in range(start, start + 1000):
        d = drop_decisions_host(gd, seed, 0, steps)
        if min(int(d.sum()), int((~d).sum())) >= min_each:
            return seed
    raise RuntimeError("no seed draws both decisions")


def rel_err(got, want) -> float:
    """Largest error over the reference's largest magnitude."""
    import jax.numpy as jnp
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.abs(jnp.asarray(got, jnp.float32) - want).max()
                 / jnp.abs(want).max())


def check(name: str, err: float, tol: float) -> None:
    print(f"{name}: max rel err={err:.3e} tol={tol:g}")
    if not err <= tol:
        raise AssertionError(f"{name}: {err} > {tol}")


def mt_task(cfg):
    """The synthetic multilingual MT task and its BATCH-sentence batches."""
    from repro.data import MTTaskConfig, MultilingualMT
    task = MultilingualMT(MTTaskConfig(vocab=cfg.vocab, n_langs=8,
                                       max_len=SEQ,
                                       src_len=(SEQ // 4, SEQ - 6)))
    return task, task.train_batches(BATCH)


def train(cfg, seed: int, steps: int, ctx=None):
    """One Trainer run, chunk=1 so every step is one synchronous
    host_cond dispatch. Returns (trainer, per-step records with the
    step's wall seconds and its consensus bit)."""
    from repro.configs.base import TrainConfig
    from repro.core.gating_dropout import drop_decisions_host
    from repro.training import Trainer
    tc = TrainConfig(lr=1e-3, warmup_steps=1000, steps=steps, seed=seed)
    _, batch_fn = mt_task(cfg)
    trainer = Trainer(cfg, tc, batch_fn, ctx=ctx, chunk=1,
                      strategy="host_cond", log=None, log_every=1)
    _, hist = trainer.run()
    decs = drop_decisions_host(cfg.moe.gating_dropout, seed, 0, steps)
    prev = 0.0
    for rec, dec in zip(hist, decs):
        rec["dropped"], rec["wall_s"] = bool(dec), rec["time_s"] - prev
        prev = rec["time_s"]
    return trainer, hist


def pattern(hist) -> str:
    return "".join("D" if r["dropped"] else "R" for r in hist)


def one_batch(cfg):
    import jax.numpy as jnp
    return {k: jnp.asarray(v)[None]
            for k, v in mt_task(cfg)[1](0).items()}


def kernel_calls(trainer, decision: bool) -> int:
    """Mosaic kernels in the lowered routed/dropped chunk program."""
    txt = trainer.chunk_fn.lower(trainer.state, one_batch(trainer.cfg),
                                 decision).as_text()
    return txt.count("tpu_custom_call")


def timing(hist):
    """First dispatch of each decision (compile + run) and the mean of
    the later ones (steady), in seconds."""
    out = {}
    for dec, name in ((False, "routed"), (True, "dropped")):
        w = [r["wall_s"] for r in hist if r["dropped"] == dec]
        out[name] = {"first_s": w[0],
                     "steady_s": sum(w[1:]) / max(len(w) - 1, 1)}
    return out


def moe_layer_check(cfg, ctx, ep: int, backends, tol: float) -> None:
    """Each backend's MoE layer vs moe_oracle(ep) on one f32 input, both
    decisions, at float32 XLA matmul precision."""
    import jax
    from repro.core import get_backend, init_moe_params, moe_oracle
    p = init_moe_params(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, cfg.d_model))
    with jax.default_matmul_precision("float32"):
        for dec in (False, True):
            y_ref, _ = jax.jit(lambda p_, x_: moe_oracle(
                p_, x_, cfg, ep=ep, decision=dec))(p, x)
            for b in backends:
                y, _ = jax.jit(lambda p_, x_: get_backend(b)(
                    p_, x_, cfg, ctx, rng=None, decision=dec))(p, x)
                check(f"moe {b} vs oracle(ep={ep}) decision={dec}",
                      rel_err(y, y_ref), tol)


def flash_check(cfg) -> None:
    """Flash decode, contiguous and paged, vs the jnp reference at the
    model's decode widths over a 2*SEQ cache, bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import flash_decode, flash_decode_paged, ref
    b, h, kv, hd, s = (BATCH, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_,
                       2 * SEQ)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.bfloat16)
    idx = jnp.asarray(np.linspace(1, s - 1, b).astype(np.int32))
    want = ref.flash_decode_ref(q, k, v, idx)
    check("flash_decode vs ref", rel_err(flash_decode(q, k, v, idx), want),
          FLASH_RTOL)
    # the same cache as pages of a shuffled arena (page 0 is scratch)
    nb = s // PAGE
    page_of = 1 + np.random.RandomState(0).permutation(b * nb)
    arena = lambda a: jnp.zeros((b * nb + 1, PAGE, kv, hd), a.dtype).at[
        page_of].set(a.reshape(b * nb, PAGE, kv, hd))          # noqa: E731
    got = flash_decode_paged(q, arena(k), arena(v),
                             jnp.asarray(page_of.reshape(b, nb)), idx)
    check("flash_decode_paged vs ref", rel_err(got, want), FLASH_RTOL)


def train_phase(cfg, backend: str, seed: int):
    """Train with ``backend``; returns (params, losses)."""
    import numpy as np
    from repro.core.backend import resolve_backend
    cfg = with_backend(cfg, backend)
    trainer, hist = train(cfg, seed, TRAIN_STEPS)
    losses = [r["loss"] for r in hist]
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss with {backend}: {losses}")
    ran = resolve_backend(cfg.moe, None)
    n_k = kernel_calls(trainer, False)
    if ran.startswith("pallas") != (n_k > 0):
        raise AssertionError(f"{ran} lowered {n_k} Mosaic kernels")
    print(f"train[{backend}] ran backend={ran} mosaic_kernels={n_k} "
          f"pattern={pattern(hist)}")
    print(f"train[{backend}] losses={losses}")
    print(f"train[{backend}] seconds={json.dumps(timing(hist))}")
    return trainer.state["params"], losses


def decode_phase(params, cfg, max_new: int) -> None:
    """Greedy-decode a validation batch through the compiled engine, MoE
    layers on the ``pallas`` kernels and attention on flash decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import GenerateConfig, generate
    cfg = with_backend(cfg, "pallas")
    task, _ = mt_task(cfg)
    b = task.sample_batch(10_000, BATCH)
    inp = {"enc_tokens": jnp.asarray(b["enc_tokens"]),
           "tokens": jnp.asarray(b["tokens"][:, :1])}           # BOS
    t0 = time.perf_counter()
    res = generate(params, inp, cfg, GenerateConfig(
        max_new=max_new, eos_id=-1, flash_decode=True))
    toks, scores = jax.device_get((res.tokens, res.scores))
    dt = time.perf_counter() - t0
    if toks.shape != (BATCH, max_new) or not ((toks >= 0)
                                              & (toks < cfg.vocab)).all():
        raise AssertionError(f"decode produced {toks.shape} {toks}")
    if not np.isfinite(scores).all():
        raise AssertionError(f"decode scores {scores}")
    print(f"decode tokens[0]={toks[0].tolist()} shape={list(toks.shape)} "
          f"compile+run_s={dt:.3f}")


def one_chip(seed: int) -> None:
    import numpy as np
    from repro.kernels.platform import resolve_interpret
    if resolve_interpret(None) is not False:
        raise AssertionError("Pallas would run in the interpreter")
    cfg = smoke_config()
    print(f"config {cfg.arch_id}: d={cfg.d_model} heads={cfg.n_heads} "
          f"d_ff={cfg.d_ff} experts={cfg.moe.n_experts} vocab={cfg.vocab} "
          f"layers={cfg.encdec.n_encoder_layers}+{cfg.n_layers} "
          f"dtype={cfg.dtype} params={cfg.n_params()} "
          f"batch={BATCH}x{SEQ}/side")
    moe_layer_check(cfg, None, 1, ("pallas", "pallas_fused"), MOE_RTOL)
    flash_check(cfg)
    seed = window_seed(cfg.moe.gating_dropout, TRAIN_STEPS, seed, 2)
    print(f"seed={seed}")
    _, ref = train_phase(cfg, "auto", seed)
    for backend in ("pallas_fused", "pallas"):
        params, got = train_phase(cfg, backend, seed)
        rel = np.abs(np.subtract(got, ref)) / np.abs(ref)
        print(f"loss rel diff {backend} vs default: max={rel.max():.3e} "
              f"tol={LOSS_RTOL:g}")
        if not (rel <= LOSS_RTOL).all():
            raise AssertionError(f"losses disagree: {ref} vs {got}")
    decode_phase(params, cfg, DECODE_TOKENS)


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core.moe import ParallelContext
    from repro.launch.mesh import make_mesh
    n = jax.device_count()
    if n != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {n}")
    ctx = ParallelContext(mesh=make_mesh((4,), ("data",)))
    cfg = smoke_config()
    print(f"mesh data=4: {cfg.moe.n_experts // 4} experts per chip")
    moe_layer_check(cfg, ctx, 4, ("sharded",), SHARDED_RTOL)
    seed = window_seed(cfg.moe.gating_dropout, 2, seed, 1)
    trainer, hist = train(cfg, seed, 2, ctx=ctx)
    losses = [r["loss"] for r in hist]
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    print(f"train[sharded] seed={seed} pattern={pattern(hist)} "
          f"losses={losses} wire_bytes={[r['comm_wire_bytes'] for r in hist]}"
          f" seconds={[r['wall_s'] for r in hist]}")
    # the executables the run dispatched: the state and a batch placed as
    # the Trainer places them
    batch = jax.device_put(one_batch(cfg), trainer.batch_sharding)
    a2a = {}
    for dec, name in ((False, "routed"), (True, "dropped")):
        txt = trainer.chunk_fn.lower(trainer.state, batch,
                                     dec).compile().as_text()
        a2a[name] = txt.count("all-to-all(")
    print(f"all-to-all ops: routed={a2a['routed']} dropped={a2a['dropped']}")
    if not (a2a["routed"] > 0 and a2a["dropped"] == 0):
        raise AssertionError(f"all-to-all counts {a2a}")
    for path, leaf in jax.tree_util.tree_leaves_with_path(trainer.state):
        name = jax.tree_util.keystr(path)
        if "experts" in name:
            per_chip = leaf.addressable_shards[0].data.shape
            if per_chip[-3] * 4 != leaf.shape[-3]:
                raise AssertionError(f"{name} {leaf.shape} -> {per_chip}")
            print(f"{name}: {leaf.shape} -> {per_chip} per chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip expert-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"device_count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 2
    from repro.launch.env import enable_compile_cache
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)")
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args.seed)
    for d in jax.devices():
        print(f"peak_bytes_in_use[{d.id}]="
              f"{d.memory_stats()['peak_bytes_in_use']}")
    print(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
