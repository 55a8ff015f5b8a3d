"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch zcode-m3-base --reduced \
      --steps 200 --batch 16 --task mt --gd-mode gate_drop --gd-rate 0.3

Runs on CPU at reduced scale (or on a real mesh via --mesh d,m). Training
executes through the scan-fused Trainer (DESIGN.md §8): `--chunk` steps
per compiled dispatch, prefetched input pipeline, metrics fetched at
chunk boundaries only. Uses the paper's host_cond strategy by default
(`--strategy`): same-decision runs dispatch to two executables, the
dropped one free of all-to-all; the per-step consensus bit comes from
the shared (seed, step) PRNG fold — see DESIGN.md §2.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import TrainConfig
from repro.core.moe import ParallelContext
from repro.data import MTTaskConfig, MultilingualMT, LMTaskConfig, SyntheticLM
from repro.launch.env import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.metrics import corpus_bleu, strip_special
from repro.obs import MetricsRegistry, Tracer, router_health, set_tracer
from repro.serve import GenerateConfig, generate
from repro.training import Trainer


def build_batch_fn(cfg, args):
    """Per-step numpy batches (the Trainer stacks them into chunks; keep
    this pure host work — it runs on the prefetch thread)."""
    if args.task == "mt":
        task = MultilingualMT(MTTaskConfig(vocab=cfg.vocab, n_langs=args.langs,
                                           max_len=args.seq))
        return task, task.train_batches(args.batch)
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=args.seq))
    return task, lambda step: task.sample_batch(step, args.batch)


def greedy_bleu(params, cfg, task, *, n=32, max_new=36, seed=10_000,
                ctx=None, lang=None):
    """Greedy decode a validation batch -> corpus BLEU (MT task only).

    THE corpus-BLEU-via-engine helper — the BLEU benchmarks call it too
    (benchmarks/common.py::decode_bleu). Decodes through the compiled
    engine (repro.serve, DESIGN.md §7): the first generated token comes
    from the prefill logits and the first decode_step runs at index
    ``prompt_len`` — the previous hand-rolled loop here fed index 0 after
    prefill, clobbering the BOS cache slot and corrupting every reported
    BLEU. ``lang`` restricts the validation batch to one language
    (Table-4 per-direction splits)."""
    kw = {} if lang is None else {"lang": lang}
    b = task.sample_batch(seed, n, **kw)
    batch = {"enc_tokens": jnp.asarray(b["enc_tokens"]),
             "tokens": jnp.asarray(b["tokens"][:, :1])}   # BOS
    res = generate(params, batch, cfg, GenerateConfig(max_new=max_new),
                   ctx=ctx)
    hyps = [strip_special(h) for h in np.asarray(res.tokens)]
    refs = [strip_special(r) for r in b["labels"]]
    return corpus_bleu(hyps, refs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zcode-m3-base")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--langs", type=int, default=8)
    ap.add_argument("--task", default="mt", choices=["mt", "lm"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--schedule", default="inverse_sqrt",
                    choices=["inverse_sqrt", "cosine", "constant"],
                    help="LR schedule (optim/adam.py)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(--batch must divide evenly)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=8,
                    help="steps per scan-fused train dispatch (DESIGN.md §8)")
    ap.add_argument("--strategy", default="host_cond",
                    choices=["traced_cond", "host_cond"],
                    help="gating-dropout execution strategy (DESIGN.md §5); "
                         "host_cond is paper-faithful")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="synthesize chunks inline instead of on the "
                         "background prefetch thread")
    ap.add_argument("--gd-mode", default=None,
                    choices=[None, "off", "gate_drop", "gate_expert_drop"])
    ap.add_argument("--gd-rate", type=float, default=None)
    ap.add_argument("--router", default=None,
                    choices=[None, "softmax", "sigmoid", "hash"])
    ap.add_argument("--backend", default=None,
                    choices=[None, "auto", "oracle", "sharded", "pallas",
                             "pallas_fused"],
                    help="MoE execution backend (DESIGN.md §6, §11)")
    ap.add_argument("--mesh", default=None, help="e.g. 4,2 => (data,model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint in --ckpt-dir "
                         "(params + opt + step) and continue training")
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--no-metrics-frame", action="store_true",
                    help="drop the in-graph router/comm MetricsFrame "
                         "outputs (telemetry only — the loss/update math "
                         "is bitwise identical either way, DESIGN.md §15)")
    ap.add_argument("--trace-out", default=None,
                    help="enable the span tracer and write a Chrome-trace/"
                         "Perfetto JSON of the run here (DESIGN.md §15)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a metrics-registry summary of the run "
                         "(.prom/.txt = Prometheus text, else JSON)")
    ap.add_argument("--jax-profile", default=None, metavar="LOGDIR",
                    help="wrap the run in a jax.profiler trace window "
                         "(TensorBoard/Perfetto logdir)")
    args = ap.parse_args()

    enable_compile_cache()
    # spans also name the chunks on the device timeline of --jax-profile
    tracer = Tracer(enabled=bool(args.trace_out or args.jax_profile))
    set_tracer(tracer)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.moe is not None and (args.gd_mode or args.gd_rate is not None
                                or args.router or args.backend):
        gd = cfg.moe.gating_dropout
        gd = dataclasses.replace(
            gd,
            mode=args.gd_mode if args.gd_mode else gd.mode,
            rate=args.gd_rate if args.gd_rate is not None else gd.rate)
        moe = dataclasses.replace(
            cfg.moe, gating_dropout=gd,
            router_type=args.router or cfg.moe.router_type,
            backend=args.backend or cfg.moe.backend)
        cfg = dataclasses.replace(cfg, moe=moe)

    ctx = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        ctx = ParallelContext(mesh=make_mesh(shape, ("data", "model")[:len(shape)]))

    tc = TrainConfig(lr=args.lr, warmup_steps=args.warmup, steps=args.steps,
                     seed=args.seed, schedule=args.schedule,
                     microbatches=args.microbatches,
                     metrics_frame=not args.no_metrics_frame)
    task, batch_fn = build_batch_fn(cfg, args)
    eval_fn = None
    if args.eval_every and args.task == "mt":
        eval_fn = lambda state, step: {  # noqa: E731
            "bleu": greedy_bleu(state["params"], cfg, task, ctx=ctx)}
    trainer = Trainer(cfg, tc, batch_fn, ctx=ctx, chunk=args.chunk,
                      strategy=args.strategy, ckpt_dir=args.ckpt_dir,
                      eval_every=args.eval_every, eval_fn=eval_fn,
                      log_every=args.log_every,
                      prefetch=not args.no_prefetch)
    if args.resume:
        assert args.ckpt_dir, "--resume needs --ckpt-dir"
        # restore() continues at the ABSOLUTE step: after --resume both the
        # data stream (batch_fn) and the Gating-Dropout consensus PRNG
        # (seed, step) pick up exactly where the checkpointed run left off
        print(f"resumed {args.ckpt_dir} @ step {trainer.restore()}")
    with tracer.profile_window(args.jax_profile):
        state, history = trainer.run()
    if args.ckpt_dir:
        print(f"checkpoint -> {args.ckpt_dir}")
    gd = cfg.moe.gating_dropout if cfg.moe is not None else None
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"arch": cfg.arch_id, "history": history,
                       "gd": dataclasses.asdict(gd) if gd else None}, f)
    if args.trace_out:
        tracer.export(args.trace_out)
    if args.metrics_out:
        reg = MetricsRegistry()
        loss_h = reg.histogram("train/loss", "recorded per-step loss")
        tok_h = reg.histogram("train/tok_s", "tokens/s at record points")
        for rec in history:
            loss_h.observe(rec["loss"])
            tok_h.observe(rec["tok_s"])
        if history:
            reg.gauge("train/final_loss").set(history[-1]["loss"])
            reg.gauge("train/wall_s").set(history[-1]["time_s"])
        rh = router_health(history)
        if rh["records"]:
            for k, v in rh.items():
                reg.gauge(f"train/router/{k}").set(float(v))
        if path_is_prom := args.metrics_out.endswith((".prom", ".txt")):
            reg.to_prometheus(args.metrics_out)
        if not path_is_prom:
            reg.to_json(args.metrics_out)


if __name__ == "__main__":
    main()
