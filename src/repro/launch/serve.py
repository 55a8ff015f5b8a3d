"""Serving CLI: one-shot batched decode OR a continuous-batching loop.

One-shot (the compiled engine, DESIGN.md §7):

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --batch 8 --prompt-len 64 --max-new 32
  PYTHONPATH=src python -m repro.launch.serve --arch zcode-m3-base --reduced \
      --beam 4                      # beam search
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --temperature 0.8 --top-k 40  # sampling

Continuous batching (slot pool + scheduler, DESIGN.md §9): ``--trace N``
synthesizes N requests with Poisson arrivals (``--rate`` req/s), mixed
prompt lengths and per-request token budgets, serves them through
``repro.serve.ContinuousScheduler``, and reports TTFT / per-token
latency / throughput percentiles (``--json-out`` for machines):

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --trace 32 --rate 50 --slots 8 --json-out serve.json

MoE archs honour ``--backend`` (DESIGN.md §6) and ``--local-routing``
(Gate-Drop local path at decode: no all-to-all in the sharded decode
executable, DESIGN.md §9).

PRNG discipline: parameter init, prompt synthesis, and sampling each fold
a DISTINCT stream off ``--seed`` (folds 0/1/2) — reusing one key made
"random" prompts functions of the weights.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.configs import PagedKVConfig, get_config, reduced
from repro.launch.env import enable_compile_cache
from repro.models import init_model
from repro.obs import (Histogram, MetricsRegistry, Tracer, monotonic,
                       set_tracer)
from repro.serve import (ContinuousScheduler, GenerateConfig, PagedScheduler,
                         Request, make_generate_fn, paged_kv_bytes)


def synth_batch(cfg, key, batch: int, prompt_len: int):
    """Conditioning inputs for a batch of synthetic prompts; each field
    draws from its own fold of ``key``."""
    out = {"tokens": jax.random.randint(
        jax.random.fold_in(key, 0), (batch, prompt_len), 3, cfg.vocab)}
    if cfg.vlm is not None:
        out["img_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1),
            (batch, cfg.vlm.n_image_tokens, cfg.vlm.d_image))
    if cfg.encdec is not None:
        if cfg.encdec.frontend == "stub":
            out["frames"] = jax.random.normal(
                jax.random.fold_in(key, 2),
                (batch, cfg.encdec.encoder_seq, cfg.d_model))
        else:
            out["enc_tokens"] = jax.random.randint(
                jax.random.fold_in(key, 3), (batch, 32), 3, cfg.vocab)
    return out


def synth_trace(cfg, key, n: int, rate: float, buckets, max_new: int):
    """Synthetic request trace: Poisson arrivals (exponential gaps at
    ``rate`` req/s), prompt lengths uniform over [2, max bucket], token
    budgets uniform over [2, max_new]."""
    rs = np.random.RandomState(
        np.asarray(jax.random.key_data(key))[-1] & 0x7FFFFFFF)
    gaps = rs.exponential(1.0 / rate, size=n)
    arrivals = np.cumsum(gaps) - gaps[0]
    reqs = []
    for i in range(n):
        plen = int(rs.randint(2, buckets[-1] + 1))
        budget = int(rs.randint(2, max_new + 1))
        toks = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 10 + i), (plen,), 3, cfg.vocab),
            np.int32)
        extras = {}
        row = synth_batch(cfg, jax.random.fold_in(key, 1000 + i), 1, 1)
        for k, v in row.items():
            if k != "tokens":
                extras[k] = np.asarray(v[0])
        reqs.append(Request(rid=i, tokens=toks, extras=extras,
                            max_new=budget, arrival=float(arrivals[i])))
    return reqs


def _pcts(xs):
    # NaN-safe through a registry histogram: np.percentile raised on an
    # empty sample list (zero-request traces); the snapshot never does
    h = Histogram("_pcts")
    for x in xs:
        h.observe(x)
    return h.percentiles((50, 90, 99))


def trace_comm_section(cfg, gen, sched, ep: int) -> dict:
    """Price every executed device call of a trace with the exchange's
    bytes model (comm/exchange.py, DESIGN.md §10): per-tick wire bytes a
    sharded deployment at expert-parallel width ``ep`` would move, as
    totals + percentiles. Decode ticks under ``--local-routing`` move
    zero bytes (the Gate-Drop local path has no all-to-all)."""
    from repro.comm import layer_cost
    from repro.training.steps import n_moe_layers
    nl = n_moe_layers(cfg)
    per_tick = []
    for kind, toks in sched.tick_log:
        if kind == "decode" and gen.local_routing:
            per_tick.append(0.0)
            continue
        c = layer_cost(cfg, tokens_per_shard=max(toks // ep, 1), ep=ep,
                       is_training=False)
        per_tick.append(c["wire_bytes"] * nl)
    return {
        "ep_model": ep,
        "n_ticks": len(per_tick),
        "wire_bytes_total": float(sum(per_tick)),
        "wire_bytes_per_tick": _pcts(per_tick) if per_tick else {},
    }


def trace_cache_section(sched: PagedScheduler) -> dict:
    """Paged-KV occupancy report for a --trace run: page/prefix stats
    mirror the comm section's role for DESIGN.md §13 — what the arena
    actually held vs what a slot pool would have pinned."""
    lay = sched.layout
    return {
        "page_size": lay.page_size,
        "n_pages": lay.n_pages,
        "n_blocks": lay.n_blocks,
        "peak_pages_in_use": sched.stats["peak_pages_in_use"],
        "peak_kv_bytes": int(sched.stats["peak_pages_in_use"]
                             * sched.page_bytes),
        "arena_kv_bytes": int(paged_kv_bytes(sched.pool, sched.cfg))
        if sched.pool is not None else 0,
        "prefix_hit_rate": (sched.stats["prefix_hits"]
                            / max(sched.stats["prefix_lookups"], 1)),
        "prefix_hits": sched.stats["prefix_hits"],
        "cow_copies": sched.stats["cow_copies"],
        "preemptions": sched.stats["preemptions"],
        "swap_ins": sched.stats["swap_ins"],
        "mean_alive_slots": (float(np.mean(sched.alive_log))
                             if sched.alive_log else 0.0),
    }


def run_trace(args, cfg, params, gen, key_prompts, key_sample) -> dict:
    buckets = tuple(int(b) for b in args.buckets.split(","))
    # trace synthesis draws from the PROMPT stream; key_sample feeds only
    # the scheduler's per-request sampling folds — distinct parent folds,
    # so prompt and sampling keys can never collide
    reqs = synth_trace(cfg, key_prompts, args.trace,
                       args.rate, buckets, gen.max_new)
    reg = MetricsRegistry()
    if args.paged:
        paged = PagedKVConfig(page_size=args.page_size,
                              n_pages=args.pages,
                              prefix_caching=not args.no_prefix_cache)
        sched = PagedScheduler(params, cfg, gen, paged=paged,
                               n_slots=args.slots, prefill_buckets=buckets,
                               admit_width=args.admit_width,
                               rng=key_sample, registry=reg)
    else:
        sched = ContinuousScheduler(params, cfg, gen, n_slots=args.slots,
                                    prefill_buckets=buckets,
                                    admit_width=args.admit_width,
                                    rng=key_sample, registry=reg)
    t0 = monotonic()
    results = sched.run(reqs)
    wall = monotonic() - t0
    n_tok = int(sum(r.length for r in results))
    # percentiles come from the registry histograms the scheduler filled
    # at retire time — the registry is THE backing store (DESIGN.md §15)
    rec = {
        "mode": "continuous",
        "arch": cfg.arch_id,
        "n_requests": len(results),
        "n_tokens": n_tok,
        "wall_s": wall,
        "tok_s": n_tok / wall,
        "req_s": len(results) / wall,
        "ttft_s": reg.histogram("serve/ttft_s").percentiles((50, 90, 99)),
        "per_token_latency_s": reg.histogram(
            "serve/per_token_latency_s").percentiles((50, 90, 99)),
        "scheduler": dict(sched.stats),
        "slots": args.slots,
        "buckets": list(buckets),
        "local_routing": gen.local_routing,
    }
    if cfg.moe is not None:
        rec["comm"] = trace_comm_section(cfg, gen, sched, args.comm_ep)
    if args.paged:
        rec["cache"] = trace_cache_section(sched)
    # throughput + scheduler stats land in the same store so one
    # --metrics-out file carries the whole serving picture
    reg.gauge("serve/wall_s").set(wall)
    reg.gauge("serve/tok_s").set(rec["tok_s"])
    reg.gauge("serve/req_s").set(rec["req_s"])
    for k, v in sched.stats.items():
        reg.gauge(f"serve/stats/{k}").set(float(v))
    if args.metrics_out:
        _write_metrics(reg, args.metrics_out)
    return rec


def _write_metrics(reg: MetricsRegistry, path: str) -> None:
    """.prom/.txt -> Prometheus text exposition, anything else -> JSON."""
    if path.endswith((".prom", ".txt")):
        reg.to_prometheus(path)
    else:
        reg.to_json(path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sampling pool size (0 = full vocab)")
    ap.add_argument("--beam", type=int, default=1,
                    help=">1 = beam search (overrides sampling)")
    ap.add_argument("--eos", type=int, default=GenerateConfig.eos_id,
                    help="EOS token id for early exit (-1 = generate "
                         "max-new tokens unconditionally); default matches "
                         "GenerateConfig.eos_id")
    ap.add_argument("--backend", default=None,
                    choices=[None, "auto", "oracle", "sharded", "pallas",
                             "pallas_fused"],
                    help="MoE execution backend (DESIGN.md §6, §11)")
    ap.add_argument("--comm-ep", type=int, default=1,
                    help="expert-parallel width the --trace comm "
                         "accounting prices the wire at (default 1 = "
                         "this process)")
    ap.add_argument("--local-routing", action="store_true",
                    help="Gate-Drop local routing at decode: MoE tokens "
                         "stay in the local expert group, no all-to-all "
                         "in the decode executable (DESIGN.md §9)")
    ap.add_argument("--flash-decode", action="store_true",
                    help="route full-cache decode attention through the "
                         "kernels.flash_decode Pallas kernel (DESIGN.md "
                         "§11; ring/window caches keep the reference path)")
    # continuous batching
    ap.add_argument("--trace", type=int, default=0,
                    help="N>0: serve N synthetic Poisson-arrival requests "
                         "through the continuous-batching scheduler")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="trace arrival rate, requests/s")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slot-pool size")
    ap.add_argument("--admit-width", type=int, default=None,
                    help="admission group width (default min(4, slots))")
    ap.add_argument("--buckets", default="8,16,32,64",
                    help="prefill length buckets, comma-separated")
    ap.add_argument("--paged", action="store_true",
                    help="serve --trace through the paged-KV scheduler "
                         "(block-table decode cache, DESIGN.md §13)")
    ap.add_argument("--page-size", type=int,
                    default=PagedKVConfig.page_size,
                    help="KV page size in tokens (--paged)")
    ap.add_argument("--pages", type=int, default=PagedKVConfig.n_pages,
                    help="physical page count (0 = n_slots_equiv full-"
                         "length requests' worth, --paged)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix page caching (--paged)")
    ap.add_argument("--json-out", default=None,
                    help="write metrics JSON here")
    ap.add_argument("--trace-out", default=None,
                    help="enable the span tracer and write a Chrome-trace/"
                         "Perfetto JSON of scheduler ticks here "
                         "(DESIGN.md §15)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the serving metrics registry here "
                         "(.prom/.txt = Prometheus text, else JSON)")
    args = ap.parse_args()

    enable_compile_cache()
    tracer = Tracer(enabled=bool(args.trace_out))
    set_tracer(tracer)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.moe is not None and args.backend:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, backend=args.backend))
    # distinct PRNG streams: params / prompts / sampling
    key = jax.random.PRNGKey(args.seed)
    key_params = jax.random.fold_in(key, 0)
    key_prompts = jax.random.fold_in(key, 1)
    key_sample = jax.random.fold_in(key, 2)
    params = init_model(key_params, cfg)

    gen = GenerateConfig(max_new=args.max_new, temperature=args.temperature,
                         top_k=args.top_k, beam_width=args.beam,
                         eos_id=args.eos, local_routing=args.local_routing,
                         flash_decode=args.flash_decode)

    if args.trace > 0:
        rec = run_trace(args, cfg, params, gen, key_prompts, key_sample)
        print(f"arch={rec['arch']} served {rec['n_requests']} requests, "
              f"{rec['n_tokens']} tokens in {rec['wall_s']:.2f} s "
              f"({rec['tok_s']:.0f} tok/s)")
        print(f"TTFT p50/p90/p99: "
              + "/".join(f"{rec['ttft_s'][p]*1e3:.1f}" for p in (50, 90, 99))
              + " ms; per-token latency p50/p90/p99: "
              + "/".join(f"{rec['per_token_latency_s'][p]*1e3:.2f}"
                         for p in (50, 90, 99)) + " ms")
        print("scheduler:", rec["scheduler"])
        if "comm" in rec:
            c = rec["comm"]
            pt = c["wire_bytes_per_tick"]
            print(f"comm[ep={c['ep_model']}]: "
                  f"{c['wire_bytes_total']/2**20:.2f} MiB wire over "
                  f"{c['n_ticks']} ticks; per-tick KiB p50/p90/p99: "
                  + "/".join(f"{pt[p]/2**10:.1f}" for p in (50, 90, 99)))
        if "cache" in rec:
            k = rec["cache"]
            print(f"cache[paged {k['page_size']}tok]: peak "
                  f"{k['peak_pages_in_use']}/{k['n_pages']} pages "
                  f"({k['peak_kv_bytes']/2**20:.2f} MiB KV), prefix hit "
                  f"rate {k['prefix_hit_rate']:.2f}, {k['cow_copies']} "
                  f"COW, {k['preemptions']} preemptions")
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(rec, f, indent=1)
        if args.trace_out:
            tracer.export(args.trace_out)
        return

    batch = synth_batch(cfg, key_prompts, args.batch, args.prompt_len)
    fn = make_generate_fn(cfg, gen)
    t0 = monotonic()
    with tracer.span("generate.compile"):
        res = jax.block_until_ready(fn(params, batch, key_sample))
    t_compile = monotonic() - t0
    t0 = monotonic()
    with tracer.span("generate.steady"):
        res = jax.block_until_ready(fn(params, batch, key_sample))
    dt = monotonic() - t0
    n_tok = int(np.asarray(res.lengths).sum())
    print(f"arch={cfg.arch_id} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.max_new} beam={args.beam}")
    print(f"compile+first: {t_compile:.2f} s; steady: {dt*1e3:.1f} ms "
          f"({dt/max(int(res.steps), 1)*1e3:.2f} ms/step, "
          f"{n_tok/dt:.0f} tok/s)")
    print("sample:", np.asarray(res.tokens)[0][:16].tolist())
    if args.json_out:
        rec = {"mode": "oneshot", "arch": cfg.arch_id,
               "n_tokens": n_tok, "wall_s": dt, "tok_s": n_tok / dt,
               "compile_s": t_compile}
        with open(args.json_out, "w") as f:
            json.dump(rec, f, indent=1)
    if args.trace_out:
        tracer.export(args.trace_out)
    if args.metrics_out:
        reg = MetricsRegistry()
        reg.gauge("serve/compile_s").set(t_compile)
        reg.gauge("serve/wall_s").set(dt)
        reg.gauge("serve/tok_s").set(n_tok / dt)
        _write_metrics(reg, args.metrics_out)


if __name__ == "__main__":
    main()
