"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers AND compiles on the production meshes, and extract the
roofline inputs (FLOPs, bytes, collective traffic) from the compiled
artifact.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Artifacts: benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json
"""
import os

# 512 simulated devices for the production meshes, merged into the
# caller's XLA_FLAGS (a caller-set device count wins)
_DEVICES_FLAG = "--xla_force_host_platform_device_count"
if _DEVICES_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_DEVICES_FLAG}=512").strip()

import argparse  # noqa: E402
import json
import re
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.obs import MetricsRegistry, Tracer, get_tracer, monotonic, \
    set_tracer
from repro.configs import (INPUT_SHAPES, ASSIGNED_ARCHS, applicable_pairs,
                           get_config, shape_applicable)
from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.core.moe import ParallelContext
from repro.launch.mesh import make_production_mesh
from repro.models.model import decode_step, init_cache, init_model, prefill
from repro.parallel.sharding import (batch_specs, cache_specs, param_specs,
                                     state_specs, to_shardings)
from repro.training.steps import init_train_state, make_train_step, total_loss

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """Model inputs for this (arch, shape) as ShapeDtypeStructs."""
    B = shape.global_batch
    L = shape.seq_len
    f32, i32 = jnp.float32, jnp.int32
    dt = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((B, L), i32), "labels": sds((B, L), i32),
                 "loss_mask": sds((B, L), f32)}
    else:
        batch = {"tokens": sds((B, L), i32)}
    if cfg.vlm is not None:
        batch["img_embeds"] = sds((B, cfg.vlm.n_image_tokens, cfg.vlm.d_image), dt)
    if cfg.encdec is not None:
        if cfg.encdec.frontend == "stub":
            batch["frames"] = sds((B, cfg.encdec.encoder_seq, cfg.d_model), dt)
        else:
            batch["enc_tokens"] = sds((B, cfg.encdec.encoder_seq), i32)
    return batch


# ---------------------------------------------------------------------------
# collective parsing
# ---------------------------------------------------------------------------

from repro.analysis import parse_collectives  # noqa: E402

# ---------------------------------------------------------------------------
# lowering per shape kind
# ---------------------------------------------------------------------------

def lower_combo(cfg: ModelConfig, shape: InputShape, mesh, *,
                static_decision=None, tag: str = "",
                tc_overrides=None) -> Dict[str, Any]:
    import dataclasses as dc
    ctx = ParallelContext(mesh=mesh)
    tc = TrainConfig(moment_dtype="bfloat16" if cfg.fsdp else "float32")
    if tc_overrides:
        tc = dc.replace(tc, **tc_overrides)
    key = jax.random.PRNGKey(0)
    tr = get_tracer()
    t0 = monotonic()

    sh = lambda specs: to_shardings(mesh, specs)

    if shape.kind == "train":
        state_shape = jax.eval_shape(
            lambda: init_train_state(init_model(key, cfg), tc))
        st_specs = sh(state_specs(cfg, ctx, state_shape))
        batch = input_specs(cfg, shape)
        b_specs = sh(batch_specs(cfg, ctx, batch))
        step = make_train_step(cfg, tc, ctx, jit=False)

        def fn(state, b):
            return step(state, b, static_decision)

        jitted = jax.jit(fn, in_shardings=(st_specs, b_specs),
                         out_shardings=(st_specs, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_shape, batch)
        tokens = shape.global_batch * shape.seq_len

    elif shape.kind == "prefill":
        params_shape = jax.eval_shape(lambda: init_model(key, cfg))
        p_specs = sh(param_specs(cfg, ctx, params_shape))
        batch = input_specs(cfg, shape)
        b_specs = sh(batch_specs(cfg, ctx, batch))
        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        c_specs = sh(cache_specs(cfg, ctx, cache_shape))

        def fn(params, b):
            return prefill(params, b, cfg, ctx, max_seq=shape.seq_len)

        jitted = jax.jit(fn, in_shardings=(p_specs, b_specs),
                         out_shardings=(None, c_specs))
        lowered = jitted.lower(params_shape, batch)
        tokens = shape.global_batch * shape.seq_len

    else:  # decode: ONE new token against a seq_len KV cache
        params_shape = jax.eval_shape(lambda: init_model(key, cfg))
        p_specs = sh(param_specs(cfg, ctx, params_shape))
        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        c_specs = sh(cache_specs(cfg, ctx, cache_shape))
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        idx = jax.ShapeDtypeStruct((), jnp.int32)

        def fn(params, caches, token, index):
            return decode_step(params, caches, token, index, cfg, ctx)

        jitted = jax.jit(fn, in_shardings=(p_specs, c_specs, None, None),
                         out_shardings=(None, c_specs),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_shape, cache_shape, tok, idx)
        tokens = shape.global_batch

    t_lower = monotonic() - t0
    tr.instant("dryrun.lowered", arch=cfg.arch_id, shape=shape.name,
               kind=shape.kind)
    t0 = monotonic()
    with tr.span("dryrun.compile", arch=cfg.arch_id, shape=shape.name):
        compiled = lowered.compile()
    t_compile = monotonic() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):      # one dict per program pre-jax-0.5
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)

    mem_d = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_d[f] = int(v)

    res = {
        "arch": cfg.arch_id,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": dict(mesh.shape),
        "n_devices": int(mesh.size),
        "tag": tag,
        "tokens_per_step": tokens,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "memory": mem_d,
        "collectives": colls,
        "lower_s": t_lower,
        "compile_s": t_compile,
    }
    return res


def art_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    suff = f"__{tag}" if tag else ""
    return os.path.join(ART_DIR, f"{arch}__{shape}__{mesh_name}{suff}.json")


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            static_decision=None, tag: str = "", verbose: bool = True,
            overrides: Dict[str, Any] = None,
            registry: MetricsRegistry = None):
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod512" if multi_pod else "pod256"
    res = lower_combo(cfg, shape, mesh, static_decision=static_decision,
                      tag=tag)
    if registry is not None:
        registry.counter("dryrun/combos").inc()
        registry.histogram("dryrun/lower_s").observe(res["lower_s"])
        registry.histogram("dryrun/compile_s").observe(res["compile_s"])
    path = art_path(arch, shape_name, mesh_name, tag)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    if verbose:
        gb = res["memory"].get("temp_size_in_bytes", 0) / 2**30
        arg = res["memory"].get("argument_size_in_bytes", 0) / 2**30
        a2a = res["collectives"].get("all-to-all", {})
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}{' '+tag if tag else ''}: "
              f"OK  flops/dev={res['flops']:.3g} temp={gb:.2f}GiB arg={arg:.2f}GiB "
              f"a2a={a2a.get('count',0)}ops/{a2a.get('bytes',0)/2**20:.1f}MiB "
              f"(lower {res['lower_s']:.0f}s compile {res['compile_s']:.0f}s)")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--lint-table", action="store_true",
                    help="print the static lint pass x executable matrix "
                         "(analysis/lint.py; pure lowering, nothing is "
                         "executed)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--decision", default=None, choices=[None, "routed", "dropped"],
                    help="bake a static gating-dropout decision (host_cond)")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans: exact cost_analysis "
                         "(XLA counts scan bodies once)")
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="enable the span tracer and write a Chrome-trace/"
                         "Perfetto JSON of lower/compile timing here")
    ap.add_argument("--metrics-out", default=None,
                    help="write lower/compile timing histograms here "
                         "(.prom/.txt = Prometheus text, else JSON)")
    args = ap.parse_args()
    set_tracer(Tracer(enabled=bool(args.trace_out)))
    reg = MetricsRegistry()
    if args.lint_table:
        from repro.analysis.lint import format_lint_table, lint_table
        print(format_lint_table(lint_table()))
        return
    dec = {None: None, "routed": False, "dropped": True}[args.decision]
    overrides = {}
    if args.seq_parallel:
        overrides["seq_parallel"] = True
    if args.no_remat:
        overrides["remat"] = False
    if args.unroll:
        overrides["scan_layers"] = False
    if args.dtype:
        overrides["dtype"] = args.dtype

    if args.all:
        ok, fail = 0, []
        for arch, shp in applicable_pairs():
            try:
                run_one(arch, shp, multi_pod=args.multi_pod,
                        static_decision=dec, tag=args.tag,
                        overrides=overrides, registry=reg)
                ok += 1
            except Exception as e:  # noqa: BLE001
                fail.append((arch, shp, f"{type(e).__name__}: {e}"))
                print(f"[dryrun] {arch} x {shp}: FAIL {type(e).__name__}: "
                      f"{str(e)[:300]}")
        print(f"[dryrun] done: {ok} ok, {len(fail)} failed")
        _dryrun_obs_out(args, reg)
        if fail:
            raise SystemExit(1)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    assert shape_applicable(args.arch, args.shape), \
        f"{args.arch} x {args.shape} marked inapplicable (see DESIGN.md §3)"
    res = run_one(args.arch, args.shape, multi_pod=args.multi_pod,
                  static_decision=dec, tag=args.tag, overrides=overrides,
                  registry=reg)
    print(json.dumps({k: v for k, v in res.items()
                      if k not in ("collectives",)}, indent=1))
    print(json.dumps(res["collectives"], indent=1))
    _dryrun_obs_out(args, reg)


def _dryrun_obs_out(args, reg: MetricsRegistry) -> None:
    if args.trace_out:
        get_tracer().export(args.trace_out)
    if args.metrics_out:
        if args.metrics_out.endswith((".prom", ".txt")):
            reg.to_prometheus(args.metrics_out)
        else:
            reg.to_json(args.metrics_out)





# ---------------------------------------------------------------------------
# exact costing by per-layer-type extrapolation
# ---------------------------------------------------------------------------
# XLA's HloCostAnalysis counts a while-loop (lax.scan) body ONCE, not
# x trip-count, so the scan-mode artifacts under-count FLOPs/bytes/
# collectives of deep models. Unrolling the full 61-100 layer models is
# too slow on this container, so instead we lower SMALL unrolled variants
# that preserve the layer-type structure, solve the linear system
#   metric(variant) = base + sum_type count_type(variant) * c_type
# and extrapolate every metric to the full depth. Costs are exactly linear
# in per-type layer counts (params, activations, collectives all scale
# per layer), so this is exact up to XLA fusion boundary effects.

def _variant_cfgs(cfg: ModelConfig):
    import dataclasses as dc
    mk = lambda **kw: dc.replace(cfg, scan_layers=False, **kw)
    if cfg.encdec is not None:
        e = cfg.encdec
        return [mk(n_layers=2, encdec=dc.replace(e, n_encoder_layers=2)),
                mk(n_layers=2, encdec=dc.replace(e, n_encoder_layers=4)),
                mk(n_layers=4, encdec=dc.replace(e, n_encoder_layers=2))]
    if cfg.vlm is not None:
        v = cfg.vlm
        return [mk(n_layers=5),
                mk(n_layers=10),
                mk(n_layers=4, vlm=dc.replace(v, cross_attn_period=2))]
    if cfg.hybrid is not None:
        h = cfg.hybrid
        return [mk(n_layers=4, hybrid=dc.replace(h, global_attn_layers=(0,))),
                mk(n_layers=5, hybrid=dc.replace(h, global_attn_layers=(0,))),
                mk(n_layers=5, hybrid=dc.replace(h, global_attn_layers=(0, 4)))]
    if cfg.moe is not None and cfg.moe.first_dense_layers > 0:
        import dataclasses as dc2
        m1 = dc2.replace(cfg.moe, first_dense_layers=1)
        m2 = dc2.replace(cfg.moe, first_dense_layers=2)
        return [mk(n_layers=2, moe=m1), mk(n_layers=3, moe=m1),
                mk(n_layers=3, moe=m2)]
    if cfg.moe is not None and cfg.moe.moe_layer_period > 1:
        return [mk(n_layers=2), mk(n_layers=4), mk(n_layers=6)]
    return [mk(n_layers=2), mk(n_layers=4)]


def _type_counts(cfg: ModelConfig):
    """{LayerSpec: n_layers} over decoder (+ encoder) plans."""
    from collections import Counter
    from repro.models.transformer import layer_plan
    c = Counter()
    for seg in layer_plan(cfg):
        for spec in seg.pattern:
            c[("dec", spec)] += seg.repeats
    if cfg.encdec is not None:
        for seg in layer_plan(cfg, encoder=True):
            for spec in seg.pattern:
                c[("enc", spec)] += seg.repeats
    return dict(c)


def _extract_metrics(res):
    m = {"flops": res["flops"], "bytes_accessed": res["bytes_accessed"]}
    for kind, rec in res["collectives"].items():
        for f in ("count", "bytes", "wire_bytes"):
            m[f"coll/{kind}/{f}"] = rec[f]
    return m


def exact_costs(arch: str, shape_name: str, *, multi_pod: bool = False,
                verbose: bool = True, overrides=None, tag: str = "exact",
                tc_overrides=None, static_decision=None):
    import dataclasses as dc
    import numpy as np
    cfg = get_config(arch)
    if overrides:
        moe_over = {k[4:]: v for k, v in overrides.items()
                    if k.startswith("moe.")}
        ssm_over = {k[4:]: v for k, v in overrides.items()
                    if k.startswith("ssm.")}
        plain = {k: v for k, v in overrides.items() if "." not in k}
        if moe_over and cfg.moe is not None:
            plain["moe"] = dc.replace(cfg.moe, **moe_over)
        if ssm_over and cfg.ssm is not None:
            plain["ssm"] = dc.replace(cfg.ssm, **ssm_over)
        cfg = dc.replace(cfg, **plain)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod512" if multi_pod else "pod256"
    variants = _variant_cfgs(cfg)
    full_counts = _type_counts(cfg)
    types = sorted(full_counts, key=str)
    rows, metrics_list = [], []
    t0 = monotonic()
    for vc in variants:
        counts = _type_counts(vc)
        assert set(counts) <= set(full_counts), \
            (arch, "variant introduces a layer type absent from full config")
        res = lower_combo(vc, shape, mesh, tag="exactvar",
                          tc_overrides=tc_overrides,
                          static_decision=static_decision)
        rows.append([1.0] + [float(counts.get(t, 0)) for t in types])
        metrics_list.append(_extract_metrics(res))
    a = np.array(rows)
    keys = sorted({k for m in metrics_list for k in m})
    pred = {}
    full_vec = np.array([1.0] + [float(full_counts[t]) for t in types])
    for k in keys:
        y = np.array([m.get(k, 0.0) for m in metrics_list])
        coef, *_ = np.linalg.lstsq(a, y, rcond=None)
        coef = np.maximum(coef, 0.0)     # costs are nonnegative
        pred[k] = float(full_vec @ coef)
    # assemble an artifact shaped like lower_combo's, memory from scan run
    scan_path = art_path(arch, shape_name, mesh_name,
                         "" if tag == "exact" else tag + "mem")
    memory = {}
    if os.path.exists(scan_path):
        with open(scan_path) as f:
            memory = json.load(f).get("memory", {})
    colls = {}
    for k, v in pred.items():
        if k.startswith("coll/"):
            _, kind, field = k.split("/")
            colls.setdefault(kind, {})[field] = v
    res = {
        "arch": cfg.arch_id, "shape": shape.name, "kind": shape.kind,
        "mesh": dict(mesh.shape), "n_devices": int(mesh.size),
        "tag": tag, "method": "layer-type extrapolation",
        "tokens_per_step": (shape.global_batch * shape.seq_len
                            if shape.kind != "decode" else shape.global_batch),
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "flops": pred.get("flops", -1.0),
        "bytes_accessed": pred.get("bytes_accessed", -1.0),
        "memory": memory, "collectives": colls,
        "lower_s": 0.0, "compile_s": monotonic() - t0,
    }
    with open(art_path(arch, shape_name, mesh_name, tag), "w") as f:
        json.dump(res, f, indent=1)
    if verbose:
        a2a = colls.get("all-to-all", {})
        print(f"[{tag}] {arch} x {shape_name} x {mesh_name}: "
              f"flops/dev={res['flops']:.3g} "
              f"a2a={a2a.get('wire_bytes', 0)/2**20:.1f}MiB "
              f"({res['compile_s']:.0f}s, {len(variants)} variants)")
    return res


def exact_main():
    import sys
    ok, fail = 0, []
    only = sys.argv[2] if len(sys.argv) > 2 else None
    for arch, shp in applicable_pairs():
        if only and arch != only:
            continue
        try:
            exact_costs(arch, shp)
            ok += 1
        except Exception as e:  # noqa: BLE001
            fail.append((arch, shp))
            print(f"[exact] {arch} x {shp}: FAIL {type(e).__name__}: "
                  f"{str(e)[:300]}")
    print(f"[exact] done: {ok} ok, {len(fail)} failed: {fail}")


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "--exact-all":
        exact_main()
    else:
        main()
