"""Cells of kind ``train_mt``: one run of MT training. Set-up, checked
first steps, the measured window, an optional traced window, and the
comparison with the reference.

The program under test is ``repro.training.Trainer``, built as
``repro.launch.train`` builds it (host_cond Gating Dropout, chunk from the
traffic file), fed the benchmark's weights and batches. One Trainer
object is built and driven from step 0. Its first ``check_steps`` steps
meet every length bucket under both decisions and are the steps the
reference repeats; the rest of the traffic's warm-up steps meet every
other (bucket, decision) pair, so that every executable the window uses
is loaded or compiled in set-up. The window then continues the same run,
in whole cycles of the traffic, until ``seconds`` have passed, and ends
on ``block_until_ready`` of the state.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import harness
import reference as R
from harness import Cell
from mt_traffic import MTTraffic, padded_fraction, real_tokens
from weights import (ModelSpec, is_leaf, leaf_names, layout, make_canonical,
                     seed_key)

WEIGHTS_STREAM = 1
END_TO_END = ("train_tokens_per_s", "peak_hbm_gb", "setup_s")


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(conf: Dict, traffic: Dict):
    """The program's ModelConfig for a configuration file and a traffic
    mix: its registry entry with the file's sizes and the mix's Gating
    Dropout recipe."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    gd = dataclasses.replace(
        base.moe.gating_dropout, mode=traffic["gating_dropout"]["mode"],
        rate=float(traffic["gating_dropout"]["rate"]),
        strategy=traffic["gating_dropout"]["strategy"])
    moe = dataclasses.replace(
        base.moe, n_experts=conf["n_experts"], top_k=conf["top_k"],
        capacity_factor=conf["capacity_factor"],
        jitter_eps=conf["jitter_eps"], balance_coef=conf["balance_coef"],
        moe_layer_period=conf["moe_layer_period"], backend=conf["backend"],
        gating_dropout=gd)
    return dataclasses.replace(
        base, d_model=conf["d_model"], n_heads=conf["n_heads"],
        n_kv_heads=conf["n_heads"], d_ff=conf["d_ff"], vocab=conf["vocab"],
        n_layers=conf["n_decoder_layers"], rope_theta=conf["rope_theta"],
        dtype=conf["dtype"], param_dtype=conf["param_dtype"],
        encdec=dataclasses.replace(
            base.encdec, n_encoder_layers=conf["n_encoder_layers"]),
        moe=moe)


def _stacks(cfg):
    from repro.models import transformer as T
    return (("encoder", "enc", T.layer_plan(cfg, encoder=True)),
            ("decoder", "dec", T.layer_plan(cfg)))


def pack(canon: Dict, cfg) -> Dict:
    """Canonical per-layer weights -> the program's stacked layout
    (segments of ``transformer.layer_plan``, a leading repeats axis)."""
    import jax
    import jax.numpy as jnp
    out = {k: canon[k] for k in ("embed", "lm_head", "final_norm",
                                 "enc_final_norm")}
    for key, side, segs in _stacks(cfg):
        layers, stack, g = canon[side], [], 0
        for seg in segs:
            npat = len(seg.pattern)
            seg_p = {}
            for pi in range(npat):
                idx = [g + r * npat + pi for r in range(seg.repeats)]
                seg_p[f"p{pi}"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *[layers[j] for j in idx])
            stack.append(seg_p)
            g += seg.repeats * npat
        out[key] = stack
    return out


def unpack(prog: Dict, cfg) -> Dict:
    """The program's stacked layout -> canonical per-layer tree."""
    import jax
    out = {k: prog[k] for k in ("embed", "lm_head", "final_norm",
                                "enc_final_norm")}
    for key, side, segs in _stacks(cfg):
        layers = []
        for seg, seg_p in zip(segs, prog[key]):
            for r in range(seg.repeats):
                for pi in range(len(seg.pattern)):
                    layers.append(jax.tree.map(lambda a: a[r],
                                               seg_p[f"p{pi}"]))
        out[side] = layers
    return out


def device_info(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileCounter:
    """Counts JAX compile events (tracing, lowering, backend compile,
    persistent-cache loads) while ``armed``."""

    def __init__(self):
        import jax.monitoring as mon
        self.armed, self.events = False, []
        mon.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)

    def _on(self, name: str, secs: float, **kw) -> None:
        if self.armed and ("/jax/core/compile" in name
                           or "compilation_cache" in name):
            self.events.append(name)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _gaps(prog: List[float], ref: List[float], skip: List[bool]) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference leaf's norm and the median reference leaf's."""
    med = statistics.median(r for r, s in zip(ref, skip) if not s)
    return max(abs(p - r) / max(r, med)
               for p, r, s in zip(prog, ref, skip) if not s)


def compare(prog: Dict, ref: Dict, limits: Dict) -> List[Dict]:
    """The numbers compared, each with its limit."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    med = statistics.median(ref["grad_norm"])
    # leaves the reference leaves unmoved to rounding move under Adam by
    # round-off alone: their change is not compared
    still = [g < 1e-3 * med for g in ref["grad_norm"]]
    none = [False] * len(still)
    return [
        {"name": "loss_gap", "value": loss, "limit": limits["loss_gap"]},
        {"name": "grad_norm_gap",
         "value": _gaps(prog["grad_norm"], ref["grad_norm"], none),
         "limit": limits["grad_norm_gap"]},
        {"name": "update_norm_gap",
         "value": _gaps(prog["change_norm"], ref["change_norm"], still),
         "limit": limits["update_norm_gap"]},
    ]


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, out_dir: str, require_tpu: bool = True,
        fault: Optional[Callable] = None,
        variants: Sequence[Tuple[str, str, str]] = ()) -> Dict:
    """One run of a training cell. Returns the result line's object, or
    raises SystemExit(3) where the chips the cell needs are missing."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    dev = device_info(devs)
    log(f"device: {json.dumps(dev)}")
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s), "
              f"JAX found {dev['count']} {dev['platform']} device(s)",
              file=sys.stderr)
        raise SystemExit(3)
    devs = devs[:cell.chips]
    from repro.configs.base import TrainConfig
    from repro.core.moe import ParallelContext
    from repro.launch.env import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import init_model
    from repro.obs.trace import Tracer
    from repro.training import Trainer
    from repro.training.loop import train_state_sharding

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    counter = CompileCounter()

    conf, tr = cell.conf, cell.traffic
    spec = ModelSpec.from_config(conf)
    cfg = program_config(conf, tr)
    opt = tr["optimizer"]
    gd = tr["gating_dropout"]
    cseed = int(gd["consensus_seed"])
    tc = TrainConfig(lr=opt["lr"], warmup_steps=opt["warmup_steps"],
                     schedule=opt["schedule"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], grad_clip=opt["grad_clip"], seed=cseed,
                     steps=1)
    n_data = int(tr["mesh"]["data"])
    ctx = (ParallelContext(mesh=make_mesh((n_data,), ("data",)))
           if n_data > 1 else None)
    traffic = MTTraffic(tr, conf["vocab"], seed)
    step_tokens: Dict[int, int] = {}

    def batch_fn(step: int):
        b = traffic.batch_at(step)
        step_tokens[step] = real_tokens(b)
        return b

    # weights: one jitted call on the device, in the program's layout
    wkey = seed_key(seed, WEIGHTS_STREAM)
    shard = train_state_sharding(cfg, tc, ctx)
    make = jax.jit(lambda k: pack(make_canonical(spec, k), cfg),
                   out_shardings=None if shard is None else shard["params"])
    params = make(wkey)
    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    if (jax.tree.structure(params) != jax.tree.structure(want)
            or any(a.shape != b.shape or a.dtype != b.dtype for a, b in
                   zip(jax.tree.leaves(params), jax.tree.leaves(want)))):
        raise RuntimeError("benchmark weights do not match the program's "
                           "parameter tree")
    # the program's spans name the traced window's idle gaps; they stay
    # off until that window
    tracer = Tracer(enabled=False)
    trainer = Trainer(cfg, tc, batch_fn, ctx=ctx, params=params,
                      chunk=int(tr["chunk"]), strategy=gd["strategy"],
                      log=None, log_every=1, tracer=tracer)
    del params
    if fault is not None:
        fault(trainer)

    def drive(start: int, stop: int, log_every: int) -> None:
        trainer.start_step = start
        trainer.tc = dataclasses.replace(tc, steps=stop)
        trainer.log_every = log_every
        trainer.run()

    decisions = lambda a, b: [R.decision(cseed, i, gd["rate"])  # noqa: E731
                              for i in range(a, b)]
    n_check, n_warm = int(tr["check_steps"]), len(traffic.warmup)
    check_dec = decisions(0, n_check)
    warm_dec = decisions(0, n_warm)
    both = {False, True} if gd["rate"] > 0 else {False}
    # step 0 meets the state as the Trainer built it, and the later steps
    # the state as the step program returns it, which a mesh may lay out
    # otherwise: the steps after the first meet every executable
    pairs = {(traffic.bucket(i), d) for i, d in enumerate(warm_dec) if i}
    if (pairs != {(b, d) for b in traffic.buckets for d in both}
            or set(check_dec) != both
            or {traffic.bucket(i) for i in range(n_check)}
            != set(traffic.buckets)):
        raise RuntimeError(
            f"consensus seed {cseed} and warmup_buckets {traffic.warmup} "
            f"draw {warm_dec}: the checked steps must meet every bucket and "
            f"both decisions, and the warm-up's steps after the first every "
            f"(bucket, decision)")
    unp = lambda t: unpack(t, cfg)  # noqa: E731
    norms = jax.jit(lambda t: R.leaf_norms(unp(t)))
    drive(0, 1, 1)
    g1 = jax.device_get(norms(trainer.state["opt"]["m"]))
    g1 = [float(x) / (1.0 - opt["b1"]) for x in g1]
    drive(1, n_check, 1)
    change = jax.jit(lambda p, k: R.leaf_norms(jax.tree.map(
        jnp.subtract, unp(p), make_canonical(spec, k))))
    ch = [float(x) for x in jax.device_get(change(trainer.state["params"],
                                                  wkey))]
    prog = {"loss": [r["loss"] for r in trainer.history[:n_check]],
            "grad_norm": g1, "change_norm": ch}
    log(f"checked steps: buckets={[traffic.bucket(i) for i in range(n_check)]}"
        f" decisions={check_dec} losses={prog['loss']}")
    drive(n_check, n_warm, 1)
    first = traffic.batch_at(0)
    log(f"padded_fraction step0={padded_fraction(first):.6f} "
        f"real_tokens step0={real_tokens(first)}")

    # ---- the measured window, in whole cycles of the traffic -------------
    block = traffic.cycle_steps
    jax.block_until_ready(trainer.state)
    counter.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    s = n_warm
    while True:
        drive(s, s + block, 0)
        s += block
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(trainer.state)
    t1 = time.perf_counter()
    counter.armed = False
    window_steps = (n_warm, s)
    tokens = sum(step_tokens[i] for i in range(*window_steps))
    positions = sum(traffic.bucket(i) * len(traffic.lengths(i)) * 2
                    for i in range(*window_steps))
    tok_s = tokens / (t1 - t0)
    win_dec = decisions(*window_steps)
    log(f"window: steps={s - n_warm} dropped={sum(win_dec)} "
        f"seconds={t1 - t0:.6f} tokens={tokens} "
        f"padded_fraction={1 - tokens / positions:.6f} "
        f"compiles={len(counter.events)} {counter.events[:4]}")

    traced = None
    if trace:
        import trace_reduce as TR
        n_tr = traffic.cycle_steps
        logdir = tempfile.mkdtemp(prefix="trace", dir=out_dir)
        tracer.enabled = True
        counter.armed = True
        with jax.profiler.trace(logdir):
            with jax.profiler.TraceAnnotation("bench.window"):
                h0 = time.perf_counter()
                drive(s, s + n_tr, 0)
                jax.block_until_ready(trainer.state)
        counter.armed = False
        # the driving thread's spans: what the host did between steps
        me = threading.get_ident()
        spans = [(e[1], e[2], e[2] + e[3]) for e in tracer.events
                 if e[0] == "X" and e[4] == me]
        inputs = {"window": "bench.window", "host_t0": h0, "spans": spans,
                  "module_substr": "chunk_fn"}
        with open(os.path.join(logdir, "reduce_inputs.json"), "w") as f:
            json.dump(inputs, f)
        traced = TR.reduce(TR.load(TR.find_xplane(logdir)), **inputs,
                           expect_steps=n_tr)
        shutil.rmtree(logdir)
        traced["decisions"] = decisions(s, s + n_tr)
        s += n_tr

    peak = memory_peak_bytes(devs)
    # free the program's state (``drive`` still holds the Trainer)
    trainer.state = None
    del trainer, drive
    gc.collect()
    log(f"live bytes before the reference: "
        f"{sum(a.nbytes for a in jax.live_arrays())}")

    # ---- the reference, after the program's state is freed ---------------
    r0 = time.perf_counter()
    ref_shard = batch_shard = None
    if ctx is not None:
        ref_shard, batch_shard = reference_shardings(spec, ctx)
    ref_batches = [{k: jax.device_put(v, batch_shard) if batch_shard else
                    jnp.asarray(v) for k, v in traffic.batch_at(i).items()}
                   for i in range(n_check)]
    params0 = jax.jit(lambda: make_canonical(spec, wkey),
                      out_shardings=ref_shard)

    def reference(precision: str, fault: str = "") -> Dict:
        return R.run_reference(spec, opt, params0, ref_batches, check_dec,
                               cseed, groups=n_data, precision=precision,
                               fault=fault, shardings=ref_shard,
                               batch_sharding=batch_shard)

    ref = reference("f32")
    log(f"reference: {time.perf_counter() - r0:.3f} s losses={ref['loss']}")
    checks = compare(prog, ref, tr["limits"])
    # the control and planted faults, read against the same reference
    # (bench/calibrate.py; never in the benchmark's own runs)
    variant_checks = {}
    for label, precision, fault in variants:
        v = reference(precision, fault)
        variant_checks[label] = {c["name"]: c["value"]
                                 for c in compare(v, ref, tr["limits"])}
        log(f"variant {label}: {json.dumps(variant_checks[label])}")
    counter.close()
    checks.append({"name": "compiles_in_window",
                   "value": len(counter.events), "limit": 0})
    # a limit not yet set from readings on the chip (null) passes nothing
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks)

    chips = cell.chips
    result: Dict[str, Any] = {
        # the checked steps are the ones a failed comparison finds wrong
        "correct": correct, "attempted": s,
        "failed": 0 if correct else n_check,
        "device": {**dev, "count": chips, "memory_peak_bytes": peak}}
    values = {"train_tokens_per_s": tok_s, "peak_hbm_gb": peak / 1e9,
              "setup_s": setup_s}
    if not trace:
        result["metrics"] = harness.end_to_end(cell, values)
    else:
        import metrics as M
        ctx_m = {"cell": cell, "spec": spec, "traffic": tr, "chips": chips,
                 "device_kind": dev["kind"], "train_tokens_per_s": tok_s,
                 "trace": traced}
        result["metrics"] = M.read_all(cell, ctx_m)
        result["device"]["busy_s"] = traced["busy_s"]
        result["device"]["window_s"] = traced["window_s"]
        result["breakdown"] = traced["breakdown"]
    if variants:
        result["variants"] = variant_checks
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def reference_shardings(spec: ModelSpec, ctx) -> Tuple[Any, Any]:
    """(parameter, batch) shardings of the reference on a mesh: expert
    weights split by expert over the data axis, as the chips hold them;
    every other matrix split along its largest axis that the chips
    divide, so that the float32 parameters, gradients and both moments
    fit; batch rows split over the chips."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = ctx.mesh.shape["data"]
    tree = layout(spec)
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_leaf)
    out = []
    for name, (shape, _) in zip(leaf_names(tree), leaves):
        axes = [None] * len(shape)
        if ".experts." in name:
            axes[0] = "data"
        elif len(shape) >= 2:
            fits = [i for i, x in enumerate(shape) if x % n == 0]
            if fits:
                axes[max(fits, key=lambda i: shape[i])] = "data"
        out.append(NamedSharding(ctx.mesh, P(*axes)))
    return (jax.tree_util.tree_unflatten(treedef, out),
            NamedSharding(ctx.mesh, P("data")))
