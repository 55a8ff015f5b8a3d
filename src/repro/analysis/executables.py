"""Named-executable registry for the lint suite (DESIGN.md §12).

Mirrors the backend registry (core/backend.py): every program the
system ships — the fused train chunk (routed + host_cond dropped), the
sharded MoE layer (routed + Gate-Drop local), decode_pool_step with and
without ``local_routing``, the pallas_fused forward/VJP, the unfused
pallas pipeline, the flash-decode step — registers here as an
ExecutableSpec that can lower itself under the small CPU device mesh,
plus the per-pass EXPECTATIONS the lint passes check it against
(zero a2a vs. cost-model equality, launch budgets, VMEM budgets,
dtype policy, host-sync scenarios).

Builders are lazy: importing this module costs nothing but host math
(the cost-model expectations); devices are touched only when an
executable's artifacts are first requested. Specs needing the mesh
declare ``n_devices=8`` and are skipped (with a warning finding) when
fewer devices are visible.

Suppressions: pass ``ignore=(...)`` or write a trailing
``# lint: ignore[pass-id]`` comment on the ``register_executable``
call line — the registrar reads it from source.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import re
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["Artifacts", "ExecutableSpec", "available_executables",
           "get_executable", "register_executable"]

_IGNORE_COMMENT = re.compile(r"#\s*lint:\s*ignore\[([\w\-,\s]+)\]")


@dataclasses.dataclass(frozen=True)
class ExecutableSpec:
    name: str
    build: Callable[[], Tuple[Callable, tuple]]   # -> (fn, example_args)
    expect: Dict[str, Dict[str, Any]]
    ignore: Tuple[str, ...] = ()
    scenario: Optional[Callable[[], Dict[str, Any]]] = None
    n_devices: int = 1                            # devices the build needs


class Artifacts:
    """Lazy per-executable artifacts: the jaxpr (pre-lowering truth for
    dtypes/launches) and the parsed compiled-HLO module (truth for
    collectives). Each is built once and cached."""

    def __init__(self, spec: ExecutableSpec):
        self._spec = spec
        self._built: Optional[Tuple[Callable, tuple]] = None

    def _fn_args(self):
        if self._built is None:
            self._built = self._spec.build()
        return self._built

    @functools.cached_property
    def jaxpr(self):
        import jax
        fn, args = self._fn_args()
        return jax.make_jaxpr(fn)(*args)

    @functools.cached_property
    def hlo(self):
        import jax
        from repro.analysis.hlo import parse_hlo
        fn, args = self._fn_args()
        text = jax.jit(fn).lower(*args).compile().as_text()
        return parse_hlo(text)


_REGISTRY: Dict[str, ExecutableSpec] = {}


def register_executable(spec: ExecutableSpec) -> ExecutableSpec:
    """Register a spec; merges ``# lint: ignore[pass-id, ...]`` comments
    written anywhere on the (possibly multi-line) registration call into
    ``spec.ignore`` — scans the caller's source from the call line until
    its parentheses close."""
    frame = inspect.stack()[1]
    extra = []
    try:
        lines, _ = inspect.findsource(frame.frame)
        depth = 0
        for ln in lines[frame.lineno - 1:frame.lineno + 31]:
            m = _IGNORE_COMMENT.search(ln)
            if m:
                extra += [p.strip() for p in m.group(1).split(",")
                          if p.strip()]
            depth += ln.count("(") - ln.count(")")
            if depth <= 0:
                break
    except (OSError, TypeError):          # exec'd / REPL code: no source
        pass
    if extra:
        spec = dataclasses.replace(spec, ignore=spec.ignore + tuple(extra))
    _REGISTRY[spec.name] = spec
    return spec


def available_executables() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_executable(name: str) -> ExecutableSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown executable {name!r}; available: "
                       f"{', '.join(available_executables())}") from None


# --------------------------------------------------------------------------
# shared config builders (host math only)
# --------------------------------------------------------------------------

def _moe_cfg(*, backend: str = "sharded", dtype: str = "float32",
             top_k: int = 2, gated: bool = True, d_model: int = 32,
             d_ff: int = 64, n_experts: int = 8):
    from repro.configs.base import (GatingDropoutConfig, ModelConfig,
                                    MoEConfig)
    return ModelConfig(
        d_model=d_model, d_ff=d_ff, vocab=64, dtype=dtype,
        gated_mlp=gated,
        moe=MoEConfig(n_experts=n_experts, top_k=top_k, d_ff_expert=d_ff,
                      jitter_eps=0.0, backend=backend,
                      gating_dropout=GatingDropoutConfig(
                          mode="gate_drop", rate=0.3)))


def _train_cfg():
    from repro.configs.base import (GatingDropoutConfig, ModelConfig,
                                    MoEConfig)
    # scan_layers=False: HLO counts a scanned segment body ONCE; the cost
    # model prices per MoE layer — unrolled, the two agree exactly.
    return ModelConfig(
        d_model=32, d_ff=64, vocab=64, n_layers=2, n_heads=2, n_kv_heads=2,
        remat=False, scan_layers=False, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=64, jitter_eps=0.0,
                      backend="sharded",
                      gating_dropout=GatingDropoutConfig(
                          mode="gate_drop", rate=0.3,
                          strategy="host_cond")))


def _decode_cfg():
    from repro.configs.base import (GatingDropoutConfig, ModelConfig,
                                    MoEConfig)
    return ModelConfig(
        d_model=64, d_ff=128, vocab=100, n_layers=1, n_heads=2,
        n_kv_heads=2, remat=False, scan_layers=False, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=128,
                      backend="sharded",
                      gating_dropout=GatingDropoutConfig(
                          mode="gate_drop", rate=0.3)))


def _layer_cost_expect(cfg, *, tokens_per_shard: int, ep: int):
    from repro.comm import layer_cost
    c = layer_cost(cfg, tokens_per_shard=tokens_per_shard, ep=ep)
    return {"cost": {"calls": c["calls"], "bytes": c["bytes"],
                     "wire_bytes": c["wire_bytes"]}}


def _step_cost_expect(cfg, *, tokens_per_shard: int, ep: int):
    from repro.comm import step_cost
    c = step_cost(cfg, tokens_per_shard=tokens_per_shard, ep=ep,
                  backward=True)
    return {"cost": {"calls": c["calls"], "bytes": c["bytes"],
                     "wire_bytes": c["wire_bytes"]}}


# --------------------------------------------------------------------------
# builders (device-touching, lazy)
# --------------------------------------------------------------------------

def _build_moe_layer(decision: bool):
    def build():
        import jax
        from repro.core import init_moe_params, moe_sharded, ParallelContext
        from repro.launch.mesh import make_mesh
        cfg = _moe_cfg()
        ctx = ParallelContext(mesh=make_mesh((8,), ("data",)))
        p = init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))

        def fn(p_, x_):
            return moe_sharded(p_, x_, cfg, ctx, rng=None,
                               decision=decision)
        return fn, (p, x)
    return build


def _build_train_chunk(decision: bool, frame: bool = True):
    def build():
        import jax
        import jax.numpy as jnp
        from repro.configs.base import TrainConfig
        from repro.core.moe import ParallelContext
        from repro.launch.mesh import make_mesh
        from repro.models import init_model
        from repro.training.loop import make_chunk_step
        from repro.training.steps import init_train_state
        cfg = _train_cfg()
        tc = TrainConfig(lr=1e-3, warmup_steps=4, seed=0,
                         metrics_frame=frame)
        ctx = ParallelContext(mesh=make_mesh((8,), ("data",)))
        state = init_train_state(init_model(jax.random.PRNGKey(0), cfg), tc)
        K, B, L = 2, 8, 16
        key = jax.random.PRNGKey(1)
        toks = jax.random.randint(key, (K, B, L), 3, cfg.vocab)
        batches = {"tokens": toks,
                   "labels": jnp.roll(toks, -1, axis=2),
                   "loss_mask": jnp.ones((K, B, L), jnp.float32)}
        chunk = make_chunk_step(cfg, tc, ctx, jit=False)

        def fn(state_, batches_):
            return chunk(state_, batches_, decision)
        return fn, (state, batches)
    return build


def _build_decode_pool(local_routing: bool):
    def build():
        import jax
        import jax.numpy as jnp
        from repro.core.moe import ParallelContext
        from repro.launch.mesh import make_mesh
        from repro.models import init_model
        from repro.serve.engine import decode_pool_step, init_slot_pool
        cfg = _decode_cfg()
        ctx = ParallelContext(mesh=make_mesh((8,), ("data",)))
        params = init_model(jax.random.PRNGKey(0), cfg)
        S = 8
        pool = init_slot_pool(cfg, S, 32)
        tok = jnp.zeros((S,), jnp.int32)
        pos = jnp.full((S,), 4, jnp.int32)
        alive = jnp.ones((S,), bool)

        def fn(p_, c_, t_, i_, a_):
            return decode_pool_step(p_, c_, t_, i_, a_, cfg, ctx,
                                    local_routing=local_routing)
        return fn, (params, pool, tok, pos, alive)
    return build


def _build_decode_paged(local_routing: bool):
    def build():
        import jax
        import jax.numpy as jnp
        from repro.core.moe import ParallelContext
        from repro.launch.mesh import make_mesh
        from repro.models import init_model
        from repro.serve.paged import (PagedLayout, decode_paged_step,
                                       paged_pool_like)
        cfg = _decode_cfg()
        ctx = ParallelContext(mesh=make_mesh((8,), ("data",)))
        params = init_model(jax.random.PRNGKey(0), cfg)
        S, max_seq = 8, 32
        layout = PagedLayout(page_size=8, n_pages=24, seq_len=max_seq)
        batch = {"tokens": jnp.zeros((S, 4), jnp.int32)}
        pool = paged_pool_like(params, batch, cfg, ctx, max_seq=max_seq,
                               n_slots=S, layout=layout)
        tables = jnp.tile(jnp.arange(layout.n_blocks, dtype=jnp.int32),
                          (S, 1))
        tok = jnp.zeros((S,), jnp.int32)
        pos = jnp.full((S,), 4, jnp.int32)
        alive = jnp.ones((S,), bool)

        def fn(p_, c_, bt_, t_, i_, a_):
            return decode_paged_step(p_, c_, bt_, t_, i_, a_, cfg, ctx,
                                     local_routing=local_routing)
        return fn, (params, pool, tables, tok, pos, alive)
    return build


def _build_pallas_fused(mode: str):
    def build():
        import jax
        import jax.numpy as jnp
        from repro.core import init_moe_params
        from repro.core.backend import get_backend
        cfg = _moe_cfg(backend="pallas_fused")
        p = init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        backend = get_backend("pallas_fused")

        def fwd(p_, x_):
            y, _aux = backend(p_, x_, cfg, None, rng=None, decision=False,
                              is_training=True, interpret=True)
            return y

        if mode == "fwd":
            return fwd, (p, x)

        def vjp(p_, x_):
            return jax.grad(lambda pp, xx: jnp.sum(fwd(pp, xx) ** 2),
                            argnums=(0, 1))(p_, x_)
        return vjp, (p, x)
    return build


def _build_pallas_pipeline():
    def build():
        import jax
        from repro.core import init_moe_params
        from repro.core.backend import get_backend
        # ungated expert MLP: dispatch + 2 grouped matmuls + combine = 4
        # launches (the gate matmul would make it 5)
        cfg = _moe_cfg(backend="pallas", gated=False)
        p = init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        backend = get_backend("pallas")

        def fn(p_, x_):
            y, _aux = backend(p_, x_, cfg, None, rng=None, decision=False,
                              is_training=True, interpret=True)
            return y
        return fn, (p, x)
    return build


def _build_flash_decode():
    def build():
        import jax
        import jax.numpy as jnp
        from repro.kernels.flash_decode import flash_decode
        key = jax.random.PRNGKey(0)
        B, H, KV, hd, S = 8, 4, 2, 16, 64
        q = jax.random.normal(key, (B, H, hd))
        k = jax.random.normal(key, (B, S, KV, hd))
        v = jax.random.normal(key, (B, S, KV, hd))
        idx = jnp.full((B,), 17, jnp.int32)

        def fn(q_, k_, v_, i_):
            return flash_decode(q_, k_, v_, i_, interpret=True)
        return fn, (q, k, v, idx)
    return build


def _build_flash_decode_paged():
    def build():
        import jax
        import jax.numpy as jnp
        from repro.kernels.flash_decode import flash_decode_paged
        key = jax.random.PRNGKey(0)
        B, H, KV, hd, ps, npg, nb = 8, 4, 2, 16, 16, 24, 4
        q = jax.random.normal(key, (B, H, hd))
        k = jax.random.normal(key, (npg + 1, ps, KV, hd))
        v = jax.random.normal(key, (npg + 1, ps, KV, hd))
        bt = jnp.tile(jnp.arange(nb, dtype=jnp.int32), (B, 1))
        idx = jnp.full((B,), 17, jnp.int32)

        def fn(q_, k_, v_, bt_, i_):
            return flash_decode_paged(q_, k_, v_, bt_, i_, interpret=True)
        return fn, (q, k, v, bt, idx)
    return build


def _build_bf16_loss():
    def build():
        import jax
        import jax.numpy as jnp
        import dataclasses as dc
        from repro.models import init_model
        from repro.training.steps import total_loss
        cfg = dc.replace(_moe_cfg(backend="oracle"), dtype="bfloat16",
                         param_dtype="bfloat16", n_layers=2, n_heads=2,
                         n_kv_heads=2, remat=False)
        params = init_model(jax.random.PRNGKey(0), cfg)
        key = jax.random.PRNGKey(1)
        toks = jax.random.randint(key, (2, 16), 3, cfg.vocab)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1),
                 "loss_mask": jnp.ones((2, 16), jnp.float32)}

        def fn(p_, b_):
            return total_loss(p_, b_, cfg, None, rng=None, decision=False)
        return fn, (params, batch)
    return build


# --------------------------------------------------------------------------
# host-sync scenarios (execute steady-state ticks under the guard)
# --------------------------------------------------------------------------

def _trainer_scenario():
    import jax
    from repro.analysis.hostsync import guard_host_transfers, jit_cache_sizes
    from repro.configs.base import TrainConfig
    from repro.data import LMTaskConfig, SyntheticLM, stack_batches
    from repro.obs.trace import Tracer
    from repro.training.loop import Trainer
    import dataclasses as dc
    cfg = dc.replace(_moe_cfg(backend="oracle"), n_layers=1, n_heads=2,
                     n_kv_heads=2, remat=False)
    # metrics_frame stays ON and the tracer is ENABLED: the guard must
    # stay green with the full observability layer live (DESIGN.md §15)
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=0, steps=8)
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    trainer = Trainer(cfg, tc, lambda i: task.sample_batch(i, 2),
                      chunk=2, strategy="traced_cond", prefetch=False,
                      log=None, tracer=Tracer(enabled=True))
    def enqueue(lo, hi, in_flight):
        return trainer._dispatch([(lo, hi, None)],
                                 stack_batches(trainer.batch_fn, lo, hi),
                                 in_flight)

    trainer._fetch(0, 2, enqueue(0, 2, 0))       # warmup: compile outside
    evs = []
    with guard_host_transfers(events=evs):
        before = jit_cache_sizes([trainer.chunk_fn])
        # run()'s steady state: chunk i+1 enqueued before i is fetched
        first = enqueue(2, 4, 0)
        second = enqueue(4, 6, 1)
        trainer._fetch(2, 4, first)
        third = enqueue(6, 8, 1)
        trainer._fetch(4, 6, second)
        trainer._fetch(6, 8, third)
        after = jit_cache_sizes([trainer.chunk_fn])
    return {"events": evs,
            "cache_sizes": [("chunk_fn", before[0], after[0])]}


def _scheduler_scenario():
    import numpy as np
    from repro.analysis.hostsync import guard_host_transfers, jit_cache_sizes
    from repro.serve.engine import GenerateConfig
    from repro.serve.scheduler import ContinuousScheduler, Request
    from repro.models import init_model
    import jax
    import dataclasses as dc
    cfg = dc.replace(_moe_cfg(backend="oracle"), n_layers=1, n_heads=2,
                     n_kv_heads=2, remat=False)
    params = init_model(jax.random.PRNGKey(0), cfg)
    gen = GenerateConfig(max_new=24, eos_id=-1)
    from repro.obs import MetricsRegistry, Tracer
    # tracer + registry live: span records and histogram observes are
    # pure host work, so the guarded ticks must stay one-sync
    sched = ContinuousScheduler(params, cfg, gen, n_slots=4,
                                prefill_buckets=(8,),
                                registry=MetricsRegistry(),
                                tracer=Tracer(enabled=True))
    for rid in range(3):
        sched.submit(Request(rid=rid,
                             tokens=np.arange(3 + rid, dtype=np.int32) + 3))
    sched.step(0.0)                              # warmup: prefill + decode
    sched.step(0.0)                              # warmup: steady decode
    jits = [sched._decode_fn, sched._prefill]
    evs = []
    with guard_host_transfers(events=evs):
        before = jit_cache_sizes(jits)
        for _ in range(3):                       # steady-state ticks
            sched.step(0.0)
        after = jit_cache_sizes(jits)
    return {"events": evs,
            "cache_sizes": [("pool_decode", before[0], after[0]),
                            ("bucket_prefill", before[1], after[1])]}


def _paged_scheduler_scenario():
    import numpy as np
    from repro.analysis.hostsync import guard_host_transfers, jit_cache_sizes
    from repro.configs.base import PagedKVConfig
    from repro.serve.engine import GenerateConfig
    from repro.serve.scheduler import PagedScheduler, Request
    from repro.models import init_model
    import jax
    import dataclasses as dc
    cfg = dc.replace(_moe_cfg(backend="oracle"), n_layers=1, n_heads=2,
                     n_kv_heads=2, remat=False)
    params = init_model(jax.random.PRNGKey(0), cfg)
    gen = GenerateConfig(max_new=24, eos_id=-1)
    from repro.obs import MetricsRegistry, Tracer
    # ample pages: the steady-state tick must stay on the one-sync path
    # (preemption swap-out is the documented exceptional second sync);
    # tracer + registry live, same as the base-scheduler scenario
    sched = PagedScheduler(params, cfg, gen, n_slots=4,
                           prefill_buckets=(8,),
                           paged=PagedKVConfig(page_size=8,
                                               n_slots_equiv=8),
                           registry=MetricsRegistry(),
                           tracer=Tracer(enabled=True))
    for rid in range(3):
        sched.submit(Request(rid=rid,
                             tokens=np.arange(3 + rid, dtype=np.int32) + 3))
    sched.step(0.0)                              # warmup: prefill + decode
    sched.step(0.0)                              # warmup: steady decode
    jits = [sched._decode_fn, sched._prefill]
    evs = []
    with guard_host_transfers(events=evs):
        before = jit_cache_sizes(jits)
        for _ in range(3):                       # steady-state ticks
            sched.step(0.0)
        after = jit_cache_sizes(jits)
    return {"events": evs,
            "cache_sizes": [("paged_decode", before[0], after[0]),
                            ("paged_prefill", before[1], after[1])]}


# --------------------------------------------------------------------------
# the registry
# --------------------------------------------------------------------------

_VMEM = {"budget_bytes": 16 << 20}
_DTYPE = {"min_elems": 4096}

register_executable(ExecutableSpec(
    name="moe_layer/routed",
    build=_build_moe_layer(decision=False),
    expect={"no-collectives": _layer_cost_expect(
        _moe_cfg(), tokens_per_shard=16, ep=8)},
    n_devices=8))

register_executable(ExecutableSpec(
    name="moe_layer/local",
    build=_build_moe_layer(decision=True),
    expect={"no-collectives": {"zero": True}},
    n_devices=8))

register_executable(ExecutableSpec(
    name="train_chunk/routed",
    build=_build_train_chunk(decision=False),
    expect={"no-collectives": _step_cost_expect(
        _train_cfg(), tokens_per_shard=16, ep=8)},
    n_devices=8))

register_executable(ExecutableSpec(
    name="train_chunk/dropped",
    build=_build_train_chunk(decision=True),
    expect={"no-collectives": {"zero": True}},
    n_devices=8))

# MetricsFrame non-interference (DESIGN.md §15): switching the in-graph
# telemetry frame OFF must leave the compiled chunk's collectives exactly
# at the cost model — the frame only widens the fetched metric dict, it
# never adds (or removes) communication
register_executable(ExecutableSpec(
    name="train_chunk/frame_off",
    build=_build_train_chunk(decision=False, frame=False),
    expect={"no-collectives": _step_cost_expect(
        _train_cfg(), tokens_per_shard=16, ep=8)},
    n_devices=8))

register_executable(ExecutableSpec(
    name="decode_pool/routed",
    build=_build_decode_pool(local_routing=False),
    expect={"no-collectives": {"nonzero": True}},
    n_devices=8))

register_executable(ExecutableSpec(
    name="decode_pool/local",
    build=_build_decode_pool(local_routing=True),
    expect={"no-collectives": {"zero": True}},
    n_devices=8))

register_executable(ExecutableSpec(
    name="decode_paged/routed",
    build=_build_decode_paged(local_routing=False),
    expect={"no-collectives": {"nonzero": True}},
    n_devices=8))

register_executable(ExecutableSpec(
    name="decode_paged/local",
    build=_build_decode_paged(local_routing=True),
    expect={"no-collectives": {"zero": True}},
    n_devices=8))

register_executable(ExecutableSpec(
    name="pallas_fused/fwd",
    build=_build_pallas_fused("fwd"),
    expect={"launch-count": {"max": 1}, "vmem-budget": _VMEM,
            "dtype-flow": _DTYPE, "no-collectives": {"zero": True}}))

register_executable(ExecutableSpec(
    name="pallas_fused/vjp",
    build=_build_pallas_fused("vjp"),
    expect={"launch-count": {"max": 1}, "vmem-budget": _VMEM}))

register_executable(ExecutableSpec(
    name="pallas_pipeline/fwd",
    build=_build_pallas_pipeline(),
    expect={"launch-count": {"max": 4}, "vmem-budget": _VMEM,
            "no-collectives": {"zero": True}}))

register_executable(ExecutableSpec(
    name="flash_decode/step",
    build=_build_flash_decode(),
    expect={"launch-count": {"max": 1}, "vmem-budget": _VMEM,
            "dtype-flow": _DTYPE}))

register_executable(ExecutableSpec(
    name="flash_decode/paged",
    build=_build_flash_decode_paged(),
    expect={"launch-count": {"max": 1}, "vmem-budget": _VMEM,
            "dtype-flow": _DTYPE}))

register_executable(ExecutableSpec(
    name="model_loss/bf16",
    build=_build_bf16_loss(),
    expect={"dtype-flow": _DTYPE, "no-collectives": {"zero": True}}))

register_executable(ExecutableSpec(
    name="trainer/ticks",
    build=lambda: (_ for _ in ()).throw(
        RuntimeError("trainer/ticks is scenario-only")),
    expect={"host-sync": {}},
    scenario=_trainer_scenario))

register_executable(ExecutableSpec(
    name="scheduler/ticks",
    build=lambda: (_ for _ in ()).throw(
        RuntimeError("scheduler/ticks is scenario-only")),
    expect={"host-sync": {}},
    scenario=_scheduler_scenario))

register_executable(ExecutableSpec(
    name="paged_scheduler/ticks",
    build=lambda: (_ for _ in ()).throw(
        RuntimeError("paged_scheduler/ticks is scenario-only")),
    expect={"host-sync": {}},
    scenario=_paged_scheduler_scenario))
