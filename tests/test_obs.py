"""Observability layer tests (repro.obs, DESIGN.md §15).

Anchors: the exported trace is valid Chrome trace-event JSON with correct
span nesting and per-thread tracks; a DISABLED tracer records nothing and
allocates nothing per call; the in-graph MetricsFrame changes not one bit
of the train-state stream when toggled (telemetry only); registry
percentiles match np.percentile exactly and never raise on empty data;
the schedulers' tick_log/alive_log stay exact live views over the
registry. This module runs under the conftest host-transfer guard, so
every instrumented path exercised here is also proven free of hidden
device->host syncs.
"""
import dataclasses
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import (GatingDropoutConfig, ModelConfig, MoEConfig,
                                TrainConfig, reduced)
from repro.data import LMTaskConfig, SyntheticLM
from repro.data.pipeline import MTTaskConfig, MultilingualMT
from repro.data.prefetch import stack_batches
from repro.models import init_model
from repro.obs import (FRAME_KEYS, MetricsFrame, MetricsRegistry, Tracer,
                       load_imbalance, monotonic, router_health)
from repro.serve import ContinuousScheduler, GenerateConfig, Request
from repro.training import Trainer, init_train_state, make_train_step
from repro.training.loop import make_chunk_step

KEY = jax.random.PRNGKey(0)


def _cfg(moe=True, rate=0.5):
    kw = {}
    if moe:
        kw["moe"] = MoEConfig(n_experts=4, top_k=1, d_ff_expert=64,
                              jitter_eps=0.0,
                              gating_dropout=GatingDropoutConfig(
                                  mode="gate_drop", rate=rate))
    return ModelConfig(d_model=32, d_ff=64, vocab=64, n_layers=2, n_heads=2,
                       n_kv_heads=2, remat=False, dtype="float32",
                       param_dtype="float32", **kw)


# ---------------------------------------------------------------------------
# tracer: spans, nesting, export schema
# ---------------------------------------------------------------------------

def test_span_nesting_and_export_schema(tmp_path):
    """Nested spans + instants + a worker-thread event export to valid
    Chrome trace-event JSON: X events with µs ts/dur, containment of the
    inner slice, 's':'t' instants, per-thread thread_name metadata."""
    tr = Tracer(enabled=True)
    with tr.span("outer", step=3):
        with tr.span("inner", kind="fetch"):
            tr.instant("mark", hit=True)
    t = threading.Thread(target=lambda: tr.instant("from_worker"),
                         name="worker")
    t.start()
    t.join()

    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())          # round-trips from disk
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}

    meta = [e for e in evs if e["ph"] == "M"]
    assert {"repro", "MainThread", "worker"} <= {
        e["args"]["name"] for e in meta}

    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["args"] == {"step": 3}
    # µs since the tracer epoch; the inner slice nests inside the outer
    assert 0 <= outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert by_name["mark"]["s"] == "t"
    assert by_name["mark"]["args"] == {"hit": True}
    # the worker-thread instant landed on its own dense track
    assert by_name["from_worker"]["tid"] != by_name["outer"]["tid"]


def test_tracer_args_jsonable():
    """Non-primitive span args are stringified, never break export."""
    tr = Tracer(enabled=True)
    with tr.span("s", shape=(2, 3), obj=object()):
        pass
    doc = tr.export()
    args = [e for e in doc["traceEvents"] if e["name"] == "s"][0]["args"]
    assert args["shape"] == "(2, 3)"
    assert isinstance(args["obj"], str)
    json.dumps(doc)


def test_disabled_tracer_costs_nothing():
    """The disabled fast path: one shared no-op context manager (no
    per-call allocation), zero events, and 100k instrumented no-op blocks
    complete in well under a second."""
    tr = Tracer(enabled=False)
    assert tr.span("a", x=1) is tr.span("b")    # shared _NULL, no alloc
    t0 = monotonic()
    for i in range(100_000):
        with tr.span("chunk", step=i):
            pass
        tr.instant("mark")
    dt = monotonic() - t0
    assert len(tr) == 0
    evs = tr.export()["traceEvents"]            # only process metadata
    assert [e["name"] for e in evs] == ["process_name"]
    assert dt < 1.0, f"disabled tracer overhead {dt:.3f}s for 100k spans"


def test_profile_window_needs_no_host_tracer(tmp_path):
    """A device profile (train's --jax-profile) does not depend on the
    host spans being on: a disabled tracer's window still writes one, and
    with no logdir the window is the shared no-op."""
    tr = Tracer(enabled=False)
    assert tr.profile_window(None) is tr.span("a")
    with tr.profile_window(str(tmp_path)):
        jnp.ones(4).block_until_ready()
    assert list(tmp_path.rglob("*.xplane.pb"))


# ---------------------------------------------------------------------------
# MetricsFrame: bitwise non-interference + host-side math
# ---------------------------------------------------------------------------

def test_metrics_frame_bitwise_non_interference():
    """metrics_frame on vs off from identical init: the train-state
    stream and the loss/acc metrics are BITWISE identical — the switch
    only adds/removes telemetry keys."""
    cfg = _cfg()
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    states, metrics = {}, {}
    for frame in (False, True):
        tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3,
                         metrics_frame=frame)
        step = make_train_step(cfg, tc)
        s = init_train_state(init_model(jax.random.PRNGKey(tc.seed), cfg),
                             tc)
        for i in range(3):
            b = {k: jnp.asarray(v)
                 for k, v in task.sample_batch(i, 4).items()}
            s, ms = step(s, b, None)
        states[frame], metrics[frame] = s, jax.device_get(ms)
    for a, b in zip(jax.tree.leaves(states[False]),
                    jax.tree.leaves(states[True])):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    np.testing.assert_array_equal(metrics[False]["loss"],
                                  metrics[True]["loss"])
    extra = set(metrics[True]) - set(metrics[False])
    assert extra and extra <= set(FRAME_KEYS)
    assert "router_entropy" in extra and "expert_load" in extra


def test_table12_train_chunk_is_frame_bitwise():
    """benchmarks/table12_obs.py (the observability overhead gate) drives
    the Trainer's enqueue/fetch pair directly: its train chunk must run,
    and the MetricsFrame must leave the loss/acc stream bitwise as is."""
    from benchmarks.table12_obs import check_train_bitwise
    bitwise, frame_keys = check_train_bitwise()
    assert bitwise
    assert frame_keys == ["expert_load", "gate_dropped", "router_entropy"]


def test_metrics_frame_typed_view():
    """from_metrics builds only from a complete frame; imbalance and
    summary math behave on known inputs."""
    assert MetricsFrame.from_metrics({"loss": np.zeros(2)}) is None
    K, E = 4, 4
    ms = {k: np.zeros(K) for k in FRAME_KEYS}
    ms["expert_load"] = np.tile(np.asarray([1.0, 0.0, 0.0, 0.0]), (K, 1))
    ms["router_entropy"] = np.full(K, 0.7)
    ms["gate_dropped"] = np.asarray([0.0, 1.0, 0.0, 1.0])
    fr = MetricsFrame.from_metrics(ms)
    assert len(fr) == K
    np.testing.assert_allclose(fr.load_imbalance(), np.full(K, float(E)))
    s = fr.summary()
    assert s["routed_steps"] == 2 and s["gate_drop_rate"] == 0.5
    assert s["router_entropy"] == pytest.approx(0.7)
    # uniform load = perfect balance; zero load reports 0, not a NaN
    np.testing.assert_allclose(load_imbalance(np.ones(E)), 1.0)
    np.testing.assert_allclose(load_imbalance(np.zeros(E)), 0.0)


def test_router_health_over_history():
    hist = [{"loss": 1.0},                       # pre-frame record
            {"loss": 0.9, "router_entropy": 0.6, "load_imbalance": 2.0,
             "gate_dropped": 0.0},
            {"loss": 0.8, "router_entropy": 0.0, "load_imbalance": 0.0,
             "gate_dropped": 1.0}]
    rh = router_health(hist)
    assert rh["records"] == 2
    assert rh["gate_drop_rate"] == 0.5
    # routed records only: the dropped step's zeros don't dilute health
    assert rh["router_entropy"] == pytest.approx(0.6)
    assert router_health([{"loss": 1.0}])["records"] == 0


# ---------------------------------------------------------------------------
# registry: percentile math, NaN safety, export formats, live views
# ---------------------------------------------------------------------------

def test_registry_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("serve/ttft_s")
    xs = np.random.RandomState(0).lognormal(size=257)
    for x in xs:
        h.observe(x)
    ps = (50, 90, 99, 99.9, 7.5)
    got = h.percentiles(ps)
    for p in ps:
        assert got[p] == float(np.percentile(np.float64(xs), p))
    snap = h.snapshot()
    assert snap["count"] == 257
    assert snap["sum"] == pytest.approx(xs.sum())


def test_registry_empty_histogram_is_nan_safe():
    """The zero-request serve crash (ISSUE 10 satellite): percentiles on
    an empty histogram return NaN instead of raising."""
    h = MetricsRegistry().histogram("serve/ttft_s")
    pct = h.percentiles()
    assert set(pct) == {50, 90, 99}
    assert all(np.isnan(v) for v in pct.values())
    snap = h.snapshot()
    assert snap["count"] == 0 and np.isnan(snap["mean"])
    json.dumps(MetricsRegistry().to_json())     # and it still exports


def test_registry_export_formats(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve/requests", "total requests").inc(3)
    reg.gauge("serve/wall_s").set(1.5)
    h = reg.histogram("serve/ttft_s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    s = reg.series("serve/tick_log")
    s.append(240.0, label="prefill")
    s.append(5.0, label="decode")
    s.append(5.0, label="decode")

    doc = json.loads(reg.to_json(str(tmp_path / "m.json")))
    assert doc["serve/requests"] == {"type": "counter", "value": 3.0}
    assert doc["serve/tick_log"]["by_label"]["decode"] == {
        "count": 2, "sum": 10.0}

    prom = reg.to_prometheus(str(tmp_path / "m.prom"))
    assert "# HELP serve_requests total requests" in prom
    assert "# TYPE serve_requests counter" in prom
    assert "serve_requests 3.0" in prom
    assert 'serve_ttft_s{quantile="0.5"} ' in prom
    assert "serve_ttft_s_count 3" in prom
    assert 'serve_tick_log_count{label="decode"} 2' in prom
    assert (tmp_path / "m.prom").read_text() == prom


def test_registry_series_views_are_live():
    """items/values are the live backing lists (the schedulers' legacy
    tick_log/alive_log attributes alias them, not copy them)."""
    s = MetricsRegistry().series("serve/tick_log")
    items, values = s.items, s.values
    s.append(7.0, label="decode")
    assert items == [("decode", 7.0)] and values == [7.0]


def test_registry_kind_collision_asserts():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(AssertionError):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# instrumentation coverage: trainer + scheduler under the hostsync guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["traced_cond", "host_cond"])
def test_trainer_instrumentation_coverage(strategy):
    """A tiny instrumented Trainer run emits the §15 span vocabulary
    (chunk put/execute/fetch/record, host_cond's decide, prefetch
    produce/wait) and the MetricsFrame lands in the history records —
    with this module under the conftest transfer guard, the run also
    proves the tracer adds no hidden host syncs."""
    cfg = _cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, steps=4, seed=0)
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    tracer = Tracer(enabled=True)
    trainer = Trainer(cfg, tc, lambda i: task.sample_batch(i, 4), chunk=2,
                      strategy=strategy, log=None, tracer=tracer)
    _, history = trainer.run()
    names = {e[1] for e in tracer.events}
    assert {"train_chunk", "chunk.put", "chunk.execute", "chunk.fetch",
            "chunk.record", "prefetch.produce", "prefetch.wait"} <= names
    assert ("chunk.decide" in names) == (strategy == "host_cond")
    assert history
    for rec in history:
        assert {"router_entropy", "load_imbalance",
                "gate_dropped"} <= set(rec)
    # the exported trace of a real run is loadable Chrome JSON
    json.dumps(tracer.export())


def test_trainer_in_flight_counter_and_one_decide_per_run():
    """host_cond with an enabled tracer: every ``chunk.execute`` carries
    ``in_flight``, 0 on a run's first chunk and on the first chunk after
    each drain (at eval steps) and 1 on every other; ``chunk.decide``
    appears once per run() with ``steps`` the run's length."""
    cfg = _cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, steps=8, seed=0)
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    tracer = Tracer(enabled=True)
    trainer = Trainer(cfg, tc, lambda i: task.sample_batch(i, 4), chunk=2,
                      strategy="host_cond", eval_every=5,
                      eval_fn=lambda s, i: {}, log=None, tracer=tracer)
    spans = trainer.schedule()
    assert spans == [(0, 1), (1, 3), (3, 5), (5, 6), (6, 8)]
    trainer.run()
    trainer.start_step = 8
    trainer.tc = dataclasses.replace(tc, steps=12)
    trainer.run()
    assert trainer.schedule() == [(8, 10), (10, 11), (11, 12)]
    spans += trainer.schedule()
    ev = [e for e in tracer.events if e[0] == "X"]
    decide = [e[5] for e in ev if e[1] == "chunk.decide"]
    assert [(a["start"], a["stop"], a["steps"]) for a in decide] == [
        (0, 8, 8), (8, 12, 4)]
    flight = {}
    for e in ev:
        if e[1] == "chunk.execute":
            c = next(sp for sp in spans if sp[0] <= e[5]["start"] < sp[1])
            flight.setdefault(c, set()).add(e[5]["in_flight"])
    # drains after the eval steps 0, 5, 7, 10, 11; 7 and 11 end a run
    assert flight == {(0, 1): {0}, (1, 3): {0}, (3, 5): {1}, (5, 6): {1},
                      (6, 8): {0}, (8, 10): {0}, (10, 11): {1},
                      (11, 12): {0}}


# the name scopes a profiler trace reads the train step's layers by
LAYER_SCOPES = ("moe", "attention", "lm_head", "optimizer")


def _scopes(op_name: str) -> set:
    """Scopes named in an op's ``op_name`` metadata: path components
    equal to a scope, bare or wrapped by a transform, as in
    ``transpose(jvp(lm_head))``. Fusions join their ops' names by ';'."""
    comps = [c for seg in op_name.split(";") for c in seg.split("/")]
    return {s for s in LAYER_SCOPES for c in comps
            if re.fullmatch(r"(?:[\w]+\()*" + s + r"\)*", c)}


@pytest.mark.parametrize("decision", [False, True])
def test_host_cond_executables_carry_layer_scopes(decision):
    """Both host_cond executables of a tiny encoder-decoder MoE step
    (scanned, rematted layers) carry every layer scope in their compiled
    HLO, forward and backward."""
    cfg = reduced(get_config("zcode-m3-base"), remat=True)
    tc = TrainConfig(steps=1)
    task = MultilingualMT(MTTaskConfig(vocab=cfg.vocab, max_len=16,
                                       src_len=(4, 12)))
    batches = stack_batches(lambda i: task.sample_batch(i, 2), 0, 1)
    state = init_train_state(init_model(KEY, cfg), tc)
    text = make_chunk_step(cfg, tc).lower(state, batches,
                                          decision).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    found = set().union(*map(_scopes, names))
    assert found == set(LAYER_SCOPES)
    backward = set().union(*(_scopes(n) for n in names if "transpose(" in n))
    assert {"moe", "attention", "lm_head"} <= backward
    assert not _scopes("state['params']['moe']['router']['w']")


def test_spans_are_profiler_host_events(tmp_path):
    """A host_cond Trainer run with an enabled tracer inside a
    ``jax.profiler`` window: the profiler writes every chunk phase's span
    on the driving thread's line of the host plane, and the prefetch
    worker's spans on a line of their own."""
    from jax.profiler import ProfileData
    cfg = _cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, steps=4, seed=0)
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    trainer = Trainer(cfg, tc, lambda i: task.sample_batch(i, 4), chunk=2,
                      strategy="host_cond", log=None,
                      tracer=Tracer(enabled=True))
    trainer.run()                       # compile outside the window
    trainer.start_step, trainer.tc = 4, dataclasses.replace(tc, steps=8)
    with jax.profiler.trace(str(tmp_path)):
        trainer.run()
    path, = tmp_path.rglob("*.xplane.pb")
    host, = [p for p in ProfileData.from_file(str(path)).planes
             if p.name == "/host:CPU"]
    lines = [{e.name for e in line.events} for line in host.lines]
    driving = [i for i, names in enumerate(lines) if "train_chunk" in names]
    assert len(driving) == 1
    assert {"train_chunk", "chunk.decide", "chunk.put", "chunk.execute",
            "chunk.fetch", "chunk.record",
            "prefetch.wait"} <= lines[driving[0]]
    produce = [i for i, names in enumerate(lines)
               if "prefetch.produce" in names]
    assert produce and driving[0] not in produce


def test_scheduler_obs_and_compat_views():
    """An instrumented ContinuousScheduler run: tick spans recorded,
    TTFT/latency histograms populated at retire time, and the legacy
    tick_log/alive_log attributes are exact views over the registry
    series."""
    cfg = _cfg(moe=False)
    params = init_model(KEY, cfg)
    reqs = [Request(rid=i, tokens=np.asarray([3 + i, 4, 5], np.int32),
                    max_new=3, arrival=0.0) for i in range(3)]
    reg, tracer = MetricsRegistry(), Tracer(enabled=True)
    sched = ContinuousScheduler(params, cfg, GenerateConfig(max_new=3),
                                n_slots=2, prefill_buckets=(4,),
                                registry=reg, tracer=tracer)
    results = sched.run(reqs)
    assert len(results) == 3

    names = {e[1] for e in tracer.events}
    assert {"sched.admit", "sched.prefill", "sched.decode"} <= names
    assert reg.histogram("serve/ttft_s").count == 3
    assert reg.histogram("serve/per_token_latency_s").count == 3
    assert sched.tick_log is reg.series("serve/tick_log").items
    assert sched.alive_log is reg.series("serve/alive_log").values
    assert any(lab == "prefill" for lab, _ in sched.tick_log)
    assert any(lab == "decode" for lab, _ in sched.tick_log)
    labels = {lab for lab, _ in sched.tick_log}
    assert labels <= {"prefill", "decode"}
