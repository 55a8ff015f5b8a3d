"""The expert exchange (DESIGN.md §10): the all-to-all pair, its virtual
emulation, and telemetry/cost-model/HLO agreement.

The contract:
  * the sharded path's exchange is BITWISE the pre-refactor inline
    all-to-all pair, and matches the oracle's virtual emulation;
  * virtual dispatch∘combine is the identity;
  * every all-to-all of a routed layer runs under the ``exchange`` scope;
  * the in-graph telemetry equals the analytic model (`comm/exchange.py`)
    equals the collective ops parsed from compiled HLO on the sharded
    path, equals ZERO on Gate-Drop local / expert-drop steps, and the
    host_cond dropped executable contains zero all-to-alls.
"""
import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_py
from repro.comm import exchange_cost, layer_cost, vcombine, vdispatch
from repro.configs.base import GatingDropoutConfig, ModelConfig, MoEConfig
from repro.core import get_backend, init_moe_params
from repro.core.moe import moe_oracle


def _cfg(mode="gate_drop", E=8, k=2):
    return ModelConfig(
        d_model=32, d_ff=64, vocab=64, dtype="float32",
        moe=MoEConfig(n_experts=E, top_k=k, d_ff_expert=64, jitter_eps=0.0,
                      gating_dropout=GatingDropoutConfig(mode=mode,
                                                         rate=0.3)))


def _xp(cfg, shape=(8, 16, 32)):
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    return p, x


# ----------------------------------------------------- oracle (virtual) path

@pytest.mark.parametrize("mode", ["gate_drop", "gate_expert_drop"])
def test_telemetry_zero_on_dropped_steps(mode):
    """Gate-Drop local / expert-drop steps move NOTHING: every comm
    counter is zero; the routed branch of the same config (ep=4 virtual
    shards) is nonzero."""
    cfg = _cfg(mode=mode)
    p, x = _xp(cfg)
    _, aux_r = moe_oracle(p, x, cfg, ep=4, decision=False)
    _, aux_l = moe_oracle(p, x, cfg, ep=4, decision=True)
    assert float(aux_r["comm_a2a_calls"]) > 0
    assert float(aux_r["comm_bytes"]) > 0
    for k in ("comm_a2a_calls", "comm_bytes", "comm_wire_bytes"):
        assert float(aux_l[k]) == 0.0, (k, mode)


@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_telemetry_zero_at_ep1(backend):
    """One device = no wire: XLA deletes group-of-1 all-to-alls from the
    executable, so the counters report zero at ep=1 — telemetry always
    mirrors the compiled executable, never the nominal exchange."""
    cfg = _cfg()
    p, x = _xp(cfg)
    _, aux = get_backend(backend)(p, x, cfg, None, rng=None,
                                  decision=False, is_training=True,
                                  token_ids=None)
    for k in ("comm_a2a_calls", "comm_bytes", "comm_wire_bytes"):
        assert float(aux[k]) == 0.0, (k, backend)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("ep,E,cap,d", [(2, 8, 4, 16), (4, 8, 6, 8),
                                        (8, 8, 4, 16)])
def test_roundtrip_identity(ep, E, cap, d, dtype):
    """Virtual dispatch∘combine is BITWISE the identity, the all-dropped
    (zero) buffer included, and dispatch lays shard ``src``'s slots for
    expert e in row e, columns ``src*cap:(src+1)*cap`` — the layout the
    real all-to-all delivers."""
    x = (jax.random.normal(jax.random.PRNGKey(ep), (ep, E, cap, d))
         * 3).astype(dtype)
    for buf in (x, jnp.zeros_like(x)):
        out = vdispatch(buf)
        assert out.shape == (E, ep * cap, d) and out.dtype == buf.dtype
        for src in range(ep):
            np.testing.assert_array_equal(
                np.asarray(out[:, src * cap:(src + 1) * cap], np.float32),
                np.asarray(buf[src], np.float32))
        np.testing.assert_array_equal(
            np.asarray(vcombine(out, ep), np.float32),
            np.asarray(buf, np.float32))


# ------------------------------------------------------------- cost model

@pytest.mark.parametrize("ep,itemsize", [(1, 4), (2, 4), (8, 4), (8, 2)])
def test_cost_model_hand_computed(ep, itemsize):
    """exchange_cost against hand-computed numbers: E=8, cap=4, d=32.
    Two all-to-alls (dispatch + combine), each moving the whole
    (E, cap, d) buffer, of which a device keeps 1/ep; at ep=1 XLA deletes
    the group-of-one op, so nothing is counted."""
    E, cap, d = 8, 4, 32
    c = exchange_cost(ep=ep, n_experts=E, cap=cap, d_model=d,
                      itemsize=itemsize)
    assert set(c) == {"calls", "bytes", "wire_bytes"}
    if ep == 1:
        assert c == {"calls": 0.0, "bytes": 0.0, "wire_bytes": 0.0}
        return
    payload = E * cap * d * itemsize             # 4096 B per a2a at f32
    assert c["calls"] == 2 and c["bytes"] == 2 * payload
    assert c["wire_bytes"] == 2 * payload * (ep - 1) / ep


def test_total_loss_surfaces_comm_metrics():
    """training metrics carry the §10 counters, consistent with
    layer_cost x n_moe_layers (ep=1 in-process: both sides zero — the op
    is absent from the executable; the nonzero multi-device metric
    stream is asserted end-to-end in the subprocess Trainer test)."""
    from conftest import train_batch
    from repro.models import init_model
    from repro.training.steps import n_moe_layers, total_loss
    cfg = dataclasses.replace(
        _cfg(), n_layers=2, n_heads=2, n_kv_heads=2, remat=False,
        param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    b = train_batch(cfg, jax.random.PRNGKey(1), B=2, L=16)
    _, m_routed = total_loss(params, b, cfg, None, rng=None, decision=False)
    per_layer = layer_cost(cfg, tokens_per_shard=2 * 16, ep=1)
    for k, ck in (("comm_bytes", "bytes"), ("comm_a2a_calls", "calls"),
                  ("comm_wire_bytes", "wire_bytes")):
        assert float(m_routed[k]) == pytest.approx(
            per_layer[ck] * n_moe_layers(cfg)), k


# ------------------------------------------------------- sharded (real mesh)

def test_sharded_substrates_structural():
    """THE sharded-path contract on a real 8-device mesh:

    * the exchange is BITWISE the pre-refactor inline all_to_all pair;
    * telemetry == cost model == compiled-HLO collective count/bytes/wire.
    """
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import GatingDropoutConfig, ModelConfig, MoEConfig
from repro.comm import layer_cost
from repro.core import init_moe_params, moe_sharded, ParallelContext
from repro.core import router as R
from repro.core.moe import _expert_ffn
from repro.analysis import parse_collectives
from repro.launch.mesh import make_mesh

cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, dtype='float32',
    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=64, jitter_eps=0.0,
                  backend='sharded',
                  gating_dropout=GatingDropoutConfig(mode='gate_drop',
                                                     rate=0.3)))
ctx = ParallelContext(mesh=make_mesh((8,), ('data',)))
p = init_moe_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
f = jax.jit(lambda p_, x_: moe_sharded(p_, x_, cfg, ctx, rng=None,
                                       decision=False))
colls = parse_collectives(f.lower(p, x).compile().as_text())['all-to-all']
y, aux = f(p, x)
c = layer_cost(cfg, tokens_per_shard=16, ep=8)
assert float(aux['comm_a2a_calls']) == colls['count'] == c['calls'] == 2
assert float(aux['comm_bytes']) == colls['bytes'] == c['bytes']
assert abs(float(aux['comm_wire_bytes']) - colls['wire_bytes']) < 1
assert abs(float(aux['comm_wire_bytes']) - c['wire_bytes']) < 1

# pre-refactor reference: the exact inline code _routed_shard used to have
moe = cfg.moe
def legacy(wr, experts, x_loc):
    B, L, d = x_loc.shape
    xf = x_loc.reshape(B * L, d)
    T, E = xf.shape[0], moe.n_experts
    cap = min(R.capacity(T, E, moe.top_k, moe.capacity_factor), T)
    rr = R.route(wr, xf, moe, rng=None, is_training=True, token_ids=None)
    info = R.dispatch_info(rr, E, cap)
    buf = R.dispatch(xf, info, E, cap)
    buf = jax.lax.all_to_all(buf, 'data', split_axis=0, concat_axis=1,
                             tiled=True)
    out = _expert_ffn(experts, buf, cfg, None)
    out = jax.lax.all_to_all(out, 'data', split_axis=1, concat_axis=0,
                             tiled=True)
    return R.combine(out, info).reshape(B, L, d)
espec = {'w_in': P('data', None, None), 'w_out': P('data', None, None),
         'w_gate': P('data', None, None)}
fn = jax.shard_map(legacy, mesh=ctx.mesh,
                   in_specs=(P(), espec, P('data', None, None)),
                   out_specs=P('data', None, None), check_vma=False)
y_legacy = np.asarray(fn(p['router']['w'], p['experts'], x))
assert np.array_equal(y_legacy, np.asarray(y)), 'exchange != inline pair'
print('OK')
""")
    assert "OK" in out


def test_sharded_ep_on_model():
    """With ep_on_model the ep group IS (data x model): the exchange is
    one all-to-all over the axis pair each way (2 calls) and matches the
    oracle's emulation over all 8 virtual shards."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MoEConfig
from repro.core import init_moe_params, moe_sharded, ParallelContext
from repro.core.moe import moe_oracle
from repro.launch.mesh import make_mesh

cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, dtype='float32',
    moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=64, jitter_eps=0.0,
                  ep_on_model=True, backend='sharded'))
ctx = ParallelContext(mesh=make_mesh((4, 2), ('data', 'model')))
p = init_moe_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
y, aux = jax.jit(lambda p_, x_: moe_sharded(p_, x_, cfg, ctx, rng=None,
                                            decision=False))(p, x)
assert float(aux['comm_a2a_calls']) == 2
# shard (data=i, model=j) holds batch rows 2i..2i+1, sequence half j:
# virtual shard 2i+j of the oracle gets the same tokens in that order
xv = x.reshape(4, 2, 2, 8, 32).transpose(0, 2, 1, 3, 4).reshape(8, 16, 32)
y_o, aux_o = moe_oracle(p, xv, cfg, ep=8, decision=False)
y_o = np.asarray(y_o).reshape(4, 2, 2, 8, 32).transpose(0, 2, 1, 3, 4)
assert np.abs(y_o.reshape(8, 16, 32) - np.asarray(y)).max() < 1e-5
assert float(aux_o['comm_a2a_calls']) == 2
print('OK')
""")
    assert "OK" in out


_MESH_CASES = [(ep, k) for ep in (2, 4, 8) for k in (1, 2)]


@pytest.fixture(scope="module")
def mesh_runs():
    """One 8-device subprocess for the real-mesh cases below: the routed
    sharded layer (real all-to-all) on a sub-mesh of ``ep`` devices
    against the oracle's virtual emulation at the same ``ep``, and the
    op names of every all-to-all in the lowered routed and dropped
    layers, forward and backward."""
    out = run_py("""
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs.base import GatingDropoutConfig, ModelConfig, MoEConfig
from repro.core import init_moe_params, moe_apply, moe_sharded, ParallelContext
from repro.core.moe import moe_oracle

def cfg_with(k):
    return ModelConfig(d_model=32, d_ff=64, vocab=64, dtype='float32',
        moe=MoEConfig(n_experts=8, top_k=k, d_ff_expert=64, jitter_eps=0.0,
                      backend='sharded',
                      gating_dropout=GatingDropoutConfig(mode='gate_drop',
                                                         rate=0.3)))

x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
res = {'parity': {}, 'ops': {}}
KEYS = ('comm_a2a_calls', 'comm_bytes', 'comm_wire_bytes')
for ep in (2, 4, 8):
    ctx = ParallelContext(mesh=Mesh(np.array(jax.devices()[:ep]), ('data',)))
    for k in (1, 2):
        cfg = cfg_with(k)
        p = init_moe_params(jax.random.PRNGKey(0), cfg)
        y, aux = jax.jit(lambda p_, x_: moe_sharded(
            p_, x_, cfg, ctx, rng=None, decision=False))(p, x)
        y_o, aux_o = moe_oracle(p, x, cfg, ep=ep, decision=False)
        res['parity'][f'{ep}-{k}'] = {
            'max_abs': float(jnp.abs(y - y_o).max()),
            'aux': [float(aux[n]) for n in KEYS],
            'aux_oracle': [float(aux_o[n]) for n in KEYS]}

cfg = cfg_with(2)
ctx = ParallelContext(mesh=Mesh(np.array(jax.devices()), ('data',)))
p = init_moe_params(jax.random.PRNGKey(0), cfg)
for name, dec in (('routed', False), ('dropped', True)):
    def loss(p_, x_):
        y, _ = moe_apply(p_, x_, cfg, ctx, rng=None, decision=dec)
        return (y ** 2).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, x).compile().as_text()
    a2a = [ln for ln in text.splitlines()
           if re.search(r" all-to-all(?:-start)?\\(", ln)]
    names = [re.search(r'op_name="([^"]*)"', ln) for ln in a2a]
    res['ops'][name] = {
        'a2a_names': [m.group(1) if m else '' for m in names],
        'exchange_ops': len(re.findall(r'op_name="[^"]*exchange/', text))}
print(json.dumps(res))
""")
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("ep,k", _MESH_CASES,
                         ids=[f"ep{ep}-top{k}" for ep, k in _MESH_CASES])
def test_sharded_matches_oracle(mesh_runs, ep, k):
    """The real all-to-all on ``ep`` devices delivers the permutation the
    oracle's virtual exchange emulates: the routed layers agree to f32
    noise and report the same telemetry."""
    r = mesh_runs["parity"][f"{ep}-{k}"]
    assert r["max_abs"] < 1e-5, r
    assert r["aux"] == r["aux_oracle"], r
    assert r["aux"][0] == 2.0, r


@pytest.mark.parametrize("name", ["routed", "dropped"])
def test_exchange_scope_in_lowered_layer(mesh_runs, name):
    """Lowered with its gradient on the 8-device CPU mesh, the routed
    sharded layer runs every all-to-all (2 forward, 2 backward) under
    ``moe/.../exchange/``, the name a profiler trace reads back (here
    ``jvp(moe)/...``, since ``moe`` is the outermost scope); the dropped
    layer has neither an all-to-all nor an ``exchange`` op."""
    r = mesh_runs["ops"][name]
    if name == "dropped":
        assert r == {"a2a_names": [], "exchange_ops": 0}, r
        return
    assert len(r["a2a_names"]) == 4, r
    assert all(re.search(r"(^|[/(])moe\)*/(.*/)?exchange/", n)
               for n in r["a2a_names"]), r


def test_dropped_chunk_no_a2a_and_trainer_telemetry():
    """The §5/§8 structural claim: a host_cond dropped chunk executable
    contains zero all-to-alls; the routed one contains them. And the
    Trainer's per-step history records carry the in-graph counters on a
    REAL 8-device mesh: routed steps report the full per-step wire,
    dropped steps zero — exactly following the host-drawn decisions."""
    out = run_py("""
import json
import re
import jax, jax.numpy as jnp
from repro.configs.base import (GatingDropoutConfig, ModelConfig, MoEConfig,
                                TrainConfig)
from repro.core.gating_dropout import drop_decision_host, drop_decisions_host
from repro.core.moe import ParallelContext
from repro.data import LMTaskConfig, SyntheticLM, stack_batches
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.training import Trainer, init_train_state, make_chunk_step
ctx = ParallelContext(mesh=make_mesh((8,), ('data',)))
gd_cfg = GatingDropoutConfig(mode='gate_drop', rate=0.5, strategy='host_cond')
# the first seed whose 6-step window holds both consensus bits
seed = next(s for s in range(64)
            if len(set(drop_decisions_host(gd_cfg, s, 0, 6).tolist())) == 2)
cfg = ModelConfig(d_model=64, d_ff=128, vocab=100, n_layers=1, n_heads=2,
                  n_kv_heads=2, remat=False, dtype='float32',
                  param_dtype='float32',
                  moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=128,
                                backend='sharded', gating_dropout=gd_cfg))
tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=seed, steps=6)
task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
batches = {k: jnp.asarray(v) for k, v in
           stack_batches(lambda i: task.sample_batch(i, 8), 0, 2).items()}
state = init_train_state(init_model(jax.random.PRNGKey(0), cfg), tc)
chunk = make_chunk_step(cfg, tc, ctx, jit=False)
for dec, name in [(False, 'routed'), (True, 'dropped')]:
    txt = jax.jit(chunk, static_argnums=(2,)).lower(
        state, batches, dec).compile().as_text()
    print(name, txt.count('all-to-all'))
tr = Trainer(cfg, tc, lambda i: task.sample_batch(i, 8), ctx=ctx, chunk=3,
             strategy='host_cond', log=None, log_every=1)
_, hist = tr.run()
gd = cfg.moe.gating_dropout
wire = [r['comm_wire_bytes'] for r in hist]
assert any(w > 0 for w in wire) and any(w == 0 for w in wire), wire
for r in hist:
    dropped = drop_decision_host(gd, tc.seed, r['step'])
    assert (r['comm_wire_bytes'] == 0) == dropped, r
    assert (r['comm_a2a_calls'] == 0) == dropped, r
print('trainer_ok', 1)
""")
    lines = dict(line.split() for line in out.strip().splitlines())
    assert int(lines["routed"]) > 0
    assert int(lines["dropped"]) == 0
    assert int(lines["trainer_ok"]) == 1


# ----------------------------------------------------------------- serving

def test_scheduler_tick_log_prices_the_trace():
    """The scheduler records every device call; the serve CLI's comm
    section prices them with the cost model — local_routing decode ticks
    cost zero on the wire."""
    from repro.launch.serve import trace_comm_section
    from repro.models import init_model
    from repro.serve import ContinuousScheduler, GenerateConfig, Request
    cfg = dataclasses.replace(
        _cfg(k=1), n_layers=2, n_heads=2, n_kv_heads=2, remat=False,
        param_dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    gen = GenerateConfig(max_new=4, eos_id=-1)
    reqs = [Request(rid=i, tokens=np.full(4 + i, 3, np.int32), arrival=0.0)
            for i in range(2)]
    sched = ContinuousScheduler(params, cfg, gen, n_slots=2,
                                prefill_buckets=(8,))
    sched.run(reqs)
    kinds = {k for k, _ in sched.tick_log}
    assert kinds == {"prefill", "decode"}
    assert len(sched.tick_log) >= sched.stats["decode_steps"]
    sec = trace_comm_section(cfg, gen, sched, ep=8)
    assert sec["ep_model"] == 8
    assert sec["wire_bytes_total"] > 0
    assert sec["n_ticks"] == len(sched.tick_log)
    assert set(sec["wire_bytes_per_tick"]) == {50, 90, 99}
    # local routing: decode moves nothing; only prefills are priced
    gen_l = dataclasses.replace(gen, local_routing=True)
    sec_l = trace_comm_section(cfg, gen_l, sched, ep=8)
    assert sec_l["wire_bytes_total"] < sec["wire_bytes_total"]
