"""Communication substrate (DESIGN.md §10): registry + quantization +
topology algebra + telemetry/cost-model/HLO agreement.

The contract, by substrate:
  dense        -- BITWISE the pre-refactor inline all-to-all pair;
  hierarchical -- same permutation as dense (bitwise), two factored hops;
  compressed   -- forward within int8/fp8 tolerance of dense, gradients
                  flow through the quantize custom VJP;
and for all of them: the in-graph telemetry equals the analytic model
(`comm/cost.py`) equals the collective ops parsed from compiled HLO on
the sharded path, equals ZERO on Gate-Drop local / expert-drop steps,
and the host_cond dropped executable still contains zero all-to-alls.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_py
from repro.comm import (available_substrates, dequantize, ep_tier_groups,
                        factored_ep, format_table, get_substrate, layer_cost,
                        quantize, substrate_table, transport_cost)
from repro.configs.base import (CommConfig, GatingDropoutConfig, ModelConfig,
                                MoEConfig, TrainConfig)
from repro.core import get_backend, init_moe_params
from repro.core.moe import moe_oracle

KEY = jax.random.PRNGKey(0)


def _cfg(comm=CommConfig(), mode="gate_drop", E=8, k=2):
    return ModelConfig(
        d_model=32, d_ff=64, vocab=64, dtype="float32",
        moe=MoEConfig(n_experts=E, top_k=k, d_ff_expert=64, jitter_eps=0.0,
                      comm=comm,
                      gating_dropout=GatingDropoutConfig(mode=mode,
                                                         rate=0.3)))


def _xp(cfg, shape=(8, 16, 32)):
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    return p, x


# ---------------------------------------------------------------- registry

def test_registry_contents_and_errors():
    from repro.configs.base import COMM_SUBSTRATES
    assert set(available_substrates()) == set(COMM_SUBSTRATES) == {
        "dense", "hierarchical", "compressed", "hierarchical_compressed",
        "overlapped", "overlapped_hierarchical", "overlapped_compressed",
        "overlapped_hierarchical_compressed"}
    with pytest.raises(KeyError, match="unknown comm substrate"):
        get_substrate("nope")
    with pytest.raises(AssertionError):
        CommConfig(substrate="nope")
    with pytest.raises(AssertionError):
        CommConfig(quant="int4")
    with pytest.raises(AssertionError):
        CommConfig(n_chunks=0)
    c = CommConfig(substrate="hierarchical_compressed")
    assert c.hierarchical and c.compressed and not c.overlapped
    assert not CommConfig().hierarchical and not CommConfig().compressed
    o = CommConfig(substrate="overlapped_hierarchical_compressed")
    assert o.overlapped and o.hierarchical and o.compressed
    assert CommConfig(substrate="overlapped").overlapped
    assert not CommConfig(substrate="overlapped").hierarchical
    assert not CommConfig(substrate="overlapped").compressed


def test_factored_ep_and_tier_groups():
    assert factored_ep(16, 0) == (4, 4)
    assert factored_ep(8, 0) == (2, 4)
    assert factored_ep(8, 4) == (4, 2)
    assert factored_ep(1, 0) == (1, 1)
    with pytest.raises(AssertionError):
        factored_ep(8, 3)
    intra, inter = ep_tier_groups(8, 4)
    assert intra == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert inter == ((0, 4), (1, 5), (2, 6), (3, 7))
    # groups partition the ranks, both ways
    for groups in (intra, inter):
        assert sorted(r for g in groups for r in g) == list(range(8))


# ------------------------------------------------------------ quantization

@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantize_roundtrip_bounds(mode):
    x = jax.random.normal(KEY, (4, 7, 33)) * 10.0
    q, s = quantize(x, mode)
    y = dequantize(q, s, x.dtype)
    assert q.dtype == (jnp.int8 if mode == "int8" else jnp.float8_e4m3fn)
    assert s.shape == x.shape[:-1] + (1,)
    # per-row scaled: error bounded by scale/2 (int8) / fp8 ulp
    amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
    bound = amax / (2 * 127) if mode == "int8" else amax / 16
    assert (np.abs(np.asarray(y - x)) <= bound + 1e-7).all()
    # all-zero rows survive exactly
    q0, s0 = quantize(jnp.zeros((3, 5)), mode)
    np.testing.assert_array_equal(np.asarray(dequantize(q0, s0, x.dtype)),
                                  np.zeros((3, 5)))


# ----------------------------------------------------- oracle (virtual) path

def test_oracle_hierarchical_bitwise_dense():
    """The two-hop factored exchange is the SAME permutation as the flat
    all-to-all — virtual emulation, ep=4 (gi=2, go=2)."""
    p, x = _xp(_cfg())
    y_d, _ = moe_oracle(p, x, _cfg(), ep=4, decision=False)
    y_h, _ = moe_oracle(p, x, _cfg(CommConfig(substrate="hierarchical")),
                        ep=4, decision=False)
    np.testing.assert_array_equal(np.asarray(y_d), np.asarray(y_h))
    # explicit non-square factorization too
    y_h2, _ = moe_oracle(
        p, x, _cfg(CommConfig(substrate="hierarchical", ep_inner=4)),
        ep=4, decision=False)
    np.testing.assert_array_equal(np.asarray(y_d), np.asarray(y_h2))


@pytest.mark.parametrize("quant,tol", [("int8", 0.05), ("fp8", 0.3)])
def test_oracle_compressed_forward_parity(quant, tol):
    """Quantized wire: forward within per-row quantization tolerance of
    dense; composing with hierarchical changes NOTHING (quantize once,
    permutation in between)."""
    p, x = _xp(_cfg())
    y_d, _ = moe_oracle(p, x, _cfg(), ep=4, decision=False)
    y_c, _ = moe_oracle(
        p, x, _cfg(CommConfig(substrate="compressed", quant=quant)),
        ep=4, decision=False)
    scale = float(jnp.abs(y_d).max())
    assert float(jnp.abs(y_d - y_c).max()) < tol * scale
    y_hc, _ = moe_oracle(
        p, x, _cfg(CommConfig(substrate="hierarchical_compressed",
                              quant=quant)), ep=4, decision=False)
    np.testing.assert_array_equal(np.asarray(y_c), np.asarray(y_hc))


def test_compressed_gradient_flows_through_quantize_vjp():
    """The custom VJP (straight-through + compressed reverse wire) keeps
    the routed path trainable: gradients nonzero for EVERY param and
    close to the dense-substrate gradients."""
    p, x = _xp(_cfg())

    def loss(pp, comm):
        y, _ = moe_oracle(pp, x, _cfg(comm), ep=4, decision=False)
        return (y ** 2).sum()

    g_d = jax.grad(lambda pp: loss(pp, CommConfig()))(p)
    g_c = jax.jit(jax.grad(
        lambda pp: loss(pp, CommConfig(
            substrate="hierarchical_compressed"))))(p)
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_c)):
        assert float(jnp.abs(b).max()) > 0.0
        ref = float(jnp.abs(a).max())
        assert float(jnp.abs(a - b).max()) < 0.05 * ref, (ref,)


def test_pallas_ep1_matches_oracle_compressed():
    """Backend choice must not change numerics: the ep=1 kernel pipeline
    applies the same payload wire transform (roundtrip quant->dequant)
    and reports the same telemetry as the oracle."""
    cfg = _cfg(CommConfig(substrate="compressed"))
    p, x = _xp(cfg)
    y_o, aux_o = moe_oracle(p, x, cfg, ep=1, decision=False)
    y_p, aux_p = get_backend("pallas")(p, x, cfg, None, rng=None,
                                       decision=False, is_training=True,
                                       token_ids=None)
    np.testing.assert_allclose(np.asarray(y_o), np.asarray(y_p), atol=2e-6)
    for k in ("comm_a2a_calls", "comm_bytes", "comm_wire_bytes"):
        assert float(aux_o[k]) == float(aux_p[k]), k


@pytest.mark.parametrize("mode", ["gate_drop", "gate_expert_drop"])
def test_telemetry_zero_on_dropped_steps(mode):
    """Gate-Drop local / expert-drop steps move NOTHING: every comm
    counter is zero; the routed branch of the same config (ep=4 virtual
    shards) is nonzero."""
    cfg = _cfg(CommConfig(substrate="compressed"), mode=mode)
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
    mirrors the compiled executable, never the nominal transport."""
    cfg = _cfg(CommConfig(substrate="compressed"))
    p, x = _xp(cfg)
    _, aux = get_backend(backend)(p, x, cfg, None, rng=None,
                                  decision=False, is_training=True,
                                  token_ids=None)
    for k in ("comm_a2a_calls", "comm_bytes", "comm_wire_bytes"):
        assert float(aux[k]) == 0.0, (k, backend)


# ------------------------------------------------------------- cost model

def test_cost_model_hand_computed():
    """transport_cost against hand-computed numbers: E=8, cap=4, d=32,
    f32 payload, ep=8 (hier auto: gi=2, go=4)."""
    E, cap, d, isz, ep = 8, 4, 32, 4, 8
    payload = E * cap * d * isz                  # 4096 B per a2a
    c = transport_cost(CommConfig(), ep=ep, n_experts=E, cap=cap,
                       d_model=d, itemsize=isz)
    assert c["calls"] == 2 and c["bytes"] == 2 * payload
    assert c["wire_bytes"] == pytest.approx(2 * payload * 7 / 8)
    assert c["intra_wire_bytes"] == 0.0          # flat = all inter-tier
    h = transport_cost(CommConfig(substrate="hierarchical"), ep=ep,
                       n_experts=E, cap=cap, d_model=d, itemsize=isz)
    assert h["calls"] == 4 and h["bytes"] == 4 * payload
    assert h["wire_bytes"] == pytest.approx(
        2 * payload * (1 / 2 + 3 / 4))           # gi=2, go=4
    assert h["inter_wire_bytes"] == pytest.approx(2 * payload * 3 / 4)
    q = transport_cost(CommConfig(substrate="compressed"), ep=ep,
                       n_experts=E, cap=cap, d_model=d, itemsize=isz)
    qbytes = E * cap * d * 1 + E * cap * 4       # int8 payload + f32 scales
    assert q["calls"] == 4 and q["bytes"] == 2 * qbytes
    # the headline claim at f32 activations: <= 0.5x dense on the wire
    assert q["wire_bytes"] <= 0.5 * c["wire_bytes"]
    hq = transport_cost(
        CommConfig(substrate="hierarchical_compressed"), ep=ep,
        n_experts=E, cap=cap, d_model=d, itemsize=isz)
    assert hq["calls"] == 8 and hq["bytes"] == 4 * qbytes
    # mesh-fixed tiers override the auto factorization
    h2 = transport_cost(CommConfig(substrate="hierarchical"), ep=ep,
                        n_experts=E, cap=cap, d_model=d, itemsize=isz,
                        tiers=(4, 2))
    assert h2["wire_bytes"] == pytest.approx(
        2 * payload * (3 / 4 + 1 / 2))
    # degenerate groups (size 1) are deleted by XLA -> not counted:
    # ep=1 moves nothing; prime ep collapses hierarchical to one hop
    c1 = transport_cost(CommConfig(), ep=1, n_experts=E, cap=cap,
                        d_model=d, itemsize=isz)
    assert c1["calls"] == 0 and c1["bytes"] == 0
    h1 = transport_cost(CommConfig(substrate="hierarchical"), ep=2,
                        n_experts=E, cap=cap, d_model=d, itemsize=isz)
    assert h1["calls"] == 2                     # gi=1 intra hop skipped


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_roundtrip_identity_every_substrate(dtype):
    """§14 round-trip property: dispatch∘combine is a pure permutation
    pair, so the transport round trip with an identity FFN body is
    BITWISE identity — for EVERY substrate x ep shape x chunk count,
    including the all-dropped (zero) buffer. Compressed substrates hold
    it on the quantizer's fixed points (one ``roundtrip`` application is
    idempotent — also asserted), so the payload is stabilized first."""
    from repro.comm.substrate import CommEnv, make_transport
    for ep, E, cap, d in ((2, 8, 4, 16), (4, 8, 6, 8), (8, 8, 4, 16)):
        x = (jax.random.normal(jax.random.PRNGKey(ep), (ep, E, cap, d))
             * 3).astype(dtype)
        for name in available_substrates():
            for n_chunks in (1, 2, cap):
                comm = CommConfig(substrate=name, n_chunks=n_chunks)
                t = make_transport(comm, CommEnv(ep=ep))
                for buf in (x, jnp.zeros_like(x)):
                    ref = t.roundtrip(buf)          # fixed-point payload
                    np.testing.assert_array_equal(
                        np.asarray(t.roundtrip(ref), np.float32),
                        np.asarray(ref, np.float32),
                        err_msg=f"roundtrip not idempotent: {name}")
                    out = t.vpipelined(ref, lambda b: b)
                    np.testing.assert_array_equal(
                        np.asarray(out, np.float32),
                        np.asarray(ref, np.float32),
                        err_msg=f"{name} ep={ep} cap={cap} n={n_chunks}")
    # the sweep leaves thousands of small chunk-shaped executables in the
    # process-wide jit cache; drop them so the rest of the suite compiles
    # against a clean CPU client (avoids late-suite compiler OOM/segfault)
    jax.clear_caches()


def test_chunked_cost_invariants_every_substrate():
    """§14 accounting regression: overlapping multiplies the a2a CALL
    count by n_eff but leaves total bytes / wire / tier split EXACTLY
    equal to the base substrate (the per-chunk payload divides evenly —
    integer arithmetic, no approx); exposed = wire/n_eff with hidden the
    remainder; non-overlapped substrates expose everything and hide
    nothing."""
    from repro.comm import effective_chunks
    E, cap, d, isz, ep = 8, 8, 32, 4, 8
    kw = dict(ep=ep, n_experts=E, cap=cap, d_model=d, itemsize=isz)
    for base in ("dense", "hierarchical", "compressed",
                 "hierarchical_compressed"):
        ov = "overlapped" if base == "dense" else f"overlapped_{base}"
        c0 = transport_cost(CommConfig(substrate=base), **kw)
        assert c0["exposed_wire_bytes"] == c0["wire_bytes"], base
        assert c0["hidden_wire_bytes"] == 0.0, base
        for n in (1, 2, 4, 8, 5):                   # 5 -> n_eff 4
            n_eff = effective_chunks(cap, n)
            cN = transport_cost(CommConfig(substrate=ov, n_chunks=n), **kw)
            assert cN["calls"] == c0["calls"] * n_eff, (ov, n)
            assert cN["bytes"] == c0["bytes"], (ov, n)
            assert cN["wire_bytes"] == c0["wire_bytes"], (ov, n)
            assert cN["intra_wire_bytes"] == c0["intra_wire_bytes"], (ov, n)
            assert cN["inter_wire_bytes"] == c0["inter_wire_bytes"], (ov, n)
            assert cN["exposed_wire_bytes"] == pytest.approx(
                cN["wire_bytes"] / n_eff), (ov, n)
            assert (cN["exposed_wire_bytes"] + cN["hidden_wire_bytes"]
                    == pytest.approx(cN["wire_bytes"])), (ov, n)
    # the chunk-count rule the transport and cost model share
    assert effective_chunks(16, 5) == 4
    assert effective_chunks(16, 16) == 16
    assert effective_chunks(16, 100) == 16          # clamped to cap
    assert effective_chunks(7, 3) == 1              # prime cap
    assert effective_chunks(6, 4) == 3


def test_transport_time_and_pipeline_time():
    """The §14 bandwidth-weighted time model: intra wire priced at the
    ICI-class rate, inter at the DCN-class rate; the two-resource FIFO
    pipeline estimate equals the hand-computed schedule."""
    from repro.comm import pipeline_time, transport_time
    from repro.configs.base import Topology
    top = Topology(intra_gbps=400.0, inter_gbps=50.0)
    E, cap, d, isz, ep = 8, 4, 32, 4, 8
    kw = dict(ep=ep, n_experts=E, cap=cap, d_model=d, itemsize=isz)
    c = transport_cost(CommConfig(substrate="hierarchical"), **kw)
    t = transport_time(c, top)
    assert t["comm_s"] == pytest.approx(
        c["intra_wire_bytes"] / 400e9 + c["inter_wire_bytes"] / 50e9)
    assert t["exposed_s"] == pytest.approx(t["comm_s"])  # non-overlapped
    cd = transport_cost(CommConfig(substrate="dense"), **kw)
    td = transport_time(cd, top)
    assert td["comm_s"] == pytest.approx(cd["wire_bytes"] / 50e9)
    # hierarchical moves MORE wire yet costs LESS time on the two-tier
    # mesh — the whole point of the factored exchange
    assert c["wire_bytes"] > cd["wire_bytes"]
    assert t["comm_s"] < td["comm_s"]
    co = transport_cost(CommConfig(substrate="overlapped", n_chunks=4),
                        **kw)
    to = transport_time(co, top)
    assert to["comm_s"] == pytest.approx(td["comm_s"])   # same wire
    assert to["exposed_s"] == pytest.approx(td["comm_s"] / 4)
    assert to["hidden_s"] == pytest.approx(3 * td["comm_s"] / 4)
    # FIFO pipeline: n=1 is fully serial; W==C at n=4 hand-computes to
    # 1.25 (vs 2.0 serial -> 1.6x); deeper never hurts; comm-bound floor
    assert pipeline_time(1.0, 1.0, 1) == pytest.approx(2.0)
    assert pipeline_time(1.0, 1.0, 4) == pytest.approx(1.25)
    assert (pipeline_time(1.0, 1.0, 8) <= pipeline_time(1.0, 1.0, 4)
            <= pipeline_time(1.0, 1.0, 2) <= 2.0)
    assert pipeline_time(0.1, 1.0, 8) >= 1.0         # can't beat the wire
    assert pipeline_time(1.0, 0.1, 8) >= 1.0         # ... or the compute


def test_substrate_table_and_dryrun_comm_table():
    """The --comm-table surface: every substrate priced, compressed
    halves the wire (plus the tiny scale overhead), hierarchical moves
    its inter-tier share below dense's all-inter wire."""
    cfg = _cfg()
    t = substrate_table(cfg, tokens_per_shard=64, ep=16, n_chunks=4)
    assert set(t) == set(available_substrates())
    dense = t["dense"]
    assert t["compressed"]["wire_bytes"] <= 0.55 * dense["wire_bytes"]
    assert (t["hierarchical"]["inter_wire_bytes"]
            < dense["inter_wire_bytes"])
    assert (t["hierarchical_compressed"]["inter_wire_bytes"]
            < t["compressed"]["inter_wire_bytes"])
    # §14 columns: overlapped rows expose wire/n_eff of identical totals
    # and carry a strictly smaller exposed-time estimate
    ov = t["overlapped"]
    assert ov["wire_bytes"] == dense["wire_bytes"]
    assert ov["exposed_wire_bytes"] < dense["exposed_wire_bytes"]
    assert ov["t_comm_s"] == pytest.approx(dense["t_comm_s"])
    assert ov["t_exposed_s"] < dense["t_exposed_s"]
    txt = format_table(t)
    for name in t:
        assert name in txt
    # the launch surface is pure math over the same model
    from repro.launch.dryrun import comm_table
    tbl = comm_table("zcode-m3-base", "train_4k")
    assert set(tbl) == set(available_substrates())
    assert tbl["compressed"]["wire_bytes"] < tbl["dense"]["wire_bytes"]


def test_total_loss_surfaces_comm_metrics():
    """training metrics carry the §10 counters, consistent with
    layer_cost x n_moe_layers (ep=1 in-process: both sides zero — the op
    is absent from the executable; the nonzero multi-device metric
    stream is asserted end-to-end in the subprocess Trainer test)."""
    from conftest import train_batch
    from repro.models import init_model
    from repro.training.steps import n_moe_layers, total_loss
    cfg = dataclasses.replace(
        _cfg(CommConfig(substrate="compressed")), n_layers=2, n_heads=2,
        n_kv_heads=2, remat=False, param_dtype="float32")
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
    """THE sharded-path contract on a real 8-device mesh, all substrates:

    * dense is BITWISE the pre-refactor inline all_to_all pair;
    * hierarchical is BITWISE dense (axis_index_groups two-hop);
    * compressed matches dense within quantization tolerance and matches
      the oracle emulation to f32 noise;
    * telemetry == cost model == compiled-HLO collective count/bytes/wire
      for every substrate."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import (CommConfig, GatingDropoutConfig, ModelConfig,
                                MoEConfig)
from repro.comm import layer_cost
from repro.core import init_moe_params, moe_sharded, ParallelContext
from repro.core import router as R
from repro.core.moe import _expert_ffn, moe_oracle
from repro.analysis import parse_collectives
from repro.launch.mesh import make_mesh

def cfg_with(comm):
    return ModelConfig(d_model=32, d_ff=64, vocab=64, dtype='float32',
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=64, jitter_eps=0.0,
                      comm=comm, backend='sharded',
                      gating_dropout=GatingDropoutConfig(mode='gate_drop',
                                                         rate=0.3)))

ctx = ParallelContext(mesh=make_mesh((8,), ('data',)))
p = init_moe_params(jax.random.PRNGKey(0), cfg_with(CommConfig()))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
ys = {}
for name in ('dense', 'hierarchical', 'compressed',
             'hierarchical_compressed', 'overlapped',
             'overlapped_hierarchical_compressed'):
    comm = CommConfig(substrate=name, n_chunks=2)
    cfg = cfg_with(comm)
    f = jax.jit(lambda p_, x_: moe_sharded(p_, x_, cfg, ctx, rng=None,
                                           decision=False))
    colls = parse_collectives(f.lower(p, x).compile().as_text()
                              )['all-to-all']
    y, aux = f(p, x)
    ys[name] = np.asarray(y)
    c = layer_cost(cfg, tokens_per_shard=16, ep=8)
    assert float(aux['comm_a2a_calls']) == colls['count'] == c['calls'], name
    assert float(aux['comm_bytes']) == colls['bytes'] == c['bytes'], name
    assert abs(float(aux['comm_wire_bytes']) - colls['wire_bytes']) < 1, name
    assert abs(float(aux['comm_wire_bytes']) - c['wire_bytes']) < 1, name
    assert (float(aux['comm_exposed_bytes'] + aux['comm_hidden_bytes'])
            == float(aux['comm_wire_bytes'])), name

assert np.array_equal(ys['dense'], ys['hierarchical'])
assert np.array_equal(ys['compressed'], ys['hierarchical_compressed'])
# §14: the micro-chunked pipeline is BITWISE its base substrate — and the
# unrolled per-chunk collectives really are distinct HLO ops (2 hops x 2
# chunks for overlapped vs dense's 2; x2 again for the factored hops)
assert np.array_equal(ys['dense'], ys['overlapped'])
assert np.array_equal(ys['compressed'],
                      ys['overlapped_hierarchical_compressed'])
scale = np.abs(ys['dense']).max()
assert np.abs(ys['dense'] - ys['compressed']).max() < 0.05 * scale

# oracle emulation == sharded, for the quantized wire too
cfgc = cfg_with(CommConfig(substrate='compressed'))
y_o, _ = moe_oracle(p, x, cfgc, ep=8, decision=False)
assert np.abs(np.asarray(y_o) - ys['compressed']).max() < 1e-5

# pre-refactor reference: the exact inline code _routed_shard used to have
cfg = cfg_with(CommConfig())
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
assert np.array_equal(y_legacy, ys['dense']), 'dense != pre-refactor inline'
print('OK')
""")
    assert "OK" in out


def test_sharded_hierarchical_ep_on_model():
    """Two-mesh-axes tiers: with ep_on_model the ep group IS
    (data x model); the hierarchical substrate hops over `model` (intra)
    then `data` (inter) — still bitwise the flat tuple-axis a2a."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.base import (CommConfig, GatingDropoutConfig, ModelConfig,
                                MoEConfig)
from repro.core import init_moe_params, moe_sharded, ParallelContext
from repro.launch.mesh import make_mesh

def cfg_with(comm):
    return ModelConfig(d_model=32, d_ff=64, vocab=64, dtype='float32',
        moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=64, jitter_eps=0.0,
                      ep_on_model=True, comm=comm, backend='sharded'))

ctx = ParallelContext(mesh=make_mesh((4, 2), ('data', 'model')))
p = init_moe_params(jax.random.PRNGKey(0), cfg_with(CommConfig()))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
ys = {}
for name in ('dense', 'hierarchical'):
    cfg = cfg_with(CommConfig(substrate=name))
    y, aux = jax.jit(lambda p_, x_: moe_sharded(p_, x_, cfg, ctx, rng=None,
                                                decision=False))(p, x)
    ys[name] = np.asarray(y)
    assert float(aux['comm_a2a_calls']) == (2 if name == 'dense' else 4)
assert np.array_equal(ys['dense'], ys['hierarchical'])
print('OK')
""")
    assert "OK" in out


def test_dropped_chunk_no_a2a_and_trainer_telemetry():
    """The §5/§8 structural claim survives EVERY wire: a host_cond
    dropped chunk executable contains zero all-to-alls even when the
    routed branch would use the maximal substrate composition
    (hierarchical + compressed); the routed one contains them. And the
    Trainer's per-step history records carry the in-graph counters on a
    REAL 8-device mesh: routed steps report the full per-step wire,
    dropped steps zero — exactly following the host-drawn decisions."""
    out = run_py("""
import json
import jax, jax.numpy as jnp
from repro.configs.base import (CommConfig, GatingDropoutConfig, ModelConfig,
                                MoEConfig, TrainConfig)
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
                                backend='sharded',
                                comm=CommConfig(
                                    substrate='hierarchical_compressed'),
                                gating_dropout=gd_cfg))
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
        _cfg(CommConfig(substrate="compressed"), k=1), n_layers=2,
        n_heads=2, n_kv_heads=2, remat=False, param_dtype="float32")
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
    assert sec["substrate"] == "compressed"
    assert sec["wire_bytes_total"] > 0
    assert sec["n_ticks"] == len(sched.tick_log)
    assert set(sec["wire_bytes_per_tick"]) == {50, 90, 99}
    # local routing: decode moves nothing; only prefills are priced
    gen_l = dataclasses.replace(gen, local_routing=True)
    sec_l = trace_comm_section(cfg, gen_l, sched, ep=8)
    assert sec_l["wire_bytes_total"] < sec["wire_bytes_total"]
