"""Gating Dropout semantics: consensus, rates, branch equivalence, and the
paper's core claim — the dropped executable contains NO all-to-all."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_py
from repro.configs.base import (GatingDropoutConfig, ModelConfig, MoEConfig)
from repro.core import (drop_decision, drop_decision_host, init_moe_params,
                        moe_oracle)
from repro.core.gating_dropout import (expected_alltoall_fraction,
                                       expected_expert_flop_fraction)


def test_decision_deterministic_consensus():
    """Every 'host' computing the decision from (seed, step) agrees — the
    TPU-native replacement for the paper's coordinator broadcast."""
    gd = GatingDropoutConfig(mode="gate_drop", rate=0.3)
    for step in range(50):
        a = bool(drop_decision(gd, 7, step))
        b = drop_decision_host(gd, 7, step)
        assert a == b


def test_decision_rate_matches_p():
    gd = GatingDropoutConfig(mode="gate_drop", rate=0.3)
    draws = [drop_decision_host(gd, 0, s) for s in range(2000)]
    assert abs(np.mean(draws) - 0.3) < 0.04


def test_batched_decisions_equal_per_step():
    """The one-dispatch batched draw (Trainer host_cond path) is bitwise
    the per-step draws, for any span and seed; disabled configs give all
    False without dispatching."""
    from repro.core.gating_dropout import drop_decisions_host
    gd = GatingDropoutConfig(mode="gate_drop", rate=0.3)
    for seed, lo, hi in [(0, 0, 64), (7, 5, 6), (3, 100, 131)]:
        batched = drop_decisions_host(gd, seed, lo, hi)
        per_step = [drop_decision_host(gd, seed, i) for i in range(lo, hi)]
        np.testing.assert_array_equal(batched, per_step)
    off = GatingDropoutConfig(mode="off", rate=0.0)
    assert not drop_decisions_host(off, 0, 0, 16).any()
    assert drop_decisions_host(gd, 0, 4, 4).shape == (0,)


def test_decision_off_at_inference():
    gd = GatingDropoutConfig(mode="gate_drop", rate=1.0)
    assert not bool(drop_decision(gd, 0, 5, is_training=False))
    assert not drop_decision_host(gd, 0, 5, is_training=False)


def test_expected_fractions():
    gd = GatingDropoutConfig(mode="gate_drop", rate=0.3)
    assert expected_alltoall_fraction(gd) == pytest.approx(0.7)
    assert expected_expert_flop_fraction(gd) == 1.0
    ged = GatingDropoutConfig(mode="gate_expert_drop", rate=0.2)
    assert expected_expert_flop_fraction(ged) == pytest.approx(0.8)


def _cfg(mode="gate_drop", rate=0.3, k=1, E=8):
    return ModelConfig(d_model=32, d_ff=64, vocab=64, moe=MoEConfig(
        n_experts=E, top_k=k, d_ff_expert=64, jitter_eps=0.0,
        gating_dropout=GatingDropoutConfig(mode=mode, rate=rate)))


def test_rate_zero_equals_baseline():
    cfg0 = _cfg(rate=0.0)
    p = init_moe_params(jax.random.PRNGKey(0), cfg0)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    y0, _ = moe_oracle(p, x, cfg0, decision=None)
    gd = cfg0.moe.gating_dropout
    for step in range(10):
        d = drop_decision_host(gd, 0, step)
        assert not d
        y, _ = moe_oracle(p, x, cfg0, decision=d)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y))


def test_traced_equals_static_branches():
    cfg = _cfg()
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    for d in (False, True):
        y_static, _ = moe_oracle(p, x, cfg, ep=4, decision=d)
        y_traced, _ = moe_oracle(p, x, cfg, ep=4, decision=jnp.asarray(d))
        np.testing.assert_allclose(np.asarray(y_static),
                                   np.asarray(y_traced), atol=1e-6)


def test_gate_expert_drop_skips_layer():
    cfg = _cfg(mode="gate_expert_drop", rate=0.2)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    y, aux = moe_oracle(p, x, cfg, ep=4, decision=True)
    assert np.abs(np.asarray(y)).max() == 0.0      # residual passthrough
    assert float(aux["balance"]) == 0.0


def test_expert_load_counts_all_k_slots():
    """Routed steps: load sums to exactly top_k (all k slots counted).
    Gate-Drop local steps report the same semantics restricted to slots
    that survived locally — sum <= top_k, equal when nothing drops, and
    ALWAYS > 1 for top_k=2 with ample capacity (the old slot-0-only
    counting capped the local sum at 1 and ignored capacity drops)."""
    cfg = _cfg(k=2, E=8)
    cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0, eval_capacity_factor=8.0))
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    _, aux_routed = moe_oracle(p, x, cfg, ep=4, decision=False)
    assert float(aux_routed["load"].sum()) == pytest.approx(2.0, abs=1e-5)
    _, aux_local = moe_oracle(p, x, cfg, ep=4, decision=True)
    s = float(aux_local["load"].sum())
    # ample capacity + 2 local experts per shard: both slots are locally
    # satisfiable, so parity with the routed-step sum holds
    assert s == pytest.approx(2.0, abs=1e-5)
    assert float(aux_local["dropped_frac"]) == pytest.approx(0.0, abs=1e-5)
    # with train capacity 1.0, drops appear and the sum is short exactly
    # by the dropped fraction of the k slots
    cfg_tight = ModelConfig(d_model=32, d_ff=64, vocab=64,
                            moe=dataclasses.replace(cfg.moe,
                                                    capacity_factor=1.0))
    _, aux_tight = moe_oracle(p, x, cfg_tight, ep=4, decision=True,
                              is_training=True)
    st = float(aux_tight["load"].sum())
    df = float(aux_tight["dropped_frac"])
    assert st == pytest.approx(2.0 * (1.0 - df), abs=1e-5)


def test_local_path_uses_only_local_experts():
    """Zero out the non-local experts: output must be unchanged on the
    dropped path (proves no token left its shard)."""
    cfg = _cfg(E=8)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    ep = 4
    y, _ = moe_oracle(p, x, cfg, ep=ep, decision=True)
    # shard s uses experts [2s, 2s+2); zeroing *other* shards' experts for
    # shard 0's tokens changes nothing
    import jax.tree_util as jtu
    p2 = jax.tree.map(lambda a: a.copy(), p)
    p2["experts"] = jax.tree.map(lambda a: a.at[2:].set(0.0), p["experts"])
    y2, _ = moe_oracle(p2, x, cfg, ep=ep, decision=True)
    T = 4 * 16 // ep   # tokens per virtual shard (flattened order)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 32)[:T],
                               np.asarray(y2).reshape(-1, 32)[:T], atol=1e-6)


def test_dropped_executable_has_no_alltoall():
    """THE paper claim, structurally: host_cond dropped executable contains
    zero all-to-all ops; the routed one contains them."""
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, MoEConfig, GatingDropoutConfig
from repro.core import init_moe_params, moe_sharded, ParallelContext
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
ctx = ParallelContext(mesh=mesh)
cfg = ModelConfig(d_model=64, d_ff=128, vocab=100, moe=MoEConfig(
    n_experts=8, top_k=1, d_ff_expert=128,
    gating_dropout=GatingDropoutConfig(mode='gate_drop', rate=0.3,
                                       strategy='host_cond')))
p = init_moe_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
for dec, name in [(False, 'routed'), (True, 'dropped')]:
    txt = jax.jit(lambda p, x: moe_sharded(
        p, x, cfg, ctx, rng=jax.random.PRNGKey(2), decision=dec)
    ).lower(p, x).compile().as_text()
    print(name, txt.count('all-to-all'))
""")
    lines = dict(l.split() for l in out.strip().splitlines())
    assert int(lines["routed"]) > 0
    assert int(lines["dropped"]) == 0


def test_sharded_matches_oracle_all_branches():
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, MoEConfig, GatingDropoutConfig
from repro.core import init_moe_params, moe_oracle, moe_sharded, ParallelContext
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
ctx = ParallelContext(mesh=mesh)
cfg = ModelConfig(d_model=64, d_ff=128, vocab=100, moe=MoEConfig(
    n_experts=8, top_k=2, d_ff_expert=128, capacity_factor=1.5,
    gating_dropout=GatingDropoutConfig(mode='gate_drop', rate=0.3)))
key = jax.random.PRNGKey(0)
p = init_moe_params(key, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
for dec in (None, True, False):
    y_ref, aux_ref = moe_oracle(p, x, cfg, ep=4, rng=key, decision=dec)
    y_sh, aux_sh = jax.jit(lambda p, x: moe_sharded(
        p, x, cfg, ctx, rng=key, decision=dec))(p, x)
    d = float(jnp.abs(y_ref - y_sh).max())
    db = abs(float(aux_ref['balance']) - float(aux_sh['balance']))
    print('diff', d, db)
    assert d < 2e-5 and db < 1e-5, (dec, d, db)
print('OK')
""")
    assert "OK" in out


def _vmapped_ffn_local(params, x, cfg, ep, rng, token_ids, token_valid):
    """The oracle's Gate-Drop local branch as it was before the expert FFN
    left the virtual-shard vmap, kept verbatim (the shard body inlined):
    each virtual shard slices its experts and runs its own FFN."""
    from repro.core import router as R
    from repro.core.moe import (_expert_ffn, _local_adjust, _local_aux,
                                _shard_rng)
    moe = cfg.moe
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    xs = xf.reshape(ep, T // ep, shape[-1])
    tok = None if token_ids is None else token_ids.reshape(ep, T // ep)
    tv = None if token_valid is None else token_valid.reshape(ep, T // ep)
    wr = params["router"]["w"]
    experts = params["experts"]
    E = moe.n_experts
    e_loc = E // ep

    def local_shard(wr, experts_loc, xf, my_shard, token_ids, token_valid):
        T = xf.shape[0]
        lo = my_shard * e_loc
        rr = R.route(wr, xf, moe, rng=_shard_rng(rng, my_shard),
                     is_training=True, token_ids=token_ids,
                     expert_lo=lo, n_local=e_loc)
        rr, valid = _local_adjust(rr, moe, lo, e_loc)
        if token_valid is not None:
            valid = valid & token_valid.reshape(-1, 1)
        rr_local = rr._replace(topk_idx=rr.topk_idx - lo)
        cap = min(R.capacity(T, e_loc, moe.top_k, moe.capacity_factor), T)
        info = R.dispatch_info(rr_local, e_loc, cap, valid=valid)
        buf = R.dispatch(xf, info, e_loc, cap)
        out = _expert_ffn(experts_loc, buf, cfg, None)
        y = R.combine(out, info)
        return y, _local_aux(rr, info, moe, T)

    def shard_local(my, xl, tl, tvl):
        ex_loc = jax.tree.map(lambda w: jax.lax.dynamic_slice_in_dim(
            w, my * e_loc, e_loc, axis=0), experts)
        return local_shard(wr, ex_loc, xl, my, tl, tvl)

    ys, auxs = jax.vmap(
        shard_local, in_axes=(0, 0, 0 if tok is not None else None,
                              0 if tv is not None else None))(
        jnp.arange(ep), xs, tok, tv)
    return (ys.reshape(T, -1).reshape(shape),
            jax.tree.map(lambda a: a.mean(0), auxs))


@pytest.mark.parametrize("tokens", ["absent", "given"])
@pytest.mark.parametrize("local_combine", ["prob", "one"])
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("ep", [1, 2, 4])
def test_oracle_local_ffn_outside_vmap_is_bitwise_the_vmapped_one(
        ep, k, local_combine, tokens):
    """The oracle's Gate-Drop branch runs one expert FFN over all experts
    outside the virtual-shard vmap; its outputs, every aux entry and the
    parameter and input gradients are bitwise those of the per-shard FFN
    it replaced."""
    cfg = ModelConfig(d_model=64, d_ff=128, vocab=64, moe=MoEConfig(
        n_experts=8, top_k=k, d_ff_expert=128,
        gating_dropout=GatingDropoutConfig(mode="gate_drop", rate=0.3,
                                           local_combine=local_combine)))
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    rng = jax.random.PRNGKey(3)
    tok = valid = None
    if tokens == "given":
        tok = jax.random.randint(jax.random.PRNGKey(4), x.shape[:2], 0, 64)
        valid = jax.random.bernoulli(jax.random.PRNGKey(5), 0.8, x.shape[:2])

    def run(layer):
        def loss(p, x):
            y, aux = layer(p, x)
            return (y * ct).sum() + aux["router_entropy"], (y, aux)
        return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(p, x)

    new = run(lambda p, x: moe_oracle(p, x, cfg, ep=ep, rng=rng,
                                      decision=True, token_ids=tok,
                                      token_valid=valid))
    old = run(lambda p, x: _vmapped_ffn_local(p, x, cfg, ep, rng, tok, valid))
    (g_new, (y_new, aux_new)), (g_old, (y_old, aux_old)) = new, old
    np.testing.assert_array_equal(np.asarray(y_new), np.asarray(y_old))
    assert sorted(aux_new) == sorted(aux_old)
    for name in aux_old:
        np.testing.assert_array_equal(np.asarray(aux_new[name]),
                                      np.asarray(aux_old[name]), err_msg=name)
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
