"""Execution-backend registry (DESIGN.md §6): oracle ≡ pallas ≡ sharded.

The acceptance bar for any new backend: same routing, same Gating Dropout
branches, same numbers (within dtype tolerance) as the pure-jnp oracle.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_py
from repro.configs.base import GatingDropoutConfig, ModelConfig, MoEConfig
from repro.core import (available_backends, get_backend, init_moe_params,
                        moe_apply, resolve_backend)
from repro.core.moe import ParallelContext
from repro.kernels.platform import default_interpret

KEY = jax.random.PRNGKey(0)


def _cfg(mode="gate_drop", k=1, E=4, dtype="float32", local_combine="prob"):
    return ModelConfig(
        d_model=32, d_ff=64, vocab=64, dtype=dtype,
        moe=MoEConfig(n_experts=E, top_k=k, d_ff_expert=64, jitter_eps=0.0,
                      gating_dropout=GatingDropoutConfig(
                          mode=mode, rate=0.3, local_combine=local_combine)))


def _apply(backend, cfg, p, x, decision):
    y, aux = get_backend(backend)(p, x, cfg, None, rng=None,
                                  decision=decision, is_training=True,
                                  token_ids=None)
    return np.asarray(y, np.float32), aux


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("mode", ["gate_drop", "gate_expert_drop"])
@pytest.mark.parametrize("decision", [False, True])
def test_backend_parity(k, mode, decision):
    """oracle ≡ pallas ≡ sharded on both the routed and dropped branches."""
    cfg = _cfg(mode=mode, k=k)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y_o, aux_o = _apply("oracle", cfg, p, x, decision)
    y_p, aux_p = _apply("pallas", cfg, p, x, decision)
    y_s, aux_s = _apply("sharded", cfg, p, x, decision)
    np.testing.assert_allclose(y_o, y_p, atol=2e-5)
    np.testing.assert_allclose(y_o, y_s, atol=2e-5)
    for a in (aux_p, aux_s):
        np.testing.assert_allclose(float(aux_o["dropped_frac"]),
                                   float(a["dropped_frac"]), atol=1e-6)


def test_backend_parity_bf16():
    """Same check at bf16 activations (kernel accumulates in f32)."""
    cfg = _cfg(k=2)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.bfloat16)
    y_o, _ = _apply("oracle", cfg, p, x, False)
    y_p, _ = _apply("pallas", cfg, p, x, False)
    np.testing.assert_allclose(y_o, y_p, atol=3e-2)


def test_backend_parity_local_combine_one():
    """Gate-Drop 'one' local combine weight matches across backends."""
    cfg = _cfg(k=2, local_combine="one")
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y_o, _ = _apply("oracle", cfg, p, x, True)
    y_p, _ = _apply("pallas", cfg, p, x, True)
    np.testing.assert_allclose(y_o, y_p, atol=2e-5)


def test_registry_contents_and_errors():
    assert {"oracle", "sharded", "pallas"} <= set(available_backends())
    with pytest.raises(KeyError, match="unknown MoE backend"):
        get_backend("nope")
    with pytest.raises(AssertionError):
        MoEConfig(backend="nope")


def test_resolve_auto():
    moe = MoEConfig()            # backend="auto"
    assert resolve_backend(moe, None) == "oracle"
    assert resolve_backend(moe, ParallelContext(mesh=None)) == "oracle"
    assert resolve_backend(dataclasses.replace(moe, backend="pallas"),
                           None) == "pallas"


def test_moe_apply_honours_config_backend():
    """MoEConfig.backend is the single switch: moe_apply(pallas) == direct
    pallas call, and != disabling would be caught by parity anyway."""
    cfg = _cfg(k=2)
    cfg_p = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, backend="pallas"))
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y_cfg, _ = moe_apply(p, x, cfg_p, decision=False)
    y_direct, _ = _apply("pallas", cfg, p, x, False)
    np.testing.assert_array_equal(np.asarray(y_cfg, np.float32), y_direct)


def test_interpret_autodetect_off_tpu():
    """The pallas backend no longer hard-codes interpret=True: the mode is
    derived from the platform (interpreter everywhere but TPU)."""
    assert default_interpret() == (jax.default_backend() != "tpu")


@pytest.mark.parametrize("decision", [False, True])
def test_backend_under_jit_and_grad(decision):
    """The pallas pipeline must be differentiable and jittable (it runs
    inside the train step) — on the routed AND the Gate-Drop local branch
    (the latter is the only path through the valid-masked dispatch VJP)."""
    cfg = _cfg(k=2)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))

    def loss(params, backend):
        y, _ = get_backend(backend)(params, x, cfg, None, rng=None,
                                    decision=decision, is_training=True,
                                    token_ids=None)
        return (y ** 2).sum()

    g_o = jax.jit(jax.grad(lambda p_: loss(p_, "oracle")))(p)
    g_p = jax.jit(jax.grad(lambda p_: loss(p_, "pallas")))(p)
    for a, b in zip(jax.tree.leaves(g_o), jax.tree.leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_force_interpret_not_stale_in_jit_cache():
    """interpret resolves BEFORE the jit boundary: a kernel first traced
    under the platform default must re-trace (not reuse the cached
    executable) when force_interpret changes the resolved mode."""
    from repro.kernels import force_interpret
    from repro.kernels.grouped_ffn import _gmm_jit, grouped_matmul
    x = jax.random.normal(KEY, (1, 8, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8))
    grouped_matmul(x, w)                      # traces platform default
    n0 = _gmm_jit._cache_size()
    with force_interpret(jax.default_backend() == "tpu"):
        try:
            grouped_matmul(x, w)              # opposite mode -> new trace
        except Exception:
            pass   # compiling off-TPU fails; reaching the compiler is enough
    assert _gmm_jit._cache_size() != n0


def test_pallas_backend_composes_with_mesh():
    """pallas + active mesh = sharded execution with the kernel pipeline:
    same all-to-alls and per-shard routing as `sharded`, oracle-equal."""
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, MoEConfig, GatingDropoutConfig
from repro.core import get_backend, init_moe_params, moe_oracle, ParallelContext
from repro.launch.mesh import make_mesh
cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, moe=MoEConfig(
    n_experts=8, top_k=2, d_ff_expert=64, jitter_eps=0.0,
    gating_dropout=GatingDropoutConfig(mode='gate_drop', rate=0.3)))
p = init_moe_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
ctx = ParallelContext(mesh=make_mesh((8,), ('data',)))
for dec in (False, True):
    y_ref, _ = moe_oracle(p, x, cfg, ep=8, decision=dec)
    y_pl, _ = jax.jit(lambda p_, x_: get_backend('pallas')(
        p_, x_, cfg, ctx, rng=None, decision=dec, is_training=True,
        token_ids=None))(p, x)
    d = float(jnp.abs(y_ref - y_pl).max())
    assert d < 2e-5, (dec, d)
print('OK')
""")
    assert "OK" in out


def _sharded_vs_oracle(jitter: float) -> str:
    return run_py(f"""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, MoEConfig, GatingDropoutConfig
from repro.core import get_backend, init_moe_params, moe_oracle, ParallelContext
from repro.launch.mesh import make_mesh
cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, moe=MoEConfig(
    n_experts=8, top_k=2, d_ff_expert=64, jitter_eps={jitter},
    gating_dropout=GatingDropoutConfig(mode='gate_drop', rate=0.3)))
p = init_moe_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
ctx = ParallelContext(mesh=make_mesh((8,), ('data',)))
for dec in (False, True):
    y_ref, _ = moe_oracle(p, x, cfg, ep=8, decision=dec)
    y_sh, _ = get_backend('sharded')(p, x, cfg, ctx, rng=None, decision=dec,
                                     is_training=True, token_ids=None)
    d = float(jnp.abs(y_ref - y_sh).max())
    assert d < 2e-5, (dec, d)
print('OK')
""")


def test_sharded_backend_multidevice_matches_oracle():
    """Registry-selected sharded backend on a real 8-device mesh equals the
    oracle with the matching virtual shard count."""
    assert "OK" in _sharded_vs_oracle(0.0)


def test_sharded_backend_without_rng_adds_no_jitter():
    """With no rng the router adds no jitter in the sharded backend either,
    as in the oracle, whatever ``jitter_eps`` says."""
    assert "OK" in _sharded_vs_oracle(0.01)
