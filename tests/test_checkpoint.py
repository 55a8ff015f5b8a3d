"""Checkpointing: save -> restore must be bitwise (bf16 leaves included),
and a restored train state must continue EXACTLY like the uninterrupted
run — same params, same Gating-Dropout consensus stream (DESIGN.md §2)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs.base import (GatingDropoutConfig, ModelConfig, MoEConfig,
                                TrainConfig)
from repro.core.gating_dropout import drop_decision_host
from repro.data import LMTaskConfig, SyntheticLM
from repro.models import init_model
from repro.training import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _tiny_cfg(**kw):
    return ModelConfig(d_model=32, d_ff=64, vocab=64, n_layers=2, n_heads=2,
                       n_kv_heads=2, remat=False, dtype="float32",
                       param_dtype="float32", **kw)


def test_roundtrip_bitwise_with_bf16(tmp_path):
    """Mixed-dtype pytree (f32 / bf16 / int32 / nested dict+list) survives
    save->restore bit-for-bit. bf16 leaves go through the uint16 bit-pattern
    path in checkpoint.py."""
    tree = {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7,
                   "b16": (jnp.arange(8, dtype=jnp.float32) / 3
                           ).astype(jnp.bfloat16)},
        "opt": [jnp.ones((2, 2), jnp.float32) * np.pi,
                jnp.full((3,), -1.5, jnp.bfloat16)],
        "step": jnp.asarray(17, jnp.int32),
    }
    save_checkpoint(str(tmp_path), 17, tree)
    assert latest_step(str(tmp_path)) == 17
    restored, meta = restore_checkpoint(str(tmp_path), tree)
    assert meta["step"] == 17
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        # bitwise: compare the raw bit patterns, not values-within-tolerance
        av = np.asarray(a.view(jnp.uint16) if a.dtype == jnp.bfloat16 else a)
        bv = np.asarray(b.view(jnp.uint16) if b.dtype == jnp.bfloat16 else b)
        np.testing.assert_array_equal(av, bv)


def test_roundtrip_model_train_state(tmp_path):
    cfg = _tiny_cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2)
    state = init_train_state(init_model(KEY, cfg), tc)
    save_checkpoint(str(tmp_path), 0, state, {"arch": cfg.arch_id})
    restored, meta = restore_checkpoint(str(tmp_path), state)
    assert meta["arch"] == cfg.arch_id
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_continues_identically(tmp_path):
    """4 straight steps == 2 steps -> checkpoint -> restore -> 2 more, with
    the batch stream and the (seed, step) consensus PRNG keyed by the
    ABSOLUTE step — the exact contract behind launch/train.py --resume."""
    cfg = _tiny_cfg(moe=MoEConfig(
        n_experts=4, top_k=1, d_ff_expert=64, jitter_eps=0.0,
        gating_dropout=GatingDropoutConfig(mode="gate_drop", rate=0.5)))
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3)
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    gd = cfg.moe.gating_dropout
    step = make_train_step(cfg, tc)   # jitted: one executable per decision

    def batch(i):
        return {k: jnp.asarray(v) for k, v in task.sample_batch(i, 4).items()}

    def run(state, lo, hi):
        for i in range(lo, hi):
            state, _ = step(state, batch(i),
                            drop_decision_host(gd, tc.seed, i))
        return state

    s_straight = run(init_train_state(init_model(KEY, cfg), tc), 0, 4)

    s = run(init_train_state(init_model(KEY, cfg), tc), 0, 2)
    save_checkpoint(str(tmp_path), 2, s)
    template = init_train_state(init_model(KEY, cfg), tc)
    s_resumed, meta = restore_checkpoint(str(tmp_path), template)
    assert meta["step"] == 2
    assert int(s_resumed["step"]) == 2       # in-graph PRNG fold continues
    s_resumed = run(s_resumed, 2, 4)

    # the dropped/routed pattern over steps 0..3 is nontrivial at rate 0.5
    assert any(drop_decision_host(gd, tc.seed, i) for i in range(8))
    for a, b in zip(jax.tree.leaves(s_straight), jax.tree.leaves(s_resumed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("strategy", ["traced_cond", "host_cond"])
def test_trainer_resume_continues_identically(tmp_path, strategy):
    """The Trainer's --resume contract (DESIGN.md §8): 6 straight scan-fused
    steps == 4 steps -> checkpoint -> restore -> 2 more, BITWISE. Both the
    data stream (batch_fn keyed by absolute step) and the Gating-Dropout
    consensus stream ((seed, step) fold) must continue where the
    checkpointed run left off — even though the resumed run chunks the
    remaining steps differently."""
    from repro.training import Trainer
    cfg = _tiny_cfg(moe=MoEConfig(
        n_experts=4, top_k=1, d_ff_expert=64, jitter_eps=0.0,
        gating_dropout=GatingDropoutConfig(mode="gate_drop", rate=0.5)))
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
    batch_fn = lambda i: task.sample_batch(i, 4)   # noqa: E731

    def make(steps, ckpt=None):
        tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3, steps=steps)
        return Trainer(cfg, tc, batch_fn, chunk=3, strategy=strategy,
                       ckpt_dir=ckpt, log=None)

    s_straight, _ = make(6).run()

    make(4, ckpt=str(tmp_path)).run()              # saves at step 4
    tr = make(6, ckpt=str(tmp_path))
    assert tr.restore() == 4
    assert int(tr.state["step"]) == 4
    s_resumed, _ = tr.run()

    gd = cfg.moe.gating_dropout
    assert any(drop_decision_host(gd, 3, i) for i in range(6))
    for a, b in zip(jax.tree.leaves(s_straight), jax.tree.leaves(s_resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_restore_keeps_experts_split_on_mesh(tmp_path):
    """On a data=4 mesh the Trainer holds each expert weight and its Adam
    moments as one quarter of the experts per device, and a restored run
    puts every leaf back on that layout (never the whole state on one
    device), with the checkpointed values."""
    from conftest import run_py
    out = run_py(f"""
import jax, numpy as np
from repro.configs.base import (GatingDropoutConfig, ModelConfig, MoEConfig,
                                TrainConfig)
from repro.core.moe import ParallelContext
from repro.data import LMTaskConfig, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.training import Trainer
ctx = ParallelContext(mesh=make_mesh((4,), ('data',)))
cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, n_layers=2, n_heads=2,
                  n_kv_heads=2, remat=False, dtype='float32',
                  param_dtype='float32',
                  moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=64,
                                backend='sharded', jitter_eps=0.0,
                                gating_dropout=GatingDropoutConfig(
                                    mode='gate_drop', rate=0.5,
                                    strategy='host_cond')))
task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
make = lambda steps: Trainer(
    cfg, TrainConfig(lr=1e-3, warmup_steps=2, seed=0, steps=steps),
    lambda i: task.sample_batch(i, 8), ctx=ctx, chunk=2,
    ckpt_dir={str(tmp_path)!r}, log=None)

def experts(state):
    return {{jax.tree_util.keystr(p): a for p, a in
            jax.tree_util.tree_leaves_with_path(state) if 'experts' in
            jax.tree_util.keystr(p)}}

saved, _ = make(2).run()
tr = make(4)
assert tr.restore() == 2
got = experts(tr.state)
assert len(got) == 9, sorted(got)      # w_in/w_out/w_gate x params, m, v
for name, a in experts(saved).items():
    b = got[name]
    assert b.sharding.is_equivalent_to(a.sharding, a.ndim), (
        name, b.sharding, a.sharding)
    shards = {{s.device.id: s.data.shape for s in b.addressable_shards}}
    assert len(shards) == 4, (name, shards)
    assert all(s[-3] * 4 == b.shape[-3] for s in shards.values()), (
        name, b.shape, shards)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
tr.run()
print('restored', len(got))
""", n_devices=4)
    assert out.split()[-1] == "9"
