"""Scan-fused Trainer (DESIGN.md §8): a K-step fused chunk must be
BITWISE-identical to K legacy per-step calls (params + opt state, both
strategies, gating dropout on); vectorized batch synthesis must equal the
loop reference; the prefetcher must preserve order and surface errors;
the host_cond dropped run executable must contain zero all-to-alls."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_py
from repro.configs.base import (EncDecConfig, GatingDropoutConfig,
                                ModelConfig, MoEConfig, TrainConfig)
from repro.core.gating_dropout import drop_decision_host, drop_decisions_host
from repro.data import (LMTaskConfig, MTTaskConfig, MultilingualMT,
                        Prefetcher, SyntheticLM, stack_batches)
from repro.models import init_model
from repro.training import (Trainer, init_train_state, make_chunk_step,
                            make_train_step, same_decision_runs)

KEY = jax.random.PRNGKey(0)


def _cfg(rate=0.5, mode="gate_drop"):
    return ModelConfig(d_model=32, d_ff=64, vocab=64, n_layers=2, n_heads=2,
                       n_kv_heads=2, remat=False, dtype="float32",
                       param_dtype="float32",
                       moe=MoEConfig(n_experts=4, top_k=1, d_ff_expert=64,
                                     jitter_eps=0.0,
                                     gating_dropout=GatingDropoutConfig(
                                         mode=mode, rate=rate)))


def _task_and_batch_fn(cfg, batch=4, seq=16):
    task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=seq))
    return task, lambda i: task.sample_batch(i, batch)


def _legacy(cfg, tc, batch_fn, steps, strategy):
    """The seed-era loop: one jitted dispatch per step; host_cond draws the
    bit on the host (static), traced_cond computes it in-graph (None)."""
    gd = cfg.moe.gating_dropout
    step = make_train_step(cfg, tc)
    s = init_train_state(init_model(jax.random.PRNGKey(tc.seed), cfg), tc)
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in batch_fn(i).items()}
        dec = (drop_decision_host(gd, tc.seed, i)
               if strategy == "host_cond" else None)
        s, _ = step(s, b, dec)
    return s


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# fused chunk == legacy per-step, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["traced_cond", "host_cond"])
def test_fused_chunk_bitwise_equals_per_step(strategy):
    """One 4-step scan-fused chunk == 4 legacy per-step calls, bit for bit
    (params AND opt state), with gating dropout drawing a nontrivial
    decision pattern at rate 0.5."""
    cfg = _cfg()
    K = 4
    gd = cfg.moe.gating_dropout
    # the first seed whose K-step window holds both consensus bits
    seed = next(s for s in range(64)
                if len(set(drop_decisions_host(gd, s, 0, K).tolist())) == 2)
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=seed)
    _, batch_fn = _task_and_batch_fn(cfg)
    decs = [drop_decision_host(gd, tc.seed, i) for i in range(K)]
    assert len(set(decs)) == 2, f"want both decisions in {decs}"

    chunk = make_chunk_step(cfg, tc)
    s = init_train_state(init_model(jax.random.PRNGKey(tc.seed), cfg), tc)
    if strategy == "traced_cond":
        batches = {k: jnp.asarray(v)
                   for k, v in stack_batches(batch_fn, 0, K).items()}
        s, ms = chunk(s, batches, None)
        assert ms["loss"].shape == (K,)       # on-device per-step metrics
    else:
        for lo, hi, dec in same_decision_runs(gd, tc.seed, 0, K):
            sub = {k: jnp.asarray(v)
                   for k, v in stack_batches(batch_fn, lo, hi).items()}
            s, ms = chunk(s, sub, dec)
            assert ms["loss"].shape == (hi - lo,)
    _assert_bitwise(s, _legacy(cfg, tc, batch_fn, K, strategy))


@pytest.mark.parametrize("strategy", ["traced_cond", "host_cond"])
def test_trainer_end_to_end_bitwise(strategy):
    """Trainer.run() (schedule + prefetch thread + run splitting + metric
    fetch at boundaries) over 7 steps with an uneven chunk size == the
    legacy loop, bit for bit."""
    cfg = _cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3, steps=7)
    _, batch_fn = _task_and_batch_fn(cfg)
    tr = Trainer(cfg, tc, batch_fn, chunk=3, strategy=strategy, log=None)
    state, history = tr.run()
    _assert_bitwise(state, _legacy(cfg, tc, batch_fn, tc.steps, strategy))
    assert history and history[-1]["step"] == tc.steps - 1
    for rec in history:
        for k in ("loss", "acc", "lr", "tok_s", "time_s"):
            assert np.isfinite(rec[k]), (rec, k)


def _legacy_states(cfg, tc, batch_fn, steps, strategy):
    """Every state of the legacy loop: [state after step i for i < steps],
    each pulled to host, with step i's loss."""
    gd = cfg.moe.gating_dropout
    step = make_train_step(cfg, tc)
    s = init_train_state(init_model(jax.random.PRNGKey(tc.seed), cfg), tc)
    out = []
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in batch_fn(i).items()}
        dec = (drop_decision_host(gd, tc.seed, i)
               if strategy == "host_cond" else None)
        s, m = step(s, b, dec)
        out.append((jax.device_get(s), float(m["loss"])))
    return out


def test_host_cond_run_keeps_one_chunk_in_flight(monkeypatch):
    """A host_cond run over four chunks: chunk i+1 is enqueued before chunk
    i's metrics are fetched, the last chunk is fetched before run()
    returns, and the consensus bits are drawn once per run(), before the
    first enqueue."""
    import repro.core.gating_dropout as G
    cfg = _cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3, steps=8)
    _, batch_fn = _task_and_batch_fn(cfg)
    tr = Trainer(cfg, tc, batch_fn, chunk=2, strategy="host_cond", log=None)
    log = []
    inner, draw, get = tr.chunk_fn, G._decisions_batch, jax.device_get
    at = [0]

    def chunk_fn(state, batches, decision):
        k = jax.tree.leaves(batches)[0].shape[0]
        log.append(("enqueue", at[0], at[0] + k))
        at[0] += k
        return inner(state, batches, decision)

    def decisions_batch(*a):
        log.append(("draw",))
        return draw(*a)

    def device_get(x):
        if isinstance(x, list) and x and isinstance(x[0], dict):
            log.append(("fetch", sum(len(p["loss"]) for p in x)))
        return get(x)

    tr.chunk_fn = chunk_fn
    monkeypatch.setattr(G, "_decisions_batch", decisions_batch)
    monkeypatch.setattr(jax, "device_get", device_get)
    tr.run()
    runs = [(a, b) for kind, *ab in log if kind == "enqueue"
            for a, b in [ab]]
    assert runs[0][0] == 0 and runs[-1][1] == 8
    assert log[0] == ("draw",) and log.count(("draw",)) == 1
    # chunk c's position in the log: its last enqueue and its fetch
    spans = tr.schedule()
    assert spans == [(0, 2), (2, 4), (4, 6), (6, 8)]
    fetches = [i for i, ev in enumerate(log) if ev[0] == "fetch"]
    assert [log[i][1] for i in fetches] == [2, 2, 2, 2]

    def last_enqueue(span):
        return max(i for i, ev in enumerate(log)
                   if ev[0] == "enqueue" and span[0] <= ev[1] < span[1])

    for c in range(len(spans) - 1):
        assert last_enqueue(spans[c]) < last_enqueue(spans[c + 1]) \
            < fetches[c], log
    assert fetches[-1] == len(log) - 1
    # a second run(): one more draw, again before its first enqueue
    n = len(log)
    tr.start_step, tr.tc = 8, dataclasses.replace(tc, steps=12)
    tr.run()
    assert log[n] == ("draw",) and log.count(("draw",)) == 2


@pytest.mark.parametrize("strategy", ["traced_cond", "host_cond"])
def test_eval_fn_sees_the_legacy_state_mid_run(strategy):
    """With eval steps inside the run and chunks in flight between them,
    eval_fn(state, i) receives bitwise the legacy loop's state after step
    i, and every recorded loss is the legacy step's."""
    cfg = _cfg()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3, steps=10)
    _, batch_fn = _task_and_batch_fn(cfg)
    seen = {}

    def eval_fn(state, i):
        seen[i] = jax.device_get(state)
        return {"evaluated": 1.0}

    tr = Trainer(cfg, tc, batch_fn, chunk=2, strategy=strategy,
                 eval_every=4, eval_fn=eval_fn, log=None, log_every=1)
    # chunks (1, 3) and (5, 7) are in flight when the next is enqueued
    assert tr.schedule() == [(0, 1), (1, 3), (3, 5), (5, 7), (7, 9),
                             (9, 10)]
    _, history = tr.run()
    legacy = _legacy_states(cfg, tc, batch_fn, tc.steps, strategy)
    assert sorted(seen) == [0, 4, 8, 9]
    for i, state in seen.items():
        _assert_bitwise(state, legacy[i][0])
    assert [r["step"] for r in history] == list(range(tc.steps))
    for r in history:
        assert r["loss"] == legacy[r["step"]][1], r
        assert ("evaluated" in r) == (r["step"] in seen)


@pytest.mark.parametrize("strategy", ["traced_cond", "host_cond"])
def test_checkpoint_and_resume_bitwise_with_chunks_in_flight(tmp_path,
                                                             strategy):
    """The checkpoint written at the end of a pipelined run() holds the
    legacy loop's state after its last step, and a run resumed from it
    ends on the legacy state too."""
    from repro.checkpoint import restore_checkpoint
    cfg = _cfg()
    _, batch_fn = _task_and_batch_fn(cfg)

    def make(steps):
        tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3, steps=steps)
        return Trainer(cfg, tc, batch_fn, chunk=2, strategy=strategy,
                       ckpt_dir=str(tmp_path), log=None)

    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=3, steps=9)
    legacy = _legacy_states(cfg, tc, batch_fn, tc.steps, strategy)
    first = make(5)
    state, _ = first.run()                  # chunks (0,2) (2,4) (4,5)
    _assert_bitwise(state, legacy[4][0])
    saved, meta = restore_checkpoint(str(tmp_path), first.state)
    assert int(meta["step"]) == 5
    _assert_bitwise(saved, legacy[4][0])
    tr = make(9)
    assert tr.restore() == 5
    state, history = tr.run()
    _assert_bitwise(state, legacy[8][0])
    assert history[-1]["step"] == 8


def test_trainer_counts_encoder_tokens():
    """tok/s accounting: MT batches consume enc_tokens + tokens; LM only
    tokens (the seed launcher counted decoder tokens only — ~2x under
    on the paper's main task)."""
    cfg = dataclasses.replace(_cfg(), family="encdec",
                              encdec=EncDecConfig(n_encoder_layers=1,
                                                  encoder_seq=8))
    tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=0, steps=2)
    task = MultilingualMT(MTTaskConfig(vocab=cfg.vocab, n_langs=4, max_len=8))
    fn = lambda i: {k: v for k, v in task.sample_batch(i, 2).items()  # noqa: E731
                    if k != "lang"}
    tr = Trainer(cfg, tc, fn, chunk=2, log=None, log_every=1)
    _, hist = tr.run()
    b = fn(0)
    per_step = b["tokens"].size + b["enc_tokens"].size
    assert per_step == 2 * b["tokens"].size
    # tok_s * time_s at the final record == all tokens consumed
    approx = hist[-1]["tok_s"] * hist[-1]["time_s"]
    np.testing.assert_allclose(approx, tc.steps * per_step, rtol=1e-3)


def test_schedule_aligns_eval_steps_to_chunk_ends():
    cfg = _cfg()
    tc = TrainConfig(steps=10, seed=0)
    _, batch_fn = _task_and_batch_fn(cfg)
    tr = Trainer(cfg, tc, batch_fn, chunk=4, eval_every=3,
                 eval_fn=lambda s, i: {}, log=None)
    spans = tr.schedule()
    assert spans[0] == (0, 1)                       # eval at step 0
    assert [e for _, e in spans] == sorted({e for _, e in spans})
    assert all(e - s <= 4 for s, e in spans)
    # every eval step i is the LAST step of its chunk (end == i + 1)
    ends = {e for _, e in spans}
    for i in (0, 3, 6, 9):
        assert i + 1 in ends, (i, spans)
    # contiguous cover of [0, steps)
    assert spans[0][0] == 0 and spans[-1][1] == tc.steps
    assert all(spans[i][1] == spans[i + 1][0] for i in range(len(spans) - 1))


def test_same_decision_runs_cover_and_are_maximal():
    gd = GatingDropoutConfig(mode="gate_drop", rate=0.5)
    runs = same_decision_runs(gd, 3, 0, 32)
    assert runs[0][0] == 0 and runs[-1][1] == 32
    assert all(runs[i][1] == runs[i + 1][0] for i in range(len(runs) - 1))
    assert all(runs[i][2] != runs[i + 1][2] for i in range(len(runs) - 1))
    for lo, hi, dec in runs:
        assert all(drop_decision_host(gd, 3, i) == dec for i in range(lo, hi))
    assert same_decision_runs(None, 0, 5, 9) == [(5, 9, False)]


def test_dropped_chunk_executable_has_no_alltoall():
    """The tentpole's structural claim survives fusion: the host_cond
    dropped RUN executable (scan over K steps, decision baked False->True
    static) contains zero all-to-all ops; the routed one contains them."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import (GatingDropoutConfig, ModelConfig, MoEConfig,
                                TrainConfig)
from repro.core.moe import ParallelContext
from repro.data import LMTaskConfig, SyntheticLM, stack_batches
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.training import init_train_state, make_chunk_step
mesh = make_mesh((4, 2), ('data', 'model'))
ctx = ParallelContext(mesh=mesh)
cfg = ModelConfig(d_model=64, d_ff=128, vocab=100, n_layers=1, n_heads=2,
                  n_kv_heads=2, remat=False, dtype='float32',
                  param_dtype='float32',
                  moe=MoEConfig(n_experts=8, top_k=1, d_ff_expert=128,
                                backend='sharded',
                                gating_dropout=GatingDropoutConfig(
                                    mode='gate_drop', rate=0.3,
                                    strategy='host_cond')))
tc = TrainConfig(lr=1e-3, warmup_steps=2, seed=0)
task = SyntheticLM(LMTaskConfig(vocab=cfg.vocab, seq_len=16))
batches = {k: jnp.asarray(v) for k, v in
           stack_batches(lambda i: task.sample_batch(i, 8), 0, 3).items()}
state = init_train_state(init_model(jax.random.PRNGKey(0), cfg), tc)
chunk = make_chunk_step(cfg, tc, ctx, jit=False)
for dec, name in [(False, 'routed'), (True, 'dropped')]:
    txt = jax.jit(chunk, static_argnums=(2,)).lower(
        state, batches, dec).compile().as_text()
    print(name, txt.count('all-to-all'))
""")
    lines = dict(l.split() for l in out.strip().splitlines())
    assert int(lines["routed"]) > 0
    assert int(lines["dropped"]) == 0


# ---------------------------------------------------------------------------
# vectorized batch synthesis == loop reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dae", [0.0, 0.3])
@pytest.mark.parametrize("lang", [None, 2])
def test_mt_vectorized_equals_loop(dae, lang):
    task = MultilingualMT(MTTaskConfig(vocab=512, n_langs=8, max_len=32,
                                       dae_frac=dae))
    for step in (0, 7, 123):
        a = task.sample_batch(step, 16, lang=lang)
        b = task.sample_batch_loop(step, 16, lang=lang)
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=f"{step}/{k}")


def test_mt_vectorized_equals_loop_truncation_and_shards():
    """max_len < src_len+2 exercises row truncation; shards must stay
    disjoint and loop-equal."""
    task = MultilingualMT(MTTaskConfig(vocab=512, n_langs=4, max_len=16,
                                       src_len=(8, 24), dae_frac=0.2))
    for step in range(4):
        for shard in (0, 1):
            a = task.sample_batch(step, 8, shard=shard, n_shards=2)
            b = task.sample_batch_loop(step, 8, shard=shard, n_shards=2)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
    s0 = task.sample_batch(0, 8, shard=0, n_shards=2)
    s1 = task.sample_batch(0, 8, shard=1, n_shards=2)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


def test_lm_vectorized_equals_loop():
    for kw in (dict(vocab=512, seq_len=128), dict(vocab=64, seq_len=16,
                                                  seed=5)):
        task = SyntheticLM(LMTaskConfig(**kw))
        for step in (0, 5, 99):
            a = task.sample_batch(step, 8)
            b = task.sample_batch_loop(step, 8)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=f"{kw}")


def test_mt_batch_shapes_and_special_tokens():
    """Invariants the model relies on: BOS at dec[0], one EOS per label
    row, mask covers exactly the target + EOS."""
    task = MultilingualMT(MTTaskConfig(vocab=512, n_langs=8, max_len=32))
    b = task.sample_batch(0, 16)
    assert b["tokens"].shape == (16, 32)
    assert (b["tokens"][:, 0] == 1).all()           # BOS
    assert ((b["labels"] == 2).sum(1) == 1).all()   # exactly one EOS
    eos_pos = (b["labels"] == 2).argmax(1)
    np.testing.assert_array_equal(b["loss_mask"].sum(1), eos_pos + 1)


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------

def test_prefetcher_preserves_order():
    out = list(Prefetcher(lambda x: x * x, range(20), depth=2))
    assert out == [x * x for x in range(20)]


def test_prefetcher_propagates_errors():
    def boom(x):
        if x == 3:
            raise ValueError("synthetic failure")
        return x

    it = Prefetcher(boom, range(10), depth=2)
    got = []
    with pytest.raises(ValueError, match="synthetic failure"):
        for v in it:
            got.append(v)
    assert got == [0, 1, 2]


def test_prefetcher_close_unblocks_worker():
    p = Prefetcher(lambda x: x, range(1000), depth=1)
    assert next(p) == 0
    p.close()
    p._thread.join(timeout=5)
    assert not p._thread.is_alive()


def test_stack_batches_leading_axis():
    task = SyntheticLM(LMTaskConfig(vocab=64, seq_len=8))
    st = stack_batches(lambda i: task.sample_batch(i, 4), 3, 7)
    assert st["tokens"].shape == (4, 4, 8)
    for j, i in enumerate(range(3, 7)):
        np.testing.assert_array_equal(st["tokens"][j],
                                      task.sample_batch(i, 4)["tokens"])
