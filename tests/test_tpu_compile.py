"""Every main-path Pallas kernel compiles for a described v5e chip.

The TPU compiler (Mosaic) is installed with jax and compiles for a chip
that is described, not attached, so these run on CPU hosts too. Interpret
mode cannot see what they check: Mosaic refuses blocks whose last two dims
are neither (8, 128)-aligned nor the array's own, and kernels that ask for
more VMEM than the chip gives. Widths are zcode-m3-base's (T=4096 tokens,
d=512, f=2048, E=128 top-1 experts, bf16 activations; decode at 8 KV heads
x 64). The topology is described inside a fixture only: the TPU library
allows one loader per process, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (combine, dispatch, flash_decode,
                           flash_decode_paged, fused_moe_ffn, grouped_matmul)

T, D, F, E = 4096, 512, 2048, 128
C = T // E                      # capacity at factor 1.0, top-1
B, H, KV, HD, S = 8, 8, 8, 64, 2048
PAGE, N_PAGES = 16, 256
BF16, F32, I32, BOOL = jnp.bfloat16, jnp.float32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _gmm_grad(x, w):
    loss = lambda a, b: grouped_matmul(a, b, interpret=False).astype(  # noqa: E731
        F32).sum()
    return jax.grad(loss, argnums=(0, 1))(x, w)


# kernel -> (fn, argument shapes and dtypes)
CASES = {
    "dispatch": (
        lambda x, st, sv: dispatch(x, st, sv, interpret=False),
        [((T, D), BF16), ((E * C,), I32), ((E * C,), BOOL)]),
    "combine": (
        lambda buf, ts, w, keep: combine(buf, ts, w, keep, interpret=False),
        [((E * C, D), BF16), ((T, 1), I32), ((T, 1), F32), ((T, 1), BOOL)]),
    "grouped_matmul": (
        lambda x, w: grouped_matmul(x, w, interpret=False),
        [((E, C, D), BF16), ((E, D, F), BF16)]),
    "grouped_matmul_grad": (
        _gmm_grad, [((E, C, D), BF16), ((E, D, F), BF16)]),
    "flash_decode": (
        lambda q, k, v, i: flash_decode(q, k, v, i, interpret=False),
        [((B, H, HD), BF16), ((B, S, KV, HD), BF16), ((B, S, KV, HD), BF16),
         ((B,), I32)]),
    "flash_decode_paged": (
        lambda q, k, v, bt, i: flash_decode_paged(q, k, v, bt, i,
                                                  interpret=False),
        [((B, H, HD), BF16), ((N_PAGES + 1, PAGE, KV, HD), BF16),
         ((N_PAGES + 1, PAGE, KV, HD), BF16), ((B, N_PAGES // B), I32),
         ((B,), I32)]),
    "megakernel": (
        lambda x, wi, wo, tw, keep, st, sv, ts: fused_moe_ffn(
            x, wi, None, wo, tw, keep, st, sv, ts, act="gelu",
            interpret=False),
        [((T, D), BF16), ((E, D, F), BF16), ((E, F, D), BF16), ((T, 1), F32),
         ((T, 1), BOOL), ((E * C,), I32), ((E * C,), BOOL), ((T, 1), I32)]),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip, no_compile_cache):
    fn, specs = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt, f"{kernel}: no Mosaic kernel in HLO"


def test_encdec_gate_drop_chunk_has_no_alltoall_on_v5e_mesh(topo,
                                                            no_compile_cache):
    """The paper's claim under the TPU partitioner, on a described 2x2
    mesh: the routed host_cond chunk of an encoder-decoder MoE exchanges
    tokens with all-to-alls and the Gate-Drop chunk has none. (The
    partitioner re-shards a merged embedding-gradient scatter with
    all-to-alls that the CPU partitioner never emits.)"""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.moe import ParallelContext
    from repro.models import init_model
    from repro.training import (init_train_state, make_chunk_step,
                                train_state_sharding)

    base = get_config("zcode-m3-base")
    cfg = dataclasses.replace(
        base, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256, vocab=1024,
        n_layers=2,
        encdec=dataclasses.replace(base.encdec, n_encoder_layers=2),
        moe=dataclasses.replace(base.moe, n_experts=8))
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    ctx = ParallelContext(mesh=mesh)
    tc = TrainConfig(steps=2)
    shape = jax.eval_shape(
        lambda: init_train_state(init_model(jax.random.PRNGKey(0), cfg), tc))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, train_state_sharding(cfg, tc, ctx))
    rows = NamedSharding(mesh, P(None, "data"))
    batch = {k: jax.ShapeDtypeStruct((1, 8, 32), F32 if k == "loss_mask"
                                     else I32, sharding=rows)
             for k in ("enc_tokens", "tokens", "labels", "loss_mask")}
    chunk = make_chunk_step(cfg, tc, ctx)
    a2a = {dec: chunk.lower(state, batch, dec).compile().as_text().count(
        "all-to-all(") for dec in (False, True)}
    assert a2a[False] > 0 and a2a[True] == 0, a2a


def test_one_chip_gate_drop_chunk_copies_no_expert_leaf(one_chip,
                                                       no_compile_cache):
    """On one described v5e chip neither host_cond step program copies an
    expert leaf of the train state. The oracle's Gate-Drop branch once ran
    its expert FFN under the virtual-shard vmap: the weight gradient came
    out transposed, Adam's update followed it, and every expert parameter
    and moment was copied into that layout and back, 24 copies a dropped
    step."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.models import init_model
    from repro.training import init_train_state, make_chunk_step

    base = get_config("zcode-m3-base")
    n_exp = 8
    cfg = dataclasses.replace(
        base, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256, vocab=1024,
        n_layers=2,
        encdec=dataclasses.replace(base.encdec, n_encoder_layers=2),
        moe=dataclasses.replace(
            base.moe, n_experts=n_exp,
            gating_dropout=dataclasses.replace(
                base.moe.gating_dropout, mode="gate_drop", rate=0.3,
                strategy="host_cond")))
    tc = TrainConfig(steps=1)
    shape = jax.eval_shape(
        lambda: init_train_state(init_model(jax.random.PRNGKey(0), cfg), tc))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shape)
    batch = {k: jax.ShapeDtypeStruct((1, 8, 32), F32 if k == "loss_mask"
                                     else I32, sharding=one_chip)
             for k in ("enc_tokens", "tokens", "labels", "loss_mask")}
    chunk = make_chunk_step(cfg, tc)
    leaf_copy = re.compile(rf"= f32\[\d+,{n_exp},\d+,\d+\]\{{[^}}]*\}} copy\(")
    copies = {dec: len(leaf_copy.findall(
        chunk.lower(state, batch, dec).compile().as_text()))
        for dec in (False, True)}
    assert copies == {False: 0, True: 0}, copies


@pytest.mark.parametrize("dropped", [False, True], ids=["routed", "dropped"])
def test_ep4_chunk_exchange_is_scoped_and_dropped_copies_no_expert_leaf(
        topo, no_compile_cache, dropped):
    """zcode-m3-big's layout on a described 2x2 mesh, 16 experts over 4
    chips (4 each), host_cond Gate-Drop: every all-to-all of the routed
    step program carries the ``exchange`` scope under ``moe`` in its
    ``op_name``, so a profiler trace can name and time it; the dropped
    program holds no all-to-all and, like the one-chip oracle's, copies no
    expert leaf of the train state. A TPU trace names each op by its
    instruction text without the metadata, and ``bench/trace_reduce.py``
    times the ops whose name holds ``all-to-all``: only the all-to-all
    instructions match, since an op that reads one names it
    ``%all_to_all.N``."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.moe import ParallelContext
    from repro.models import init_model
    from repro.training import (init_train_state, make_chunk_step,
                                train_state_sharding)

    base = get_config("zcode-m3-big")
    n_exp, chips = 16, 4
    cfg = dataclasses.replace(
        base, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256, vocab=1024,
        n_layers=2,
        encdec=dataclasses.replace(base.encdec, n_encoder_layers=2),
        moe=dataclasses.replace(
            base.moe, n_experts=n_exp,
            gating_dropout=dataclasses.replace(
                base.moe.gating_dropout, mode="gate_drop", rate=0.3,
                strategy="host_cond")))
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    ctx = ParallelContext(mesh=mesh)
    tc = TrainConfig(steps=1)
    shape = jax.eval_shape(
        lambda: init_train_state(init_model(jax.random.PRNGKey(0), cfg), tc))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, train_state_sharding(cfg, tc, ctx))
    rows = NamedSharding(mesh, P(None, "data"))
    batch = {k: jax.ShapeDtypeStruct((1, 16, 32), F32 if k == "loss_mask"
                                     else I32, sharding=rows)
             for k in ("enc_tokens", "tokens", "labels", "loss_mask")}
    text = make_chunk_step(cfg, tc, ctx).lower(
        state, batch, dropped).compile().as_text()
    a2a = [ln for ln in text.splitlines()
           if re.search(r"= \S+ all-to-all(?:-start)?\(", ln)]
    if dropped:
        assert a2a == []
        # a chip's expert leaves: f32[1, 4, 128, 256] or [1, 4, 256, 128]
        leaf_copy = re.compile(rf"= f32\[1,{n_exp // chips},(?:128,256|"
                               rf"256,128)\]\{{[^}}]*\}} copy\(")
        assert leaf_copy.findall(text) == []
    else:
        assert a2a
        bare = lambda ln: re.sub(r", metadata=\{.*\}", "", ln)  # noqa: E731
        assert [ln for ln in map(bare, text.splitlines())
                if "all-to-all" in ln] == [bare(ln) for ln in a2a]
        names = [re.search(r'op_name="([^"]*)"', ln) for ln in a2a]
        assert all(m and re.search(r"(^|/)moe/(.*/)?exchange/", m.group(1))
                   for m in names), [m and m.group(1) for m in names]
