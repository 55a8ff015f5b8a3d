"""The compiled-program lint subsystem (src/repro/analysis, DESIGN.md §12).

Three layers:
  * IR walkers as pure functions — canned-HLO parsing, dtype table,
    jaxpr dtype-flow / pallas-launch extraction;
  * each pass catches a DELIBERATELY seeded violation (an extra
    all_to_all, an f32 upcast, an oversized block footprint, an extra
    pallas launch, a hidden host pull, a jit cache miss) — a lint suite
    that never fires is indistinguishable from one that never looks;
  * the registry/driver surface: suppressions, gating, the CLI.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, SRC, run_py
from repro.analysis import (DTYPE_BYTES, UnknownDtypeError,
                            collectives_summary, parse_collectives,
                            parse_hlo, shape_bytes)
from repro.analysis.executables import Artifacts, ExecutableSpec
from repro.analysis.hostsync import guard_host_transfers, jit_cache_sizes
from repro.analysis.jaxprs import (count_primitive, f32_upcast_dots,
                                   pallas_launches, walk_eqns)
from repro.analysis.lint import format_report, gate
from repro.analysis.passes import available_passes, get_pass, run_pass

pytestmark = pytest.mark.lint


# --------------------------------------------------------------- dtype table

def test_shape_bytes_quantized_wire_dtypes():
    """The seed parser priced every unknown dtype at 4 bytes — the 8-bit
    wire dtypes were 4x over-priced."""
    assert shape_bytes("s8", (8, 16)) == 128
    assert shape_bytes("u8", (8, 16)) == 128
    assert shape_bytes("f8e4m3fn", (4, 4)) == 16
    assert shape_bytes("f8e5m2", (4,)) == 4
    assert shape_bytes("pred", (32,)) == 32
    assert shape_bytes("bf16", (2, 3)) == 12
    assert shape_bytes("f32", "8,16") == 512      # XLA's comma string
    assert shape_bytes("f32", ()) == 4            # scalar
    assert DTYPE_BYTES["c128"] == 16


def test_shape_bytes_unknown_dtype_raises():
    with pytest.raises(UnknownDtypeError):
        shape_bytes("f128", (2,))
    with pytest.raises(KeyError):                 # it IS a KeyError
        shape_bytes("mystery", (2,))


# --------------------------------------------------------------- HLO walker

_CANNED = """\
HloModule jit_step, entry_computation_layout={(f32[8,16]{1,0})->f32[8,16]{1,0}}

%fused_computation (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  ROOT %m = f32[8,16]{1,0} multiply(%p0, %p0)
}

ENTRY %main.42 (arg0: f32[8,16]) -> f32[8,16] {
  %arg0 = f32[8,16]{1,0} parameter(0)
  %all-to-all.1 = (f32[8,16]{1,0}, u8[64]{0}) all-to-all(%arg0, %arg0), \
replica_groups={{0,1,2,3},{4,5,6,7}}, channel_id=3, dimensions={0}
  %get-tuple-element.5 = f32[8,16]{1,0} get-tuple-element(%all-to-all.1), index=0
  %ag-start = f32[16,16]{1,0} all-gather-start(%get-tuple-element.5), \
replica_groups=[2,4], dimensions={0}, channel_id=4
  %ag-done = f32[16,16]{1,0} all-gather-done(%ag-start)
  %fus = f32[8,16]{1,0} fusion(%get-tuple-element.5), kind=kLoop, \
calls=%fused_computation
  ROOT %ar = f32[8,16]{1,0} all-reduce(%fus), replica_groups={{0,1,2,3,4,5,6,7}}, \
to_apply=%add
}
"""


def test_parse_hlo_structure():
    mod = parse_hlo(_CANNED)
    assert mod.entry == "main.42"
    assert set(mod.computations) == {"fused_computation", "main.42"}
    a2a = mod.find("all-to-all")
    assert len(a2a) == 1
    i = a2a[0]
    # tuple result flattened; layout braces skipped
    assert [(s.dtype, s.dims) for s in i.shapes] == \
        [("f32", (8, 16)), ("u8", (64,))]
    assert i.result_bytes == 8 * 16 * 4 + 64
    assert i.replica_groups == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert i.group_size == 4
    assert i.channel_id == 3
    assert i.computation == "main.42"
    # fusion body resolution
    fus = mod.find("fusion")[0]
    assert [c.name for c in mod.called_by(fus)] == ["fused_computation"]
    # root flag
    assert mod.find("all-reduce")[0].is_root


def test_parser_excludes_gte_and_counts_async_once():
    """The two structural traps: a get-tuple-element line that textually
    CONTAINS "all-to-all" (its operand name) must not count, and an
    async -start/-done pair is one collective, not two."""
    mod = parse_hlo(_CANNED)
    summary = collectives_summary(mod)
    assert summary["all-to-all"]["count"] == 1
    assert summary["all-gather"]["count"] == 1          # start+done = 1
    gte = [i for i in mod.instructions()
           if i.opcode == "get-tuple-element"]
    assert len(gte) == 1 and "all-to-all" in gte[0].raw


def test_collectives_summary_wire_model():
    s = collectives_summary(parse_hlo(_CANNED))
    a2a_payload = 8 * 16 * 4 + 64
    assert s["all-to-all"]["bytes"] == a2a_payload
    assert s["all-to-all"]["wire_bytes"] == a2a_payload * 3 / 4
    assert s["all-to-all"]["max_group"] == 4
    # iota groups [2,4] -> two groups of 4
    ag = 16 * 16 * 4
    assert s["all-gather"]["bytes"] == ag
    assert s["all-gather"]["wire_bytes"] == ag * 3 / 4
    ar = 8 * 16 * 4
    assert s["all-reduce"]["wire_bytes"] == 2 * ar * 7 / 8
    # the back-compat wrapper is the same numbers
    assert parse_collectives(_CANNED) == s


# -------------------------------------------------------------- jaxpr walker

def test_walk_eqns_recurses_with_path():
    def f(x):
        return jax.lax.scan(lambda c, t: (c + jnp.sin(t), c), x, x)[0]

    jx = jax.make_jaxpr(f)(jnp.ones(4))
    assert count_primitive(jx, "sin") == 1       # scan body counted ONCE
    paths = [p for eqn, p in walk_eqns(jx) if eqn.primitive.name == "sin"]
    assert paths == [("scan",)]


def test_f32_upcast_dots_catches_seeded_upcast():
    x = jnp.ones((128, 128), jnp.bfloat16)

    def bad(a, b):
        return a.astype(jnp.float32) @ b.astype(jnp.float32)

    hits = f32_upcast_dots(jax.make_jaxpr(bad)(x, x))
    assert len(hits) == 1
    assert hits[0].out_elems == 128 * 128
    assert set(hits[0].src_dtypes) == {"bfloat16"}


def test_f32_upcast_dots_whitelists():
    x = jnp.ones((128, 128), jnp.bfloat16)
    # a dot that KEEPS bf16 operands never matches, whatever it accumulates
    ok = jax.make_jaxpr(
        lambda a, b: jax.lax.dot(a, b,
                                 preferred_element_type=jnp.float32))(x, x)
    assert f32_upcast_dots(ok) == []
    # small outputs (router logits shape) stay below min_elems
    s = jnp.ones((32, 8), jnp.bfloat16)
    small = jax.make_jaxpr(
        lambda a: a.astype(jnp.float32) @ a.astype(jnp.float32).T)(s)
    assert f32_upcast_dots(small) == []
    assert len(f32_upcast_dots(small, min_elems=512)) == 1
    # native f32 dots are not upcasts
    f = jnp.ones((128, 128), jnp.float32)
    assert f32_upcast_dots(jax.make_jaxpr(lambda a: a @ a)(f)) == []


def _flash_fn():
    from repro.kernels.flash_decode import flash_decode
    B, H, KV, hd, S = 4, 2, 1, 16, 32
    q = jnp.ones((B, H, hd))
    k = jnp.ones((B, S, KV, hd))
    v = jnp.ones((B, S, KV, hd))
    idx = jnp.full((B,), 7, jnp.int32)
    return (lambda *a: flash_decode(*a, interpret=True)), (q, k, v, idx)


def test_pallas_launches_extracts_real_grid_mapping():
    fn, args = _flash_fn()
    launches = pallas_launches(jax.make_jaxpr(fn)(*args))
    assert len(launches) == 1
    l = launches[0]
    assert l.grid and all(g >= 1 for g in l.grid)
    assert l.buffers and all(b.bytes > 0 for b in l.buffers)
    assert l.vmem_bytes() >= sum(b.bytes for b in l.buffers)


# ------------------------------------------------- passes catch seeded bugs

def _spec(name, fn, args, expect, **kw):
    return ExecutableSpec(name=name, build=lambda: (fn, args),
                          expect=expect, **kw)


def test_dtype_flow_pass_fires_on_upcast():
    x = jnp.ones((128, 128), jnp.bfloat16)
    spec = _spec("inject/upcast",
                 lambda a: a.astype(jnp.float32) @ a.astype(jnp.float32),
                 (x,), {"dtype-flow": {"min_elems": 4096}})
    fs = run_pass("dtype-flow", spec, Artifacts(spec))
    assert [f.severity for f in fs] == ["error"]
    assert "bfloat16" in fs[0].message and "jaxpr:" in fs[0].location
    ok, verdict = gate(fs)
    assert not ok and "FAIL" in verdict


def test_vmem_budget_pass_fires_on_oversized_blocks():
    """Seed an over-budget launch by shrinking the budget under the real
    footprint — equivalent to a block spec outgrowing VMEM."""
    fn, args = _flash_fn()
    real = pallas_launches(jax.make_jaxpr(fn)(*args))[0].vmem_bytes()
    spec = _spec("inject/vmem", fn, args,
                 {"vmem-budget": {"budget_bytes": real - 1}})
    fs = run_pass("vmem-budget", spec, Artifacts(spec))
    assert len(fs) == 1 and fs[0].severity == "error"
    assert "MiB" in fs[0].message and fs[0].location.startswith("pallas:")
    # at the real footprint it passes
    spec_ok = _spec("inject/vmem-ok", fn, args,
                    {"vmem-budget": {"budget_bytes": real}})
    assert run_pass("vmem-budget", spec_ok, Artifacts(spec_ok)) == []


def test_launch_count_pass_fires_on_extra_launch():
    fn, args = _flash_fn()

    def twice(*a):
        return fn(*a) + fn(*a)                  # a second pallas_call

    spec = _spec("inject/launches", twice, args,
                 {"launch-count": {"max": 1}})
    fs = run_pass("launch-count", spec, Artifacts(spec))
    assert len(fs) == 1 and "2 pallas_call" in fs[0].message


def test_host_sync_pass_fires_on_hidden_pull_and_cache_miss():
    def scenario():
        jit_f = jax.jit(lambda v: v * 2)
        jit_f(jnp.ones(4))                      # warmup
        with guard_host_transfers() as events:
            before = jit_cache_sizes([jit_f])
            float(jnp.sum(jit_f(jnp.ones(4))))  # hidden pull
            jit_f(jnp.ones(8))                  # shape leak -> retrace
            after = jit_cache_sizes([jit_f])
        return {"events": events,
                "cache_sizes": [("jit_f", before[0], after[0])]}

    spec = ExecutableSpec(name="inject/hostsync", build=lambda: (None, ()),
                          expect={"host-sync": {}}, scenario=scenario)
    fs = run_pass("host-sync", spec, Artifacts(spec))
    kinds = {f.location.split(":")[0] for f in fs}
    assert any("test_analysis" in f.location for f in fs
               if "__float__" in f.message), fs
    assert any(f.location == "jit:jit_f" and "re-traced" in f.message
               for f in fs)
    assert "jit" in kinds


def test_no_collectives_pass_fires_on_extra_all_to_all():
    """Seed the §3 violation on a real 8-device mesh: a 'dropped'
    executable that still carries an all_to_all, and a routed one whose
    bytes disagree with the cost model."""
    out = run_py("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.analysis.executables import ExecutableSpec, Artifacts
from repro.analysis.passes import run_pass
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ('data',))

def leaky(x):   # pretends to be a zero-comm LOCAL path, but isn't
    def shard(x):
        return jax.lax.all_to_all(x, 'data', split_axis=0, concat_axis=1,
                                  tiled=True)
    return shard_map(shard, mesh=mesh, in_specs=P('data'),
                     out_specs=P('data'))(x)

x = jnp.ones((64, 64), jnp.float32)     # per-device shard (8, 64)
spec = ExecutableSpec(name='inject/leak', build=lambda: (leaky, (x,)),
                      expect={'no-collectives': {'zero': True}})
fs = run_pass('no-collectives', spec, Artifacts(spec))
assert len(fs) == 1 and fs[0].severity == 'error', fs
assert 'ZERO' in fs[0].message and 'all-to-all' in fs[0].location, fs

# count/bytes drift against the cost model is also an error
bytes_ = 64 * 64 * 4 // 8           # per-device result bytes
spec2 = ExecutableSpec(name='inject/drift', build=lambda: (leaky, (x,)),
                       expect={'no-collectives': {'cost': {
                           'calls': 2, 'bytes': bytes_ * 2,
                           'wire_bytes': 0.0}}})
fs2 = run_pass('no-collectives', spec2, Artifacts(spec2))
msgs = ' | '.join(f.message for f in fs2)
assert 'count 1 != cost model 2' in msgs, msgs
assert 'payload' in msgs and 'wire' in msgs, msgs

# exact agreement is clean
wire = bytes_ * (8 - 1) / 8
spec3 = ExecutableSpec(name='inject/exact', build=lambda: (leaky, (x,)),
                       expect={'no-collectives': {'cost': {
                           'calls': 1, 'bytes': bytes_,
                           'wire_bytes': wire}}})
assert run_pass('no-collectives', spec3, Artifacts(spec3)) == []

# and an executable EXPECTED to route but compiling to silence is flagged
spec4 = ExecutableSpec(name='inject/silent',
                       build=lambda: ((lambda y: y * 2), (x,)),
                       expect={'no-collectives': {'nonzero': True}})
fs4 = run_pass('no-collectives', spec4, Artifacts(spec4))
assert len(fs4) == 1 and 'silently elided' in fs4[0].message, fs4
print('OK')
""")
    assert "OK" in out


# ------------------------------------------------------ suppression + gate

def test_suppression_keeps_finding_but_passes_gate():
    x = jnp.ones((128, 128), jnp.bfloat16)
    spec = _spec("inject/suppressed",
                 lambda a: a.astype(jnp.float32) @ a.astype(jnp.float32),
                 (x,), {"dtype-flow": {}}, ignore=("dtype-flow",))
    fs = run_pass("dtype-flow", spec, Artifacts(spec))
    assert len(fs) == 1 and fs[0].suppressed
    ok, verdict = gate(fs)
    assert ok and "1 suppressed" in verdict
    assert "(suppressed)" in format_report(fs)
    assert fs[0].as_dict()["suppressed"] is True


def test_register_executable_parses_ignore_comment():
    from repro.analysis.executables import (_REGISTRY, get_executable,
                                            register_executable)
    try:
        register_executable(ExecutableSpec(
            name="inject/commented", build=lambda: (None, ()),
            expect={}))  # lint: ignore[vmem-budget, dtype-flow]
        spec = get_executable("inject/commented")
        assert spec.ignore == ("vmem-budget", "dtype-flow")
    finally:
        _REGISTRY.pop("inject/commented", None)


def test_pass_registry_surface():
    assert set(available_passes()) == {
        "no-collectives", "dtype-flow", "vmem-budget", "launch-count",
        "host-sync"}
    assert "scenario" in get_pass("host-sync").needs
    assert get_pass("no-collectives").needs == ("hlo",)
    with pytest.raises(KeyError, match="unknown lint pass"):
        get_pass("nope")


# -------------------------------------------------------------------- CLI

def test_lint_cli_gate_and_json(tmp_path):
    """The CI entry: `python -m repro.launch.lint --gate` on the 8-device
    CPU mesh, restricted to a cheap executable to keep the test fast."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)          # the CLI must set the mesh itself
    out_json = tmp_path / "lint.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.lint", "--gate",
         "--only", "pallas_fused/fwd", "--only", "flash_decode/step",
         "--json-out", str(out_json)],
        capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LINT GATE: ok" in r.stdout
    rep = json.loads(out_json.read_text())
    assert rep["ok"] is True and rep["findings"] == []


def test_lint_cli_list():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", "repro.launch.lint", "--list"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    for needle in ("no-collectives", "moe_layer/routed", "train_chunk/dropped",
                   "scheduler/ticks"):
        assert needle in r.stdout
