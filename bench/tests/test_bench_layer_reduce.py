"""The layer reduction and its readers, on a hand-made trace laid out as
the TPU profiler lays out its planes, and on a protobuf made from text
as the profiler writes it."""
import benchpaths  # noqa: F401  (bench/ and src/ on the path)

import pytest

import layer_reduce as LR
import metrics as M

JIT = "jit(chunk_fn)/jit(main)"


def hand_trace():
    # window 10.0-11.0 s; two chips; two step programs, decisions R, D.
    # The driving thread's line holds the window and the chunk spans; the
    # worker's line holds a span named like a driving one, to be ignored.
    driving = [("bench.window", 10.0, 11.0),
               ("train_chunk", 10.0, 10.5), ("chunk.decide", 10.0, 10.05),
               ("chunk.put", 10.05, 10.08), ("chunk.execute", 10.08, 10.1),
               ("PjitFunction(chunk_fn)", 10.08, 10.1),
               ("chunk.fetch", 10.1, 10.45), ("chunk.record", 10.45, 10.5),
               ("prefetch.wait", 10.5, 10.55),
               ("train_chunk", 10.55, 10.95), ("chunk.decide", 10.55, 10.6),
               ("chunk.put", 10.6, 10.62), ("chunk.execute", 10.62, 10.64),
               ("chunk.fetch", 10.64, 10.9), ("chunk.record", 10.9, 10.95)]
    worker = [("prefetch.produce", 10.0, 10.3), ("chunk.put", 10.3, 10.9)]
    ops0 = [("iota", 10.02, 10.03, "jit(_decisions_batch)/iota"),
            ("fusion.1", 10.1, 10.2, f"{JIT}/jvp()/while/body/moe/dot"),
            ("fusion.2", 10.2, 10.3,
             f"{JIT}/transpose(jvp())/checkpoint/rematted_computation/"
             f"attention/dot"),
            ("fusion.3", 10.3, 10.35, f"{JIT}/optimizer/sub;while/body"),
            ("copy.1", 10.35, 10.4, f"{JIT}/copy"),
            ("fusion.1", 10.65, 10.75, f"{JIT}/moe/dot;moe/add"),
            ("fusion.4", 10.75, 10.85, f"{JIT}/transpose(jvp(lm_head))/dot"),
            ("outside", 9.0, 9.5, f"{JIT}/moe/dot")]
    ops1 = [("fusion.1", 10.1, 10.3, f"{JIT}/moe/dot"),
            ("fusion.4", 10.65, 10.7, f"{JIT}/jvp(lm_head)/dot")]
    mods = [("jit_chunk_fn(1)", 10.1, 10.4), ("jit_chunk_fn(2)", 10.65, 10.85)]
    return {"host": [worker, driving],
            "devices": {0: {"ops": ops0, "modules": mods},
                        1: {"ops": ops1, "modules": mods}}}


def reduced():
    return LR.reduce(hand_trace(), "bench.window", "chunk_fn", expect_steps=2)


def test_scope_sums_per_step():
    r = reduced()
    assert r["chips"] == 2 and r["steps"] == 2
    assert r["scopes_seen"] == ["moe", "attention", "lm_head", "optimizer"]
    s0, s1 = r["scope_s"]
    # means over the two chips
    assert s0 == pytest.approx({"moe": 0.15, "attention": 0.05,
                                "lm_head": 0.0, "optimizer": 0.025,
                                "other": 0.025})
    assert s1 == pytest.approx({"moe": 0.05, "attention": 0.0,
                                "lm_head": 0.075, "optimizer": 0.0,
                                "other": 0.0})
    # the scopes and ``other`` add up to the step's op time
    assert r["op_s"] == pytest.approx([0.25, 0.125])
    for st, total in zip(r["scope_s"], r["op_s"]):
        assert sum(st.values()) == pytest.approx(total)


def test_scope_split_by_decision():
    r = reduced()
    ctx = {"layers": r, "trace": {"decisions": [False, True]}}
    assert M.reader("moe_ms.routed")(ctx) == pytest.approx(150.0)
    assert M.reader("moe_ms.dropped")(ctx) == pytest.approx(50.0)
    assert M.reader("optimizer_ms")(ctx) == pytest.approx(12.5)
    assert M.reader("lm_head_ms")(ctx) == pytest.approx(37.5)
    assert M.reader("attention_ms")(ctx) == pytest.approx(25.0)
    # no dropped step in the window: nothing to read
    ctx["trace"]["decisions"] = [False, False]
    assert M.reader("moe_ms.dropped")(ctx) is None


def test_idle_piecewise_by_innermost_span():
    r = reduced()
    idle = r["idle_s"]
    # chip 0 idle: 10.0-10.02, 10.03-10.1, 10.4-10.65, 10.85-11.0;
    # chip 1: 10.0-10.1, 10.3-10.65, 10.7-11.0; each instant goes to the
    # innermost span of the driving thread, never to train_chunk whose
    # children cover it
    assert idle == pytest.approx({
        "chunk.decide": (0.09 + 0.10) / 2, "chunk.put": 0.05,
        "chunk.execute": 0.04, "chunk.fetch": (0.11 + 0.36) / 2,
        "chunk.record": 0.10, "prefetch.wait": 0.05,
        LR.NO_SPAN: 0.05})
    busy = ((0.01 + 0.3 + 0.2) + (0.2 + 0.05)) / 2
    assert sum(idle.values()) == pytest.approx(r["window_s"] - busy)
    ctx = {"layers": r, "trace": {"decisions": [False, True]}}
    assert M.reader("idle_ms.decide")(ctx) == pytest.approx(47.5)
    assert M.reader("idle_ms.input")(ctx) == pytest.approx(50.0)


def test_spans_of_other_threads_are_ignored():
    t = hand_trace()
    t["host"][0] = []       # the worker's line, with its chunk.put
    assert LR.reduce(t, "bench.window", "chunk_fn", 2) == reduced()
    r = reduced()
    assert "prefetch.produce" not in r["spans_seen"]
    assert "PjitFunction(chunk_fn)" not in r["idle_s"]


def test_readers_zero_when_present_none_when_absent():
    layers = {"steps": 2, "scopes_seen": ["optimizer"],
              "scope_s": [{"optimizer": 0.0, "other": 0.1}] * 2,
              "spans_seen": ["chunk.put"], "idle_s": {}}
    ctx = {"layers": layers, "trace": {"decisions": [False, True]}}
    assert M.reader("optimizer_ms")(ctx) == 0.0
    assert M.reader("idle_ms.input")(ctx) == 0.0
    for name in ("moe_ms.routed", "moe_ms.dropped", "lm_head_ms",
                 "attention_ms", "idle_ms.decide"):
        assert M.reader(name)(ctx) is None, name
    assert M.reader("optimizer_ms")({"trace": {"decisions": []}}) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jvp()/while/body/closed_call/moe/tanh", "moe"),
    ("jit(f)/transpose(jvp())/checkpoint/rematted_computation/moe/sub",
     "moe"),
    ("jit(f)/jvp(lm_head)/mul", "lm_head"),
    ("jit(chunk_fn)/while/body/closed_call/attention/convert_element_type:",
     "attention"),
    ("jit(f)/transpose(jvp(lm_head))/dot_general", "lm_head"),
    ("jit(f)/optimizer/mul;while/body/closed_call", "optimizer"),
    ("jit(f)/while/body/add;attention/dot", "attention"),
    ("state['params']['moe']['router']['w']", "other"),
    ("jit(f)/moe_table/add", "other"),
    ("", "other"),
])
def test_scope_of(op_name, scope):
    assert LR.scope_of(op_name) == scope


@pytest.mark.parametrize("expect", [1, 3])
def test_step_count_off_raises(expect):
    with pytest.raises(ValueError, match="step executions"):
        LR.reduce(hand_trace(), "bench.window", "chunk_fn", expect)


def test_missing_window_or_plane_raises():
    with pytest.raises(ValueError, match="host event"):
        LR.reduce(hand_trace(), "nope", "chunk_fn", 2)
    t = hand_trace()
    t["devices"] = {}
    with pytest.raises(ValueError, match="plane"):
        LR.reduce(t, "bench.window", "chunk_fn", 2)


XSPACE = """
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 10000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000000000 } }
  lines { id: 2 name: "python" timestamp_ns: 10000000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 300000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chunk.decide" } }
  event_metadata { key: 3 value { id: 3 name: "prefetch.produce" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 10100000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 300000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 10100000000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000000000 }
    events { metadata_id: 3 offset_ps: 100000000000
      duration_ps: 100000000000 }
    events { metadata_id: 4 offset_ps: 200000000000
      duration_ps: 100000000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_chunk_fn(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = f32[8] fusion()"
    stats { metadata_id: 7 str_value: "jit(chunk_fn)/jvp(moe)/dot:" }
    stats { metadata_id: 8 str_value: "loop fusion" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8] fusion()"
    stats { metadata_id: 8 str_value: "loop fusion" }
    stats { metadata_id: 7 str_value: "jit(chunk_fn)/optimizer/sub:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.3 = f32[8] copy()" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "hlo_category" } } }
"""


def test_load_reads_op_names_from_event_metadata(tmp_path):
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    t = LR.load_bytes(data)
    assert [[n for n, _, _ in line] for line in t["host"]] == [
        ["bench.window", "chunk.decide"], ["prefetch.produce"]]
    dev = t["devices"][0]
    assert [op for _, _, _, op in dev["ops"]] == [
        "jit(chunk_fn)/jvp(moe)/dot:", "jit(chunk_fn)/optimizer/sub:", ""]
    assert dev["modules"] == [("jit_chunk_fn(1)", pytest.approx(10.1),
                               pytest.approx(10.4))]
    # as the profiler lays out its directory
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(data)
    r = LR.read(str(tmp_path), 1)
    assert r == LR.reduce(t, "bench.window", "chunk_fn", 1)
    # the copy names no scope; lm_head and attention are absent
    assert r["scopes_seen"] == ["moe", "optimizer"]
    assert r["scope_s"] == [pytest.approx({"moe": 0.1, "optimizer": 0.1,
                                           "other": 0.1})]
    # idle 10.0-10.1 under chunk.decide, 10.4-11.0 under no span
    assert r["idle_s"] == pytest.approx({"chunk.decide": 0.1,
                                         LR.NO_SPAN: 0.6})
