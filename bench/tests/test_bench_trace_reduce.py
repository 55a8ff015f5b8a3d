"""The trace reduction, on a hand-made trace laid out as the TPU
profiler lays out its planes."""
import benchpaths  # noqa: F401  (bench/ and src/ on the path)

import pytest

import trace_reduce as TR


def hand_trace():
    # window 10.0-11.0 s on the trace clock; two chips; two step programs
    # each; chip 1 runs an all-to-all inside its first step
    ops0 = [("fusion.1", 10.1, 10.3), ("fusion.2", 10.25, 10.4),
            ("all-to-all.3", 10.4, 10.45), ("fusion.1", 10.6, 10.9),
            ("outside", 9.0, 9.5)]
    ops1 = [("fusion.1", 10.1, 10.2), ("all-to-all.3", 10.2, 10.3),
            ("fusion.1", 10.6, 10.8)]
    mods = [("jit_chunk_fn(1)", 10.1, 10.45), ("jit_other(2)", 10.5, 10.55),
            ("jit_chunk_fn(3)", 10.6, 10.9)]
    return {"host": [("bench.window", 10.0, 11.0), ("other", 10.0, 10.1)],
            "devices": {0: {"ops": ops0, "modules": mods},
                        1: {"ops": ops1, "modules": mods}}}


def test_hand_trace():
    # host spans on the host clock: the window began at 100.0 there
    spans = [("chunk.fetch", 100.4, 100.6), ("train_chunk", 100.0, 100.6)]
    r = TR.reduce(hand_trace(), "bench.window", 100.0, spans, "chunk_fn",
                  expect_steps=2)
    assert r["window_s"] == pytest.approx(1.0)
    # chip 0 busy 10.1-10.45 and 10.6-10.9: 0.65 s; chip 1: 0.4 s
    assert r["busy_s"] == pytest.approx((0.65 + 0.4) / 2)
    assert r["chips"] == 2
    assert r["step_s"] == pytest.approx([0.35, 0.3])
    assert r["a2a_s"] == pytest.approx([(0.05 + 0.1) / 2, 0.0])
    ops = dict((n, v) for n, v in r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.2 + 0.3 + 0.1 + 0.2) / 2)
    assert "outside" not in ops
    idle = dict((n, v) for n, v in r["breakdown"]["idle_gaps"])
    # gaps: chip 0: 10.0-10.1 (train_chunk), 10.45-10.6 (chunk.fetch),
    # 10.9-11.0 (none); chip 1: 10.0-10.1, 10.3-10.6 (chunk.fetch),
    # 10.8-11.0 (none)
    assert idle["train_chunk"] == pytest.approx(0.1)
    assert idle["chunk.fetch"] == pytest.approx((0.15 + 0.3) / 2)
    assert idle["no host span"] == pytest.approx((0.1 + 0.2) / 2)
    total_idle = sum(idle.values())
    assert total_idle == pytest.approx(r["window_s"] - r["busy_s"])


def test_union():
    assert TR.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]


def test_missing_window_raises():
    with pytest.raises(ValueError):
        TR.reduce(hand_trace(), "nope", 0.0, [], "chunk_fn", expect_steps=2)


@pytest.mark.parametrize("expect", [1, 3])
def test_step_count_off_raises(expect):
    with pytest.raises(ValueError, match="step executions"):
        TR.reduce(hand_trace(), "bench.window", 100.0, [], "chunk_fn",
                  expect_steps=expect)


def test_no_device_plane_raises():
    t = hand_trace()
    t["devices"] = {}
    with pytest.raises(ValueError, match="plane"):
        TR.reduce(t, "bench.window", 100.0, [], "chunk_fn", expect_steps=2)
