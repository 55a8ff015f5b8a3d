"""The benchmark's MT traffic: the same seed gives the same batches,
every seed does the same work per cycle, batches hold a token budget in
length buckets, the padded fraction matches the batch, the warm-up meets
every bucket under both decisions, and the consensus draw the reference
uses is the program's."""
import json
import os

from benchpaths import BENCH  # bench/ and src/ on the path

import numpy as np
import pytest

import reference as R
from mt_traffic import PAD, MTTraffic, padded_fraction, real_tokens

NAME = "mt.gd30.tok2048"


def traffic(name=NAME):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_batches(seed):
    a = MTTraffic(traffic(), 64000, seed)
    b = MTTraffic(traffic(), 64000, seed)
    for step in (0, 1, 8, 9, 34, 97):
        x, y = a.batch_at(step), b.batch_at(step)
        assert set(x) == {"enc_tokens", "tokens", "labels", "loss_mask"}
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
            assert x[k].shape == (a.rows[a.bucket(step)], a.bucket(step))
    other = MTTraffic(traffic(), 64000, seed + 1).batch_at(0)
    assert not np.array_equal(other["enc_tokens"],
                              a.batch_at(0)["enc_tokens"])


def test_token_budget_in_buckets():
    t = MTTraffic(traffic(), 64000, 3)
    assert t.buckets == [32, 64, 128, 256]
    assert {s: t.rows[s] for s in t.buckets} == {32: 64, 64: 32, 128: 16,
                                                 256: 8}
    for step in range(40):
        s = t.bucket(step)
        n = t.lengths(step)
        assert len(n) * s == 2048
        # the row fits its bucket and not the next smaller one
        smaller = max([b for b in t.buckets if b < s], default=0)
        assert n.max() + 2 <= s and n.min() + 2 > smaller


def test_every_seed_same_work_per_cycle():
    tr = traffic()
    sums, orders = set(), set()
    for seed in (1, 2, 3**20):
        t = MTTraffic(tr, 64000, seed)
        w, c = len(t.warmup), t.cycle_steps
        steps = range(w + c, w + 2 * c)
        sums.add(sum(real_tokens(t.batch_at(i)) for i in steps))
        lens = np.concatenate([t.lengths(i) for i in steps])
        np.testing.assert_array_equal(np.sort(lens), np.sort(t.cycle_lengths))
        orders.add(tuple(t.bucket(i) for i in steps))
    assert len(sums) == 1 and len(orders) == 3


def test_padded_fraction_matches_batch():
    t = MTTraffic(traffic(), 64000, 12345)
    for step in range(12):
        b = t.batch_at(step)
        n, s = t.lengths(step), t.bucket(step)
        # source: tag + sentence + EOS; target: sentence + EOS
        real = int(np.sum(n + 2) + np.sum(n + 1))
        assert real_tokens(b) == real
        assert (b["enc_tokens"] != PAD).sum(1).tolist() == (n + 2).tolist()
        assert (b["labels"] != PAD).sum(1).tolist() == (n + 1).tolist()
        assert padded_fraction(b) == pytest.approx(1 - real / (2 * len(n) * s))
        # line the run prints before its window
        line = f"padded_fraction step0={padded_fraction(b):.6f}"
        assert float(line.split("=")[1]) == pytest.approx(
            1 - real / (2 * len(n) * s), abs=1e-6)


def test_lengths_heavy_tailed_and_capped():
    t = MTTraffic(traffic(), 64000, 0)
    n = t.cycle_lengths
    assert n.max() <= 254 and n.min() >= 4
    assert np.mean(n) > np.median(n)
    assert 28 <= np.median(n) <= 32


@pytest.mark.parametrize("name", [NAME, "tiny"])
def test_warmup_meets_every_bucket_and_decision(name):
    tr = traffic() if name == NAME else json.load(
        open(os.path.join(BENCH, "tests", "data", "tiny_traffic.json")))
    t = MTTraffic(tr, 512, 1)
    gd = tr["gating_dropout"]
    dec = [R.decision(gd["consensus_seed"], i, gd["rate"])
           for i in range(len(t.warmup))]
    pairs = {(t.bucket(i), d) for i, d in enumerate(dec) if i}
    assert pairs == {(b, d) for b in t.buckets for d in (False, True)}
    k = tr["check_steps"]
    assert {t.bucket(i) for i in range(k)} == set(t.buckets)
    assert set(dec[:k]) == {False, True}


def test_consensus_draw_is_the_programs():
    from repro.configs.base import GatingDropoutConfig
    from repro.core.gating_dropout import drop_decisions_host
    gd = GatingDropoutConfig(mode="gate_drop", rate=0.3)
    seed = traffic()["gating_dropout"]["consensus_seed"]
    prog = drop_decisions_host(gd, seed, 0, 12).tolist()
    assert [R.decision(seed, i, 0.3) for i in range(12)] == prog
    assert prog[:9] == [False, False, False, True, False, True, True, True,
                        False]
