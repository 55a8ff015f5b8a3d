"""The benchmark's yardstick: the FLOP count against a hand count, the
table of peaks, and a run with no TPU."""
import json
import os
import subprocess
import sys

from benchpaths import BENCH, ROOT  # bench/ and src/ on the path

import pytest

import flops
from weights import ModelSpec, n_params


def base_spec():
    with open(os.path.join(BENCH, "configs", "zcode-m3-base.e2d2.json")) as f:
        return ModelSpec.from_config(json.load(f))


def test_forward_flops_hand_count():
    # zcode-m3-base.e2d2, one pair of s=10 source and t=8 target positions
    d, f, E, V, s, t = 512, 2048, 128, 64000, 10, 8
    per_token_layer = 8 * d * d + 4 * d * f          # projections + FFN
    enc = 2 * (s * per_token_layer + 4 * s * s * d) + 2 * s * d * E
    dec = 2 * (t * per_token_layer + 2 * t * (t + 1) * d
               + 4 * t * d * d + 4 * s * d * d + 4 * t * s * d) \
        + 2 * t * d * E
    head = 2 * t * d * V
    assert flops.forward_flops(base_spec(), s, t) == enc + dec + head
    assert enc + dec + head == 791_773_184


def test_train_flops_per_token():
    spec = base_spec()
    assert flops.pair_positions(100, 256) == (102, 101)
    assert flops.pair_positions(254, 256) == (256, 255)
    got = flops.train_flops_per_token(spec, [100], 256)
    assert got == pytest.approx(3 * flops.forward_flops(spec, 102, 101)
                                / 203)
    # the LM head dominates: 2 d V per target token, half the tokens
    assert 130e6 < got < 160e6


def test_params_of_the_cut_model():
    assert n_params(base_spec()) == 613_036_032


def test_peaks():
    p = flops.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")


def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train.base.gd30", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert "needs 1 TPU chip" in r.stderr
    assert '"correct"' not in r.stdout
