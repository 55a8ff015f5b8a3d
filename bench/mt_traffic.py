"""Multilingual MT training traffic, kept with the benchmark.

A copy of the program's synthetic MT task (``repro.data.pipeline.
MultilingualMT``): each language has a seeded token permutation, and a
sample for a direction into language ``l`` is

    source = [tag(l)] s_1..s_n [EOS]
    target = reverse(perm_l(s)), fed as [BOS] t_1..t_m, labels t_1..t_m EOS

with Zipf-distributed content tokens and low-resource languages drawn
with a small weight.

Unlike the program's copy, batches are made as MT trainers make them: by
a token budget with length buckets (fairseq's ``--max-tokens`` with
``batch_by_size``). Bucket ``S`` holds the sentences whose source row
(tag, sentence, EOS) fits ``S`` positions and not the next smaller
bucket, ``tokens // S`` rows of them per chip, so every step holds the
same number of positions per side. Sentence lengths are log-normal and
come from a fixed CYCLE: each bucket's batches of a cycle hold the
mid-point quantiles of the distribution within that bucket, as many
batches as the bucket's share of ``cycle_sentences`` fills. A cycle's
batches run in an order drawn from the seed, and the lengths in an order
drawn from the seed, so every seed does the same work per cycle.

The first steps take their buckets from the file's ``warmup_buckets``,
so that they meet every bucket under both Gating-Dropout decisions
before the measured window.

Everything here is numpy and a pure function of (traffic file, vocab,
seed, step), so the same seed gives the same batches.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np

PAD, BOS, EOS = 0, 1, 2


class MTTraffic:
    """Length-bucketed batches under a token budget per side."""

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        self.seed = int(seed)
        n_langs = int(traffic["n_langs"])
        self.n_langs = n_langs
        self.first_content = 3 + n_langs
        self.n_content = vocab - self.first_content
        if self.n_content <= 10:
            raise ValueError(f"vocab {vocab} too small for {n_langs} langs")
        # the permutations are the task (the "languages"), fixed by the
        # traffic file; the seed draws the sentences
        root = np.random.default_rng(int(traffic["task_seed"]))
        self.perms = np.stack([root.permutation(self.n_content)
                               for _ in range(n_langs)])
        n_low = max(1, int(n_langs * float(traffic["low_resource_frac"])))
        w = np.ones(n_langs)
        w[-n_low:] = float(traffic["low_resource_weight"])
        self.lang_p = w / w.sum()
        ranks = np.arange(1, self.n_content + 1)
        zipf = 1.0 / ranks ** float(traffic["zipf_exponent"])
        self.content_p = zipf / zipf.sum()

        ln, bt = traffic["length"], traffic["batching"]
        if ln["dist"] != "lognormal":
            raise ValueError(f"unknown length distribution {ln['dist']!r}")
        chips = int(traffic["mesh"]["data"])
        self.buckets: List[int] = sorted(int(s) for s in bt["buckets"])
        tokens = int(bt["tokens_per_side_per_chip"])
        if any(tokens % s for s in self.buckets):
            raise ValueError(f"bucket sizes {self.buckets} must divide the "
                             f"token budget {tokens}")
        self.rows = {s: tokens // s * chips for s in self.buckets}
        lo_n, hi_n = int(ln["min"]), min(int(ln["max"]), self.buckets[-1] - 2)
        dist = NormalDist(np.log(float(ln["median"])), float(ln["sigma"]))
        cdf = lambda x: dist.cdf(np.log(x)) if x > 0 else 0.0  # noqa: E731
        n_sent = int(bt["cycle_sentences"])
        # each bucket: the quantiles of its slice of the distribution,
        # the first bucket taking the mass below ``min`` and the last the
        # mass above ``max``, which are clipped
        self.bucket_lengths: Dict[int, np.ndarray] = {}
        self.cycle_buckets: List[int] = []
        prev = 0
        for i, s in enumerate(self.buckets):
            hi = min(s - 2, hi_n)
            a = 0.0 if i == 0 else cdf(prev + 0.5)
            b = 1.0 if i == len(self.buckets) - 1 else cdf(hi + 0.5)
            k = max(1, round((b - a) * n_sent / self.rows[s]))
            m = k * self.rows[s]
            u = a + (b - a) * (np.arange(m) + 0.5) / m
            x = np.exp([dist.inv_cdf(float(v)) for v in u])
            self.bucket_lengths[s] = np.clip(np.rint(x), max(prev + 1, lo_n),
                                             hi).astype(np.int64)
            self.cycle_buckets += [s] * k
            prev = hi
        self.cycle_steps = len(self.cycle_buckets)
        self.warmup = [int(s) for s in bt["warmup_buckets"]]
        if not set(self.warmup) <= set(self.buckets):
            raise ValueError(f"warmup_buckets {self.warmup} not among "
                             f"{self.buckets}")
        self.cycle_lengths = np.concatenate(
            [self.bucket_lengths[s] for s in self.buckets])
        self._cycle_cache: Dict[int, Dict] = {}

    def _rng(self, *parts: int) -> np.random.Generator:
        return np.random.default_rng([self.seed & 0xFFFFFFFF,
                                      self.seed >> 32, *parts])

    def _cycle(self, c: int) -> Dict:
        """Cycle ``c``: its seeded order of batches, and each bucket's
        lengths in a seeded order."""
        got = self._cycle_cache.get(c)
        if got is None:
            rng = self._rng(1, c)
            got = {"order": [self.cycle_buckets[i] for i in
                             rng.permutation(self.cycle_steps)],
                   "lengths": {s: rng.permutation(self.bucket_lengths[s])
                               for s in self.buckets}}
            self._cycle_cache = {c: got}
        return got

    def bucket(self, step: int) -> int:
        """The bucket (positions per row) of ``step``."""
        if step < len(self.warmup):
            return self.warmup[step]
        c, j = divmod(step - len(self.warmup), self.cycle_steps)
        return self._cycle(c)["order"][j]

    def lengths(self, step: int) -> np.ndarray:
        """The source lengths of ``step``'s rows."""
        s = self.bucket(step)
        rows = self.rows[s]
        if step < len(self.warmup):
            # the warm-up's batches: the first rows of the bucket's
            # lengths in an order drawn from (seed, step)
            return self._rng(3, step).permutation(
                self.bucket_lengths[s])[:rows]
        c, j = divmod(step - len(self.warmup), self.cycle_steps)
        cyc = self._cycle(c)
        k = sum(1 for b in cyc["order"][:j] if b == s)
        return cyc["lengths"][s][k * rows:(k + 1) * rows]

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """One step's batch: enc_tokens, tokens, labels, loss_mask."""
        L = self.bucket(step)
        n = self.lengths(step)
        b = len(n)
        rng = self._rng(2, step)
        langs = rng.choice(self.n_langs, size=b, p=self.lang_p)
        n_max = int(n.max())
        content = rng.choice(self.n_content, size=(b, n_max),
                             p=self.content_p)
        fc = self.first_content
        pos = np.arange(n_max)[None, :]
        valid = pos < n[:, None]
        t_fwd = self.perms[langs[:, None], content]
        rev = np.take_along_axis(t_fwd, np.maximum(n[:, None] - 1 - pos, 0),
                                 axis=1)
        rows = np.arange(b)
        enc = np.full((b, L), PAD, np.int32)
        enc[:, 0] = 3 + langs
        enc[:, 1:1 + n_max] = np.where(valid, content + fc, PAD)
        enc[rows, 1 + n] = EOS
        body = np.where(valid, rev + fc, PAD)
        dec = np.full((b, L), PAD, np.int32)
        dec[:, 0] = BOS
        dec[:, 1:1 + n_max] = body
        lab = np.full((b, L), PAD, np.int32)
        lab[:, :n_max] = body
        lab[rows, n] = EOS
        msk = (np.arange(L)[None, :] < (n + 1)[:, None]).astype(np.float32)
        return {"enc_tokens": enc, "tokens": dec, "labels": lab,
                "loss_mask": msk}


def real_tokens(batch: Dict[str, np.ndarray]) -> int:
    """Non-padding source and target tokens of a batch: the source row's
    tag, sentence and EOS, and the target positions the loss counts."""
    return int((batch["enc_tokens"] != PAD).sum()
               + batch["loss_mask"].sum())


def padded_fraction(batch: Dict[str, np.ndarray]) -> float:
    """Share of the batch's source and target positions that are padding."""
    total = batch["enc_tokens"].size + batch["loss_mask"].size
    return 1.0 - real_tokens(batch) / total
