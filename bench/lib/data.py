"""The training traffic: a seeded synthetic corpus with learnable structure.

A copy of the program's ``repro.data.pipeline.SyntheticCorpus`` (same
arithmetic, so the same seed gives the same rows), kept with the benchmark
so that no change to the program changes the traffic it is measured on:

    with prob (1 - noise): next = (a * tok + b) mod V      (affine map)
    with prob noise:       next ~ Uniform(V)

A batch is a pure function of (seed, step); every row of every step
differs.  The parameters come from the cell's traffic file.

One departure from the program's copy: the multiplier ``a`` is drawn
again until it is coprime to the vocabulary size, so that the map is a
bijection on every seed.  The program draws any odd ``a``, which for a
vocabulary of 32,000 (2^8 x 5^3) is a multiple of 5 on about a fifth of
the seeds; the map then collapses the residues mod 125, and a batch holds
about 2,000 distinct tokens in place of about 7,000, a different work.
"""

from __future__ import annotations

import math

import numpy as np


class Corpus:
    def __init__(self, vocab: int, seq: int, batch: int, seed: int,
                 noise: float):
        self.vocab, self.seq, self.batch = vocab, seq, batch
        self.seed, self.noise = seed, noise
        g = np.random.default_rng(seed)
        self.a = int(g.integers(1, vocab) | 1)
        self.b = int(g.integers(0, vocab))
        while math.gcd(self.a, vocab) != 1:
            self.a = int(g.integers(1, vocab) | 1)

    def _stream(self, rng, n, length):
        v = self.vocab
        toks = np.empty((n, length), np.int64)
        toks[:, 0] = rng.integers(0, v, n)
        noise = rng.random((n, length)) < self.noise
        rand = rng.integers(0, v, (n, length))
        for t in range(1, length):
            nxt = (self.a * toks[:, t - 1] + self.b) % v
            toks[:, t] = np.where(noise[:, t], rand[:, t], nxt)
        return toks

    def batch_at(self, step: int) -> dict:
        """-> {"tokens", "labels"}: int32 [batch, seq] each."""
        rng = np.random.default_rng((self.seed, step))
        toks = self._stream(rng, self.batch, self.seq + 1)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}
