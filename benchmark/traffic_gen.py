"""The one general traffic generator: data files in, seeded inputs out.

Training batches: token ids drawn on the host from a bounded Zipf over the
vocabulary (rank r has weight r^-a; ranks are mapped to ids by a seeded
permutation), labels the next token. There is something to learn (the
unigram distribution), where uniform random labels only settle at ln V.

Every seed does the same work: the sizes are the traffic file's, and the
seed draws only the token ids.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # --seed may exceed 2**31; SeedSequence takes any non-negative integer
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2**63 - 1), *stream]))


class ZipfTokens:
    """Seeded batches [batch, seq + 1] of token ids; inputs are [:, :-1]
    and labels [:, 1:]."""

    def __init__(self, seed: int, vocab: int, exponent: float):
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
        self._cdf = np.cumsum(w / w.sum())
        self._ids = _rng(seed, 1).permutation(vocab).astype(np.int32)
        self._seed = seed
        self._vocab = vocab

    def batch(self, step: int, batch: int, seq: int) -> tuple:
        u = _rng(self._seed, 2, step).random((batch, seq + 1))
        ranks = np.minimum(np.searchsorted(self._cdf, u), self._vocab - 1)
        toks = self._ids[ranks]
        return toks[:, :-1], toks[:, 1:]


def sample_batch(seed: int, vocab: int, sequences: int, tokens: int,
                 exponent: float = 1.1) -> tuple:
    """The seeded sample the correctness check runs on."""
    return ZipfTokens(seed, vocab, exponent).batch(2 ** 31 - 1, sequences,
                                                   tokens)
