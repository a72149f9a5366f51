"""The token stream: what ``--seed`` makes.

Token ids are drawn from a Zipf(1.0) distribution over the configuration's
vocabulary (``p(k) ~ 1/k``, rank ``k`` is token id ``k - 1``) and cut into
sequences; ``targets`` is the sequence rolled left by one.  Uniform tokens
have nothing to learn below ``ln V``; under a Zipf stream the unigram
distribution alone is worth about three nats at these vocabularies, so "the
loss fell" means something inside one window.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class ZipfStream:
    def __init__(self, vocab: int, seed: int, exponent: float = 1.0):
        weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
        self._cdf = np.cumsum(weights / weights.sum())
        self._cdf[-1] = 1.0
        self._rng = np.random.default_rng(seed)

    def rows(self, n: int, seq: int) -> Dict[str, np.ndarray]:
        ids = np.searchsorted(self._cdf, self._rng.random((n, seq)),
                              side="right").astype(np.int32)
        return {"input_ids": ids, "targets": np.roll(ids, -1, axis=1)}

    def batches(self, rows: int, seq: int) -> Iterator[Dict[str, np.ndarray]]:
        """A fresh batch for every step, for as long as the loop asks."""
        while True:
            yield self.rows(rows, seq)
