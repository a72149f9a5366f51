"""The token stream: what ``--seed`` makes.

Token ids are drawn from a Zipf(1.0) distribution over the configuration's
vocabulary (``p(k) ~ 1/k``, rank ``k`` is token id ``k - 1``) and cut into
sequences; ``targets`` is the sequence rolled left by one.  Uniform tokens
have nothing to learn below ``ln V``; under a Zipf stream the unigram
distribution alone is worth about three nats at these vocabularies, so "the
loss fell" means something inside one window.

Where a traffic file gives ``window_ids_seed``, the ids of ``batches`` — the
warm-up's and the window's — are drawn from that number and are the same, in
the same order, for every ``--seed``; the seed draws ``rows`` (the reference
check's sequences) as before.  It is for a cell whose work follows from the
ids: where a layer holds a part of its experts, the ids steer the routers, the
routers decide how many rows a step works on, and which way they go is decided
by the smallest difference between two streams — the same rows with the two
rows of a step in another order already end 1.3% apart — so two seeds' windows
did different amounts of work (PERF.md section 6, PR 67).
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional

import numpy as np


class ZipfStream:
    def __init__(self, vocab: int, seed: int, exponent: float = 1.0,
                 window_ids_seed: Optional[int] = None):
        weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
        self._cdf = np.cumsum(weights / weights.sum())
        self._cdf[-1] = 1.0
        self._rng = np.random.default_rng(seed)
        self._window_rng = None if window_ids_seed is None \
            else np.random.default_rng(window_ids_seed)

    def _draw(self, rng, n: int, seq: int) -> Dict[str, np.ndarray]:
        ids = np.searchsorted(self._cdf, rng.random((n, seq)),
                              side="right").astype(np.int32)
        return {"input_ids": ids, "targets": np.roll(ids, -1, axis=1)}

    def rows(self, n: int, seq: int) -> Dict[str, np.ndarray]:
        return self._draw(self._rng, n, seq)

    def batches(self, rows: int, seq: int) -> Iterator[Dict[str, np.ndarray]]:
        """A fresh batch for every step, for as long as the loop asks."""
        while True:
            yield self.rows(rows, seq) if self._window_rng is None \
                else self._draw(self._window_rng, rows, seq)
