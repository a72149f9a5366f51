"""``tokens.ZipfStream`` whose ids are held for runs: the stream of a cell
whose objective looks further ahead than the next token.

Every id is Zipf(1.0) over the vocabulary, as ``ZipfStream`` draws it; with
probability ``hold`` a position repeats the id before it and with ``1 - hold``
it takes a fresh draw, so an id stands for a run of ``1 / (1 - hold)``
positions on average and the marginal distribution is the Zipf stream's.  The
byte ``r + 1`` ahead is then the byte at hand with probability ``hold ** (r +
1)``: what a head ``r`` positions further ahead can learn differs from head to
head, the loss has more to fall than the unigram entropy, and an objective
that scores the wrong byte reads another loss.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from perfbench.harness.tokens import ZipfStream


class HeldZipfStream(ZipfStream):
    def __init__(self, vocab: int, seed: int, hold: float):
        super().__init__(vocab, seed)
        if not 0.0 < hold < 1.0:
            raise ValueError(f"hold is a probability, not {hold}")
        self._hold = float(hold)

    def rows(self, n: int, seq: int) -> Dict[str, np.ndarray]:
        drawn = super().rows(n, seq)
        fresh = self._rng.random((n, seq)) >= self._hold
        fresh[:, 0] = True
        # each position reads the draw of the last fresh position up to it
        at = np.maximum.accumulate(
            np.where(fresh, np.arange(seq), 0), axis=1)
        ids = np.take_along_axis(drawn["input_ids"], at, axis=1)
        return {"input_ids": ids, "targets": np.roll(ids, -1, axis=1)}
