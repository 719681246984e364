"""The one traffic generator: a seeded bigram token stream.

A copy of the program's ``data.SyntheticLM`` (each token has four likely
successors, with 10% uniform noise), kept here so that no change to the
program can change the yardstick.  A traffic mix is a JSON file under
``traffic/`` that names the job and its sizes; every seed gives batches of
the same sizes, only the tokens differ.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class BigramStream:
    """Deterministic token batches: ``batch(step, n)`` depends only on the
    seed, the step index and the sizes."""

    vocab_size: int
    seq_len: int
    seed: int = 0

    def __post_init__(self):
        rng = np.random.RandomState(self.seed % 2 ** 32)
        self._succ = rng.randint(0, self.vocab_size, size=(self.vocab_size, 4))

    def batch(self, step: int, batch_size: int) -> dict:
        rng = np.random.RandomState((self.seed * 9176 + step) % 2 ** 31)
        toks = np.zeros((batch_size, self.seq_len), np.int32)
        cur = rng.randint(0, self.vocab_size, size=(batch_size,))
        toks[:, 0] = cur
        for t in range(1, self.seq_len):
            pick = rng.randint(0, 4, size=cur.shape)
            nxt = self._succ[cur, pick]
            noise = rng.rand(*cur.shape) < 0.1
            rand = rng.randint(0, self.vocab_size, size=cur.shape)
            cur = np.where(noise, rand, nxt)
            toks[:, t] = cur
        return {"tokens": toks}
