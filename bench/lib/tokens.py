"""The benchmark's one generator of training batches.

A traffic file says how rows are drawn; this reads it.  Batch ``step`` of a
run is a function of (seed, step) alone, so the same seed gives the same
inputs, and every row of every step differs.

Token ids follow a Zipf law over the vocabulary (rank r drawn with weight
r ** -exponent), with ranks mapped to ids by a permutation drawn from the
seed: natural text has such a skewed unigram distribution, and the skew
sets how the loss and the unembedding's gradient are spread.
"""
from __future__ import annotations

import numpy as np


class TokenBatches:
    def __init__(self, seed: int, vocab_size: int, batch_size: int,
                 seq_len: int, zipf_exponent: float):
        self.seed = seed % 2**32
        self.batch_size = batch_size
        self.seq_len = seq_len
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -zipf_exponent)
        self.cdf = cdf / cdf[-1]
        self.ids = np.random.default_rng([self.seed, 0]).permutation(
            vocab_size).astype(np.int32)

    def batch(self, step: int) -> dict:
        """{tokens, labels}: (batch_size, seq_len) int32 each, labels the
        tokens shifted by one."""
        rng = np.random.default_rng([self.seed, 1, step])
        u = rng.random((self.batch_size, self.seq_len + 1))
        rows = self.ids[np.minimum(np.searchsorted(self.cdf, u),
                                   len(self.ids) - 1)]
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
