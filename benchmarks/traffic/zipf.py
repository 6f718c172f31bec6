"""Training batches of i.i.d. tokens from a Zipf law.

Rank ``r`` (1-based) has probability proportional to ``r ** -exponent``; which
token holds which rank is a permutation of the vocabulary drawn from the seed,
so no run can lean on token ids being ordered by frequency. Tokens are drawn
by inverse CDF (``searchsorted`` on the cumulative law) from a generator keyed
on ``(seed, step)``: batch ``k`` is a pure function of the seed, the traffic
file and ``k``, whatever was drawn before. The unigram law is all there is to
learn, which is enough for the loss to fall well below ``ln(vocab)`` in thirty
steps.
"""

from __future__ import annotations

import numpy as np


class Generator:
    def __init__(self, data: dict, seed: int, vocab_size: int, rows: int, seq: int):
        self.seed, self.rows, self.seq = seed, rows, seq
        weights = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(data["exponent"])
        self.cdf = np.cumsum(weights / weights.sum())
        self.token_of_rank = np.random.default_rng([seed, 0]).permutation(vocab_size).astype(np.int32)

    def batch(self, step: int):
        """Inputs and next-token targets ``[rows, seq]`` int32 for step ``step`` (1-based)."""
        u = np.random.default_rng([self.seed, step]).random((self.rows, self.seq + 1))
        ranks = np.minimum(np.searchsorted(self.cdf, u), len(self.cdf) - 1)
        tokens = self.token_of_rank[ranks]
        return tokens[:, :-1], tokens[:, 1:]
