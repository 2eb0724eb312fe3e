"""Causal-LM batches: token ids drawn independently from a Zipf law
over the vocabulary, p(id = k) proportional to 1 / (k + 1) ** exponent,
by inverting the cumulative distribution."""

import numpy as np


def batches(seed: int, rows: int, seq: int, vocab: int,
            exponent: float = 1.0):
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1) ** exponent)
    cdf /= cdf[-1]
    while True:
        ids = np.searchsorted(cdf, rng.random((rows, seq)))
        yield {"tokens": np.minimum(ids, vocab - 1).astype(np.int32)}
