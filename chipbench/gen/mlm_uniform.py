"""Masked-LM batches: token ids uniform over 1..vocab-1, each position
masked with probability ``mask_rate`` (its input id replaced by 0)."""

import numpy as np


def batches(seed: int, rows: int, seq: int, vocab: int,
            mask_rate: float = 0.15):
    rng = np.random.default_rng(seed)
    while True:
        targets = rng.integers(1, vocab, size=(rows, seq), dtype=np.int32)
        mask = rng.random((rows, seq)) < mask_rate
        yield {"tokens": np.where(mask, 0, targets).astype(np.int32),
               "targets": targets, "mask": mask}
