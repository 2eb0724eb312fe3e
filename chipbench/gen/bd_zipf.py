"""Block-diffusion batches: clean token ids drawn independently from a
Zipf law over the DATA ids 0..vocab-2 (p(id = k) proportional to
1 / (k + 1) ** exponent; the last id of the vocabulary is the mask
token), and the noise of the masked-diffusion objective drawn here, from
the same seed, so that the system and the reference see the same
corrupted batch: one t ~ U(t_min, 1] for every block of ``block``
tokens, and every token of the block replaced by the mask id with
probability t. A batch holds ``tokens`` (clean), ``noised``, ``replaced``
and ``t`` [rows, seq / block]."""

import numpy as np


def batches(seed: int, rows: int, seq: int, vocab: int, block: int = 4,
            exponent: float = 1.0, t_min: float = 1e-3):
    if seq % block:
        raise ValueError(f"seq {seq} is not a multiple of block {block}")
    rng = np.random.default_rng(seed)
    mask_id, data_ids = vocab - 1, vocab - 1
    cdf = np.cumsum(1.0 / np.arange(1, data_ids + 1) ** exponent)
    cdf /= cdf[-1]
    while True:
        ids = np.searchsorted(cdf, rng.random((rows, seq)))
        tokens = np.minimum(ids, data_ids - 1).astype(np.int32)
        # U(t_min, 1]: 1 - u is in (0, 1] for u in [0, 1)
        t = (t_min + (1.0 - t_min) * (1.0 - rng.random((rows, seq // block)))
             ).astype(np.float32)
        replaced = rng.random((rows, seq)) < np.repeat(t, block, axis=1)
        yield {"tokens": tokens,
               "noised": np.where(replaced, mask_id, tokens).astype(np.int32),
               "replaced": replaced, "t": t}
