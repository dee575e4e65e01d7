"""Training batches of seeded token ids, cycled by the runner.

Mix parameters: ``sequence_tokens``, ``sequences_per_chip``,
``distinct_batches``.
"""

import numpy as np


def batches(mix: dict, vocab: int, seed: int, chips: int):
    """``distinct_batches`` seeded global batches of token ids
    [chips · sequences_per_chip, sequence_tokens + 1] (the +1: labels are
    the inputs shifted by one)."""
    rng = np.random.default_rng([seed, 0x6261])
    shape = (chips * int(mix["sequences_per_chip"]),
             int(mix["sequence_tokens"]) + 1)
    return [rng.integers(0, vocab, size=shape, dtype=np.int64)
            for _ in range(int(mix["distinct_batches"]))]
