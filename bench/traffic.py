"""The one traffic generator: every mix under ``bench/traffic/`` is a data
file of parameters that these functions read.  Everything is drawn from the
run's ``--seed``; the same seed gives the same inputs.

The draws are the benchmark's own, so that a change to the program's
generators cannot move the yardstick: :func:`zipf_tokens` draws token ids by
a Zipf law over a vocabulary, the unigram law of text, with the ids of each
rank permuted by the seed.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size (``--seed`` may need more than 32 bits) as two
    uint32 words, for the generators and for JAX keys."""
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(w) for w in seed_words(seed)]
                                 + [int(stream)])


def zipf_tokens(vocab: int, shape, exponent: float, seed: int,
                stream: int = 2) -> np.ndarray:
    """int32 token ids of ``shape`` drawn by P(rank i) ~ 1 / i**exponent
    over ``vocab`` ids; which id holds which rank is permuted by the
    seed."""
    g = rng(seed, stream)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    ids = g.permutation(vocab).astype(np.int32)
    ranks = np.searchsorted(cdf, g.random(int(np.prod(shape))), side="right")
    return ids[np.minimum(ranks, vocab - 1)].reshape(shape)
