"""Token source of the training traffic: rows drawn from one token pool.

A training job reads pre-tokenised shards; here the shard is one stream of
``pool_tokens`` token ids drawn once at set-up from the seed, vectorised,
under a Zipf law over the vocabulary (rank r has probability proportional
to r ** -zipf_exponent, ranks assigned to token ids by a seeded
permutation). A row of S + 1 tokens is the window at an offset that the
row's random generator picks, so rows of different steps differ.

``sample(rng, batch, seq_len)`` is the task interface that
``repro.data.ShardedTokenDataset`` calls with its own per-(shard, step)
generator; the ring rotation of shards over replicas stays the program's.
"""
from __future__ import annotations

import numpy as np


class TokenPool:
    def __init__(self, vocab: int, seed: int, source: dict):
        if source.get("kind") != "zipf_stream":
            raise ValueError(f"unknown token source {source.get('kind')!r}")
        rng = np.random.default_rng([int(seed), 0x7070])
        weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
            source["zipf_exponent"])
        cdf = np.cumsum(weights)
        cdf /= cdf[-1]
        ids = rng.permutation(vocab).astype(np.int32)
        ranks = np.searchsorted(cdf, rng.random(int(source["pool_tokens"])),
                                side="right")
        self.stream = ids[np.minimum(ranks, vocab - 1)]
        self.vocab = vocab

    def sample(self, rng: np.random.Generator, batch: int,
               seq_len: int) -> np.ndarray:
        offsets = rng.integers(0, self.stream.shape[0] - seq_len + 1,
                               size=batch)
        return self.stream[offsets[:, None] + np.arange(seq_len)]
