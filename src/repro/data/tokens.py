"""Synthetic token data source for the LM architectures.

A first-order Markov chain over the vocabulary with a learnable structure
(low-entropy transitions) so short training runs show decreasing loss —
giving the integration tests a real signal, not noise.
"""
from __future__ import annotations

import numpy as np


class MarkovTokens:
    def __init__(self, vocab: int, seed: int = 0, branching: int = 8):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.branching = branching
        # each token deterministically prefers `branching` successors
        self._succ = self.rng.integers(0, vocab, size=(min(vocab, 4096),
                                                       branching))

    def sample(self, batch: int, seq_len: int) -> np.ndarray:
        out = np.empty((batch, seq_len), np.int32)
        cur = self.rng.integers(0, self.vocab, size=batch)
        for t in range(seq_len):
            out[:, t] = cur
            idx = cur % self._succ.shape[0]
            pick = self.rng.integers(0, self.branching, size=batch)
            nxt = self._succ[idx, pick]
            noise = self.rng.random(batch) < 0.1
            cur = np.where(noise, self.rng.integers(0, self.vocab, batch), nxt)
        return out

    def batches(self, batch: int, seq_len: int):
        while True:
            yield {"tokens": self.sample(batch, seq_len)}


class PackedDocuments:
    """Documents of heavy-tailed length packed into fixed-length rows.

    Lengths are lognormal (``median`` tokens, shape ``sigma``) and cut at
    ``seq_len``; each document is followed by ``eos``, and a row is filled
    document by document, the last one cut at the row's end.  The tokens
    come from one ``MarkovTokens`` chain per row (sampled for all rows at
    once), an ``eos`` taking the place of the chain's token at each
    document's end.  Attention runs causally across the documents of a
    row, as packed pretraining commonly does."""

    def __init__(self, vocab: int, seq_len: int, seed: int = 0,
                 median: float = 1024.0, sigma: float = 1.2, eos: int = 0):
        self.src = MarkovTokens(vocab, seed=seed)
        self.rng = np.random.default_rng([seed, 1])
        self.seq_len, self.eos = seq_len, eos
        self.mu, self.sigma = float(np.log(median)), sigma

    def lengths(self, n: int) -> np.ndarray:
        raw = self.rng.lognormal(self.mu, self.sigma, size=n)
        return np.clip(np.rint(raw), 1, self.seq_len).astype(np.int64)

    def eos_positions(self) -> np.ndarray:
        """Where one row's documents end: each document's length, plus one
        for its ``eos``, laid end to end until the row is full."""
        ends, n = [], 0
        while True:
            (length,) = self.lengths(1)
            n += int(length)
            if n >= self.seq_len:
                return np.asarray(ends, np.int64)
            ends.append(n)
            n += 1

    def sample(self, batch: int) -> np.ndarray:
        out = self.src.sample(batch, self.seq_len)
        for row in out:
            row[self.eos_positions()] = self.eos
        return out
