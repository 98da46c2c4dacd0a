"""Markov-chain token stream: the benchmark's copy of the program's
``data.pipeline.SyntheticTokens`` generator (same draws for the same seed),
kept here so that a change to the program cannot change the traffic."""
from __future__ import annotations

import numpy as np


class MarkovTokens:
    """Fixed random successor table, ``branch`` successors per token.

    ``batch(step)`` draws the global batch of ``step`` from
    ``default_rng((seed, step))``: any step's tokens can be drawn again,
    in any order, which is what the reference relies on."""

    def __init__(self, vocab_size: int, seq_len: int, global_batch: int,
                 seed: int, branch: int = 16):
        self.vocab_size, self.seq_len = vocab_size, seq_len
        self.global_batch, self.seed, self.branch = global_batch, seed, branch
        rng = np.random.default_rng(seed)
        self.succ = rng.integers(0, vocab_size, size=(vocab_size, branch))

    def _gen(self, rng: np.random.Generator, n: int) -> np.ndarray:
        toks = np.empty((n, self.seq_len + 1), np.int64)
        cur = rng.integers(0, self.vocab_size, size=n)
        for t in range(self.seq_len + 1):
            toks[:, t] = cur
            pick = rng.integers(0, self.branch, size=n)
            cur = self.succ[cur, pick]
        return toks

    def batch(self, step: int) -> dict:
        toks = self._gen(np.random.default_rng((self.seed, step)),
                         self.global_batch)
        pos = np.broadcast_to(np.arange(self.seq_len),
                              (self.global_batch, self.seq_len))
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32),
                "positions": np.ascontiguousarray(pos.astype(np.int32))}
