"""The token stream the LM cells train on, and the window starts a node's
loader draws from it.

A copy owned by the benchmark: a Zipfian unigram mixed with a sparse
bigram successor table, so that a real model lowers its loss on it.  The
stream is a pure function of its seed.  ``loader_starts`` replays the rule
by which a per-node loader draws ``seq_len`` windows from contiguous,
disjoint shards (one ``integers`` call per node, in node order), so the
reference trains on the rows the program trained on without reading them
from the program.
"""

from __future__ import annotations

import numpy as np


def make_token_stream(num_tokens: int, vocab_size: int, seed: int,
                      order: int = 2) -> np.ndarray:
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab_size + 1)
    probs /= probs.sum()
    succ = rng.integers(0, vocab_size, size=(vocab_size, order))
    toks = np.empty(num_tokens, dtype=np.int32)
    toks[0] = rng.choice(vocab_size, p=probs)
    follow = rng.random(num_tokens) < 0.6
    draws = rng.choice(vocab_size, size=num_tokens, p=probs)
    picks = rng.integers(0, order, size=num_tokens)
    for t in range(1, num_tokens):
        toks[t] = succ[toks[t - 1], picks[t]] if follow[t] else draws[t]
    return toks


def node_shards(tokens: np.ndarray, m: int) -> np.ndarray:
    """(m, shard_len) contiguous shards; the trailing remainder is dropped."""
    n = len(tokens) // m
    return np.stack([tokens[i * n:(i + 1) * n] for i in range(m)])


class StartReplay:
    """Draws (m, batch) window starts exactly as a seeded per-node loader
    does: starts lie in ``[0, shard_len - seq_len - 1)``."""

    def __init__(self, seed: int, m: int, shard_len: int, seq_len: int):
        self._rng = np.random.default_rng(seed)
        self.m = m
        self.hi = shard_len - seq_len - 1

    def draw(self, batch: int) -> np.ndarray:
        return np.stack([self._rng.integers(0, self.hi, size=batch)
                         for _ in range(self.m)])


def gather_windows(shards: np.ndarray, starts: np.ndarray, seq_len: int):
    """(m, B) starts -> tokens, labels (m, B, seq_len) int32."""
    idx = starts[:, :, None] + np.arange(seq_len + 1)[None, None, :]
    full = np.take_along_axis(shards[:, None, :], idx.astype(np.int64),
                              axis=2)
    return (np.ascontiguousarray(full[:, :, :seq_len], dtype=np.int32),
            np.ascontiguousarray(full[:, :, 1:], dtype=np.int32))
