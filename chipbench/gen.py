"""The one traffic generator. A mix is a data file under `traffic/`;
this module turns it and a seed into inputs.

Every seed gets the same amount of work: the mix fixes every size, and
the run's seed chooses only the tokens.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(list(words)))


def markov_tokens(rng: np.random.Generator, vocab: int, rows: int,
                  length: int) -> np.ndarray:
    """Token streams with a learnable pattern: each token follows from
    the one before (t -> 31 t + 17 mod vocab), except one in ten, drawn
    uniformly."""
    toks = np.zeros((rows, length), np.int64)
    toks[:, 0] = rng.integers(0, vocab, rows)
    jump = rng.random((rows, length)) < 0.1
    fresh = rng.integers(0, vocab, (rows, length))
    for t in range(1, length):
        nxt = (toks[:, t - 1] * 31 + 17) % vocab
        toks[:, t] = np.where(jump[:, t], fresh[:, t], nxt)
    return toks


def train_batch(mix: dict, vocab: int, seed: int,
                index: int) -> Dict[str, np.ndarray]:
    """Batch number `index` of a run: `batch` rows of `seq` tokens, and
    the next token of each as its label. Every (seed, index) gives
    other rows."""
    toks = markov_tokens(_rng(seed, index), vocab, mix["batch"],
                         mix["seq"] + 1)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}
