"""Token batches for training traffic, made from the run's seed.

The row generator is a copy of the program's ``data/pipeline.SyntheticLM``
(kept here so that the yardstick does not move with the program): every
row is a pure function of (seed, step, row). Tokens follow a Zipf-like
unigram law over the vocabulary, with an 8-token motif repeated through
the row, so that a model's loss falls over the first steps. Each row holds
``seq + 1`` tokens: the inputs and, shifted by one, the labels.
"""
from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

SEED_MOD = 2 ** 63


class TokenStream:
    def __init__(self, vocab: int, seq_len: int, seed: int,
                 spec: Mapping = None):
        spec = dict(spec or {})
        if spec.get("unigram", "zipf") != "zipf":
            raise ValueError(f"unknown unigram law {spec['unigram']!r}")
        self.vocab, self.seq_len = int(vocab), int(seq_len)
        self.seed = int(seed) % SEED_MOD
        self.motif_len = int(spec.get("motif_len", 8))
        probs = 1.0 / np.arange(1, self.vocab + 1)
        self._probs = probs / probs.sum()

    def row(self, step: int, row: int) -> np.ndarray:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, int(step), int(row)]))
        s, k = self.seq_len, self.motif_len
        toks = rng.choice(self.vocab, size=(s + 1,),
                          p=self._probs).astype(np.int32)
        motif = rng.integers(0, self.vocab, size=(k,), dtype=np.int32)
        for start in range(0, s - k, max(16, s // 8)):
            toks[start:start + k] = motif
        return toks

    def batch(self, step: int, rows: int) -> Dict[str, np.ndarray]:
        """The global batch of ``step``: ``rows`` rows, every chip's."""
        toks = np.stack([self.row(step, r) for r in range(rows)])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
