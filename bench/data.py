"""Training feed made from ``--seed``: a Zipfian token corpus with bigram
structure, cut into consecutive windows, so every row of every step
differs. The corpus generator is a copy of the program's
``data/pipeline.synthetic_corpus``; the benchmark keeps its own so that its
inputs stay fixed whatever a later change does to the program."""
from __future__ import annotations

import numpy as np


def synthetic_corpus(vocab: int, n_tokens: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks
    probs /= probs.sum()
    toks = rng.choice(vocab, size=n_tokens, p=probs).astype(np.int32)
    toks[1::2] = (toks[0::2][: len(toks[1::2])] + 1) % vocab
    return toks


class Windows:
    """Iterator of ``{"tokens", "labels"}`` batches ``[batch, seq]``:
    consecutive windows of ``seq + 1`` corpus tokens from a seeded offset,
    wrapping round the corpus."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int, *,
                 n_tokens: int = 1 << 20):
        self.corpus = synthetic_corpus(vocab, n_tokens, seed)
        self.seq, self.batch = seq, batch
        self.cursor = int(np.random.default_rng(seed).integers(n_tokens))

    def __iter__(self):
        return self

    def __next__(self):
        n, w = len(self.corpus), self.seq + 1
        idx = (self.cursor + np.arange(self.batch * w)) % n
        self.cursor = (self.cursor + self.batch * w) % n
        arr = self.corpus[idx].reshape(self.batch, w)
        return {"tokens": arr[:, :-1], "labels": arr[:, 1:]}
