"""Token traffic: Zipf-distributed ids, deterministic in (seed, step).

A copy of the program's ``SyntheticZipf`` (``data/pipeline.py``), kept here
so that no change to the program changes the yardstick. A traffic file
names the law and its parameters; every step's rows differ.
"""
from __future__ import annotations

import numpy as np


class Zipf:
    def __init__(self, vocab: int, alpha: float):
        w = np.arange(1, vocab + 1, dtype=np.float64) ** (-alpha)
        self.cdf = np.cumsum(w / w.sum())
        self.vocab = vocab

    def rows(self, seed: int, step: int, batch: int, length: int) -> np.ndarray:
        rng = np.random.RandomState((seed * 1_000_003 + step) % (2**31))
        ids = np.searchsorted(self.cdf, rng.rand(batch, length))
        return np.minimum(ids, self.vocab - 1).astype(np.int32)


LAWS = {"zipf": Zipf}


class TrainFeed:
    """Global batches of next-token pairs for one traffic file."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        spec = dict(traffic["tokens"])
        law = LAWS[spec.pop("law")]
        cap = spec.pop("vocab_cap", vocab_size)
        self.law = law(min(vocab_size, cap), **spec)
        self.seed = seed
        self.batch, self.seq = traffic["global_batch"], traffic["seq"]

    def __call__(self, step: int) -> dict:
        rows = self.law.rows(self.seed, step, self.batch, self.seq + 1)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
