"""The general traffic generator: rows of token ids drawn from the seed.

A traffic mix is a data file (``bench/traffic/<mix>.json``) of the
sizes this generator and the mix's driver read: batch, sequence and
prompt lengths, save or snapshot periods, the optimizer's settings.
Every row of every batch is drawn independently and uniformly over the
vocabulary, so rows all differ and every seed gives the same sizes.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


class TokenRows:
    """A batch per step, a pure function of (seed, step).

    It stands in for the training data pipeline: ``state`` and
    ``restore_state`` are the data cursor the trainer saves and
    restores with its image."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 step: int = 0):
        self.seed = seed & (2 ** 64 - 1)
        self.batch, self.seq, self.vocab = batch, seq, vocab
        self.step = step

    def peek(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        return {"tokens": rng.integers(0, self.vocab, (self.batch, self.seq),
                                       dtype=np.int32)}

    def next(self) -> Dict[str, np.ndarray]:
        out = self.peek(self.step)
        self.step += 1
        return out

    def state(self) -> Dict[str, Any]:
        return {"seed": self.seed, "step": self.step}

    def restore_state(self, st: Dict[str, Any]) -> None:
        self.seed, self.step = st["seed"], st["step"]
