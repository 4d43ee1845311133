"""Training batches: a fresh batch of token ids every step, uniform over
the vocabulary, made on the host from `--seed` alone. Every row differs."""
from __future__ import annotations

import numpy as np


class TokenBatches:
    def __init__(self, batch: int, seq: int, vocab: int, seed: int):
        self.shape = (int(batch), int(seq) + 1)
        self.vocab = int(vocab)
        self._rng = np.random.default_rng([int(seed), 1])

    def next_batch(self) -> np.ndarray:
        """[batch, seq + 1] int32: inputs are [:, :-1], targets [:, 1:]."""
        return self._rng.integers(0, self.vocab, self.shape, dtype=np.int32)


def make(traffic: dict, config: dict, seed: int, seconds: float):
    del traffic, seconds          # the mix has no parameter of its own yet
    return TokenBatches(config["sizing"]["global_batch"],
                        config["sizing"]["seq_len"],
                        config["model"]["vocab_size"], seed)
