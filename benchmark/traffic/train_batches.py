"""Training batches: [global_batch, seq_len + 1] token ids, uniform over
the vocabulary, all from the seed. Stands for packed documents (every
position carries a target; no padding).

``distinct_batches`` (the cell's traffic parameter) is the length of the
data's cycle: step i trains on batch i mod distinct_batches, put on the
devices anew every step. Uniform random tokens carry nothing to learn but
themselves: with a fresh batch every step the loss of 41 steps moved by
+-0.01 either way (my chip runs, PR 24), so "the loss fell" was a coin
toss on the seed. On a cycle the model fits the batches it sees again and
the loss on batch 0 falls on every seed."""

from __future__ import annotations

import numpy as np


def plan(params: dict, seed: int, seconds: float) -> dict:
    return {"mode": "train", "seed": int(seed),
            "distinct_batches": int(params.get("distinct_batches", 1))}


def batch(seed: int, step: int, global_batch: int, seq_len: int,
          vocab_size: int) -> np.ndarray:
    """Pure function of its arguments."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(step)])
    return rng.integers(0, vocab_size, (global_batch, seq_len + 1),
                        dtype=np.int32)
