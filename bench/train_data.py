"""The one training-batch generator: it reads a training mix's parameters
from ``bench/traffic/<mix>.json`` and turns them, with the run's seed, into
the batches of a closed-loop job (the next step starts when the last one
returns, so there are no arrival times).

Token ids follow a Zipf law of the mix's ``exponent`` over the vocabulary
(exponent 0 is uniform): the id of rank r (r = 1, 2, ...) has probability
proportional to r ** -exponent.  Which id holds which rank is a permutation
drawn from the mix's ``schedule_seed``, the same for every run; the run's
seed draws the tokens.  Each row is ``seq_len + 1`` tokens: the inputs are
the first ``seq_len``, the labels the next token at each position.  Every
batch is made at set-up; step ``i`` reads batch ``i mod batches``, so the
first ``batches`` steps see rows that all differ.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import seed_sequence


def rank_probabilities(vocab: int, exponent: float) -> np.ndarray:
    """P(rank r) for r = 1..vocab, proportional to r ** -exponent."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    return w / w.sum()


class TrainBatches:
    """The batches of one run: ``batch_at(step)`` -> {"tokens", "labels"},
    each (global_batch, seq_len) int32."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        spec = mix["tokens"]
        if spec["distribution"] != "zipf":
            raise ValueError(f"unknown token distribution "
                             f"{spec['distribution']!r}")
        self.seq_len = int(mix["seq_len"])
        self.global_batch = int(mix["global_batch"])
        n = int(mix["batches"])
        order = np.random.default_rng(seed_sequence(mix["schedule_seed"]))
        self.rank_to_id = order.permutation(vocab).astype(np.int32)
        cdf = np.cumsum(rank_probabilities(vocab, spec["exponent"]))
        rng = np.random.default_rng(seed_sequence(seed))
        u = rng.random((n, self.global_batch, self.seq_len + 1))
        ranks = np.minimum(np.searchsorted(cdf, u, side="right"), vocab - 1)
        self.rows = self.rank_to_id[ranks]

    def __len__(self) -> int:
        return len(self.rows)

    def batch_at(self, step: int) -> dict:
        rows = self.rows[step % len(self.rows)]
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
