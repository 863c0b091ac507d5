"""The one general traffic generator. A traffic mix is a data file under
``benchmark/traffic``; this module turns (file, --seed) into the inputs
of a run. One kind today: ``train_job``. The serving kinds (open and
closed loop) belong to the PR that proves a serving cell on the chip
(``PERF.md`` section 7).

Steadiness: every seed gives the same amount and shape of work (the
file fixes sizes and counts); the seed draws the token ids and, through
the engine, the weights.
"""

from __future__ import annotations

import numpy as np


def train_batches(traffic: dict, seed: int, n_chips: int, vocab: int):
    """The pool of distinct batches a training job cycles through:
    ``batch_pool`` arrays [sequences_per_chip * n_chips, seq_len + 1] of
    token ids drawn on the host from the seed (inputs are [:, :-1],
    targets [:, 1:])."""
    rng = np.random.default_rng([int(seed), 31])
    b = int(traffic["sequences_per_chip"]) * n_chips
    s = int(traffic["seq_len"])
    return [rng.integers(0, vocab, size=(b, s + 1), dtype=np.int32)
            for _ in range(int(traffic["batch_pool"]))]
