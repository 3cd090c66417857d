"""The generator of token traffic for the ``lm_train`` driver kind: full
sequences of ids drawn from a seed and a traffic file's parameters.

Every seed gives the same number of sequences of the same length, so the
work of a run does not depend on the seed; only the ids do.  ``ids:
"uniform"`` draws every id uniformly from the vocabulary held (the slice
``[0, vocab)``): no padding, no document boundary.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def make_sequences(traffic: Dict, seed: int, vocab: int, count: int):
    """``count`` distinct sequences, (count, seq_len) int32."""
    if traffic["ids"] != "uniform":
        raise ValueError(f"unknown id distribution {traffic['ids']!r}")
    rng = np.random.RandomState(seed % (2 ** 32))
    return rng.randint(0, vocab, (count, traffic["seq_len"])).astype(np.int32)


def token_source(sequences, rows: int):
    """The distinct sequences repeated in order up to ``rows`` rows: what
    the program's loader reads, in a fixed order."""
    return sequences[np.arange(rows) % len(sequences)]


def reference_batches(sequences, batch: int, steps: int) -> List:
    """The first ``steps`` batches as the loader assembles them from the
    source in order."""
    return [sequences[(s * batch + np.arange(batch)) % len(sequences)]
            for s in range(steps)]
