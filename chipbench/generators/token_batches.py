"""Training batches drawn from the seed: ``rows`` sequences of ``seq_len``
token ids, Zipf-distributed (a few ids take most of the mass, as in text), a
fresh batch every step so no two rows of a run are alike."""

import numpy as np


def batches(traffic, seed, vocab):
    """An endless iterator of int32 [rows, seq_len] arrays."""
    rs = np.random.RandomState(seed)
    shape = (traffic["rows"], traffic["seq_len"])
    while True:
        yield (np.minimum(rs.zipf(traffic["zipf_a"], shape), vocab)
               .astype(np.int32) - 1)
