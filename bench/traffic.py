"""Training traffic: synthetic language-model batches from a seed.

A Zipf-weighted sparse Markov chain over the vocabulary (each token has
``branch`` likely successors), so that next-token statistics are not
trivial; the same construction as the program's
``repro.data.synthetic.token_stream``, written in numpy on the host so
that making a batch costs the device nothing.  Batch ``i`` depends only
on (seed, i): every step gets rows of its own, and every seed the same
shapes.
"""

from __future__ import annotations

import numpy as np


def markov_table(seed: int, vocab: int, branch: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 1])
    p = 1.0 / np.arange(1, vocab + 1)
    return rng.choice(vocab, size=(vocab, branch), p=p / p.sum()).astype(
        np.int32)


def batch(seed: int, i: int, table: np.ndarray, *, accum: int, rows: int,
          seq: int):
    """(tokens, labels), int32 (accum, rows, seq): rows of next-token
    pairs for step ``i``."""
    rng = np.random.default_rng([seed, 2, i])
    n = accum * rows
    toks = np.empty((n, seq + 1), np.int32)
    toks[:, 0] = rng.integers(0, table.shape[0], n)
    choice = rng.integers(0, table.shape[1], (n, seq))
    for t in range(seq):
        toks[:, t + 1] = table[toks[:, t], choice[:, t]]
    toks = toks.reshape(accum, rows, seq + 1)
    return toks[..., :-1], toks[..., 1:]
