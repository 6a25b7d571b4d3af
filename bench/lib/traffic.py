"""The inputs a cell's mix asks for, made from the seed: the calibration
token ids of a quantize mix, and the seeded choices of a run (which block
the check reads)."""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, salt: str) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), sum(map(ord, salt))])


def calib_tokens(n_seqs: int, seq_len: int, vocab: int, seed: int) -> np.ndarray:
    """Calibration token ids, Zipf-distributed over the vocabulary (text's
    rank-frequency law), (n_seqs, seq_len) int32."""
    rng = rng_for(seed, "calib")
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / ranks
    p /= p.sum()
    ids = rng.permutation(vocab)  # which id holds which rank
    draws = rng.choice(vocab, size=(n_seqs, seq_len), p=p)
    return ids[draws].astype(np.int32)
