"""Traffic the benchmark owns: CTR rows.

``CtrStream`` is copied from ``repro.data.synthetic_ctr.CtrStream``, so that a
change to the program cannot move the yardstick, without drift or multi-hot
bags: per-field ids from a power law (``u**(1/zipf)`` squared, times the
field's vocabulary), standard-normal dense features, and labels drawn from a
planted per-(field, value) score.  ``batch_at(step)`` is a pure function of
(seed, step).  ``labels=False`` skips the label draw, which comes last in the
random stream, so ids and dense features are the same either way.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def _field_value_score(field: np.ndarray, value: np.ndarray,
                       seed: int) -> np.ndarray:
    """Deterministic pseudo-random score in [-1, 1] per (field, value)."""
    with np.errstate(over="ignore"):           # uint64 wraparound intended
        h = (value.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
             + field.astype(np.uint64) * np.uint64(0xBF58476D1CE4E5B9)
             + np.uint64(seed % 2**32) * np.uint64(0x94D049BB133111EB))
        h ^= h >> np.uint64(29)
        h *= np.uint64(0xBF58476D1CE4E5B9)
        h ^= h >> np.uint64(32)
    return (h.astype(np.float64) / 2 ** 64) * 2.0 - 1.0


class CtrStream:
    """Step-indexed synthetic CTR batches (host-side numpy)."""

    def __init__(self, vocab_sizes: Sequence[int], n_dense: int,
                 batch_size: int, zipf: float, seed: int,
                 label_temperature: float = 1.2):
        self.vocab = np.asarray(vocab_sizes, np.int64)
        self.fields = np.arange(len(self.vocab), dtype=np.int64)
        self.n_dense = n_dense
        self.batch_size = batch_size
        self.zipf = zipf
        self.seed = int(seed)
        self.label_temperature = label_temperature

    def _ids(self, rs: np.random.RandomState, n: int) -> np.ndarray:
        u = rs.random_sample((n, len(self.vocab)))
        skew = u ** (1.0 / max(1e-6, self.zipf)) if self.zipf != 1.0 else u
        ids = (skew * skew * self.vocab[None, :]).astype(np.int64)
        return np.minimum(ids, self.vocab[None, :] - 1)

    def batch_at(self, step: int, labels: bool = True) -> dict:
        rs = np.random.RandomState((self.seed * 1_000_003 + step) % 2 ** 31)
        n = self.batch_size
        ids = self._ids(rs, n)
        batch = {"dense": rs.randn(n, self.n_dense).astype(np.float32),
                 "sparse": ids.astype(np.int32)}
        if labels:
            score = _field_value_score(
                np.broadcast_to(self.fields[None, :], ids.shape), ids,
                self.seed).mean(axis=1) * 4.0
            score = score + 0.3 * batch["dense"][:, :min(4, self.n_dense)
                                                 ].mean(axis=1)
            prob = 1.0 / (1.0 + np.exp(-score / self.label_temperature))
            batch["label"] = (rs.random_sample(n) < prob).astype(np.int32)
        return batch
