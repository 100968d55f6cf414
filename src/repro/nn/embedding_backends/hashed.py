"""``hashed`` — the QR / compositional hashing-trick baseline.

Quotient–remainder composition (Shi et al., the family surveyed in
"Embedding Compression in Recommender Systems"): each field keeps ``m``
remainder buckets and ``ceil(vocab/m)`` quotient buckets; row ``x``'s
embedding is the elementwise product

    e(x) = Q[x // m] * R[x % m]

which is collision-free as a pair (x ↦ (x//m, x%m) is injective) while
training only O(m + vocab/m) rows per field instead of O(vocab).  Both
tables are concatenated across fields (like the ``full`` blob) and
replicated — the substrate is small by construction, so lookups are local
and batches shard over the whole mesh, same serving story as ROBE.

Lookups go through the fused ``kernels/ops.qr_lookup`` op: with
``spec.use_kernel`` both row gathers (DMA from the tables in HBM) and the
product run in one Pallas pass (``kernels/qr_lookup.py``); otherwise the same math runs as the jnp
reference path.

``m`` defaults to the power of two nearest √(max vocab), the
memory-optimal split.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.nn.embedding_backends.base import EmbeddingBackend, \
    register_backend


def default_buckets(vocab_sizes: Tuple[int, ...]) -> int:
    """Power of two nearest √(max vocab) — minimizes m + max_v/m."""
    v = max(vocab_sizes)
    m = 1
    while m * m < v:
        m *= 2
    return max(2, m)


@functools.lru_cache(maxsize=128)
def qr_layout(vocab_sizes: Tuple[int, ...], m: int):
    """(q_rows, q_offsets, r_offsets): concatenated-table row layout."""
    q_rows = tuple(-(-int(v) // m) for v in vocab_sizes)
    q_off = np.concatenate([[0], np.cumsum(q_rows)[:-1]]).astype(np.int64)
    r_off = (np.arange(len(vocab_sizes), dtype=np.int64) * m)
    return q_rows, q_off, r_off


def _m(spec) -> int:
    return int(spec.hashed_buckets) if spec.hashed_buckets > 0 \
        else default_buckets(spec.vocab_sizes)


class HashedBackend(EmbeddingBackend):
    name = "hashed"
    local_batch = True

    def init(self, key, spec, pad_rows_to: int = 1) -> dict:
        m = _m(spec)
        q_rows, _, _ = qr_layout(spec.vocab_sizes, m)
        kq, kr = jax.random.split(key)
        scale = 1.0 / np.sqrt(spec.dim)
        # product composition: |q·r| ~ scale² ≈ the full table's row scale
        # once both factors carry √scale
        s = np.sqrt(scale)
        q = jax.random.uniform(kq, (sum(q_rows), spec.dim), jnp.float32,
                               -s, s)
        r = jax.random.uniform(kr, (m * spec.n_fields, spec.dim),
                               jnp.float32, -s, s)
        return {"q_table": q, "r_table": r}

    def lookup(self, params, spec, idx, fields=None):
        from repro.kernels.ops import qr_lookup
        fields = fields if fields is not None else tuple(range(spec.n_fields))
        m = _m(spec)
        _, q_off, r_off = qr_layout(spec.vocab_sizes, m)
        # static per-field offsets: the fused op computes the quotient /
        # remainder indices in-path (in-kernel when spec.use_kernel)
        qo = tuple(int(q_off[f]) for f in fields)
        ro = tuple(int(r_off[f]) for f in fields)
        return qr_lookup(params["q_table"], params["r_table"], idx,
                         qo, ro, m, spec.use_kernel)

    def cacheable_rows(self, params, spec, field: int,
                       ids: np.ndarray) -> np.ndarray:
        """Hot-row-cache hook: recompose Q[x//m] * R[x%m] on the host for
        ``ids`` in ``field`` — same f32 elementwise product (single
        rounding) as the jnp reference path, so cached serve scores stay
        bit-exact.  Caching the *composed* row also skips the recomposition
        multiply on every hot hit, not just the two fetches."""
        m = _m(spec)
        _, q_off, r_off = qr_layout(spec.vocab_sizes, m)
        ids = np.asarray(ids, np.int64)
        q = np.asarray(params["q_table"])
        r = np.asarray(params["r_table"])
        return q[ids // m + int(q_off[field])] * r[ids % m + int(r_off[field])]

    def affected_rows(self, spec, field: int, touched: np.ndarray,
                      candidates: np.ndarray) -> np.ndarray:
        """Push-invalidation hook: training id x moves bucket rows
        Q[x//m] and R[x%m], so every candidate sharing a quotient OR
        remainder bucket with a touched id has a changed composed row —
        exact-id invalidation would leave those cache entries stale."""
        m = _m(spec)
        t = np.asarray(touched, np.int64).ravel()
        c = np.asarray(candidates, np.int64).ravel()
        return (np.isin(c // m, np.unique(t // m))
                | np.isin(c % m, np.unique(t % m)))

    def param_specs(self, spec, rules, mesh=None) -> dict:
        # replicated on every mesh: a degraded mesh changes nothing, the
        # elastic restore just re-broadcasts both tables to the survivors
        return {"q_table": P(), "r_table": P()}

    def param_count(self, spec) -> int:
        m = _m(spec)
        q_rows, _, _ = qr_layout(spec.vocab_sizes, m)
        return (sum(q_rows) + m * spec.n_fields) * spec.dim


register_backend(HashedBackend())
