"""``tt`` — tensor-train factorized embedding tables (the TT-Rec baseline,
arXiv:2101.11714).

The concatenated logical [total_rows, dim] table is reshaped to a 3-way
tensor [n1·n2·n3, d1·d2·d3] (n1·n2·n3 ≥ total_rows, d1·d2·d3 = dim) and
stored as three TT cores

    G1 [n1, d1, r]   G2 [n2, r, d2, r]   G3 [n3, r, d3]

Row ``g`` decomposes mixed-radix into (i1, i2, i3); its embedding is the
chain contraction G1[i1] · G2[i2] · G3[i3] reshaped to [dim] — the rows
are never materialized, so the trained parameter count is
O(n^(1/3) · d · r²) instead of O(n · d).  Cores are replicated (the
substrate is small by construction): lookups are local gathers + two tiny
einsums, batches shard over the whole mesh, same serving story as ROBE.

Lookups go through the fused ``kernels/ops.tt_lookup`` op: with
``spec.use_kernel`` the mixed-radix index decomposition, the three
VMEM-resident core gathers, and the chain contraction run in one Pallas
pass (``kernels/tt_lookup.py``; interpret mode only — the TPU compiler
refuses its in-kernel gathers, so it raises on a TPU); otherwise the same
math runs as the jnp reference path.
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


@functools.lru_cache(maxsize=128)
def factor_rows(n: int) -> Tuple[int, int, int]:
    """(n1, n2, n3) with n1·n2·n3 ≥ n, each ≈ n^(1/3)."""
    n3 = max(1, int(round(n ** (1.0 / 3.0))))
    n2 = max(1, int(round((n / n3) ** 0.5)))
    n1 = -(-n // (n2 * n3))
    return n1, n2, n3


@functools.lru_cache(maxsize=128)
def factor_dim(d: int) -> Tuple[int, int, int]:
    """(d1, d2, d3) exact factorization of d, as balanced as possible."""
    best, best_key = (d, 1, 1), d
    for d1 in range(1, d + 1):
        if d % d1:
            continue
        rest = d // d1
        for d2 in range(1, rest + 1):
            if rest % d2:
                continue
            d3 = rest // d2
            key = max(d1, d2, d3)
            if key < best_key:
                best, best_key = (d1, d2, d3), key
    return best


def _rank(spec) -> int:
    return int(spec.tt_rank) if spec.tt_rank > 0 else 8


class TensorTrainBackend(EmbeddingBackend):
    name = "tt"
    local_batch = True

    def _dims(self, spec):
        return factor_rows(spec.total_rows), factor_dim(spec.dim), _rank(spec)

    def init(self, key, spec, pad_rows_to: int = 1) -> dict:
        (n1, n2, n3), (d1, d2, d3), r = self._dims(spec)
        k1, k2, k3 = jax.random.split(key, 3)
        # e = Σ_{p,q} G1·G2·G3 sums r² products: std(e) ≈ r·σ³ — pick σ so
        # rows come out at the full table's 1/√dim scale
        sigma = (1.0 / (np.sqrt(spec.dim) * r)) ** (1.0 / 3.0)
        return {
            "core0": jax.random.normal(k1, (n1, d1, r), jnp.float32) * sigma,
            "core1": jax.random.normal(k2, (n2, r, d2, r),
                                       jnp.float32) * sigma,
            "core2": jax.random.normal(k3, (n3, r, d3), jnp.float32) * sigma,
        }

    def lookup(self, params, spec, idx, fields=None):
        from repro.kernels.ops import tt_lookup
        fields = fields if fields is not None else tuple(range(spec.n_fields))
        factors, _, _ = self._dims(spec)
        # static per-field offsets: the fused op runs the mixed-radix index
        # decomposition in-path (in-kernel when spec.use_kernel)
        off = tuple(int(spec.offsets[f]) for f in fields)
        return tt_lookup(params["core0"], params["core1"], params["core2"],
                         idx, off, factors, spec.dim, spec.use_kernel)

    def param_specs(self, spec, rules, mesh=None) -> dict:
        # replicated on every mesh: a degraded mesh changes nothing, the
        # elastic restore just re-broadcasts the cores to the survivors
        return {"core0": P(), "core1": P(), "core2": P()}

    def param_count(self, spec) -> int:
        (n1, n2, n3), (d1, d2, d3), r = self._dims(spec)
        return n1 * d1 * r + n2 * r * d2 * r + n3 * r * d3


register_backend(TensorTrainBackend())
