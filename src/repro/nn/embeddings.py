"""Embedding front-end: ``EmbeddingSpec`` + the ``EmbeddingBackend`` API.

The paper's entire comparison axis is "same model, different embedding
substrate".  That axis is a *protocol*, not an if-branch: every substrate
is an ``EmbeddingBackend`` (``repro.nn.embedding_backends``) registered by
name and selected via ``EmbeddingSpec.kind``:

* ``"full"``   — the uncompressed baseline.  All fields' tables concatenate
  into one [total_rows, dim] blob, row-sharded over `model` on the
  production mesh (the classic model-parallel DLRM layout); the distributed
  lookup is a masked local gather + ``psum_scatter`` (≡ the Neo-style
  all_to_all exchange).  ``placement="2d"`` shards rows over the whole mesh.
* ``"robe"``   — the paper's technique: one tiny shared ROBE array replaces
  every table, replicated, lookups purely local — the embedding exchange
  disappears.  ``placement="model"`` shards the array ZeRO-3 style and
  all-gathers it per step (arrays beyond HBM; beyond-paper extension).
* ``"hashed"`` — QR compositional hashing-trick baseline (quotient ×
  remainder tables, collision-free pair decomposition).
* ``"tt"``     — tensor-train factorized tables (TT-Rec baseline): three
  small cores, rows contracted on the fly.

Each backend owns its init, lookups, PartitionSpec tree (consumed by
``repro.dist.param_specs``), distributed shard_map bodies, and parameter
count — ``get_backend(spec.kind)`` is the only dispatch point.

``embedding_init`` / ``embedding_lookup`` / ``embedding_lookup_bag`` below
are thin wrappers over the backend so existing callers keep working.  JAX
has no EmbeddingBag: multi-hot lookups are gather + segment reduction in
every backend, as the assignment requires.

Multi-hot fields (``EmbeddingSpec.bag_sizes``): a model whose field ``f``
carries ``bag_sizes[f]`` ids a sample takes its ids as ``[B, ΣL]``
columns, each tied to its field (``EmbeddingSpec.columns``).  The backend
looks every column up under its own field, and ``pool_bags`` sums each
field's columns (``embedding_lookup_dist``) to ``[B, F, dim]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.robe import RobeSpec
from repro.nn.embedding_backends import (backend_names,            # noqa: F401
                                         full_lookup_sharded_body,
                                         get_backend,
                                         robe_allgather_body)

__all__ = ["EmbeddingSpec", "embedding_init", "embedding_lookup",
           "embedding_lookup_bag", "embedding_lookup_dist", "pool_bags",
           "get_backend", "backend_names", "full_lookup_sharded_body",
           "robe_allgather_body"]


@dataclasses.dataclass(frozen=True)
class EmbeddingSpec:
    vocab_sizes: Tuple[int, ...]          # rows per categorical field
    dim: int
    kind: str = "robe"                    # any registered backend name
    robe: Optional[RobeSpec] = None
    use_kernel: bool = False              # fused Pallas lookup path (robe /
    #   hashed / tt kernels; interpret mode off-TPU)
    placement: str = "default"            # backend-interpreted layout knob:
    #   full: "default"/"model" row-shard | "2d" whole-mesh row-shard
    #   robe: "default" replicated | "model" ZeRO-3 sharded + all-gather
    hashed_buckets: int = 0               # QR remainder buckets (0 = auto)
    tt_rank: int = 0                      # TT core rank (0 = default 8)
    bag_sizes: Tuple[int, ...] = ()       # ids a sample carries per field,
    #   summed within the field; () = one id per field

    def __post_init__(self):
        object.__setattr__(self, "vocab_sizes",
                           tuple(int(v) for v in self.vocab_sizes))
        object.__setattr__(self, "bag_sizes",
                           tuple(int(n) for n in self.bag_sizes))
        if not self.vocab_sizes:
            raise ValueError("vocab_sizes must be non-empty")
        if any(v <= 0 for v in self.vocab_sizes):
            raise ValueError(f"vocab_sizes must be positive, got "
                             f"{self.vocab_sizes}")
        if self.dim <= 0:
            raise ValueError(f"dim must be positive, got {self.dim}")
        if self.bag_sizes and (len(self.bag_sizes) != len(self.vocab_sizes)
                               or min(self.bag_sizes) < 1):
            raise ValueError(f"bag_sizes must give every one of the "
                             f"{len(self.vocab_sizes)} fields at least one "
                             f"id, got {self.bag_sizes}")
        get_backend(self.kind).validate(self)

    @property
    def n_fields(self) -> int:
        return len(self.vocab_sizes)

    @property
    def total_rows(self) -> int:
        return int(sum(self.vocab_sizes))

    @functools.cached_property
    def offsets(self) -> np.ndarray:
        """Per-field row offsets into the concatenated logical table.
        Cached: lookups index this on every trace."""
        return np.concatenate([[0], np.cumsum(self.vocab_sizes)[:-1]]
                              ).astype(np.int64)

    @functools.cached_property
    def columns(self) -> Tuple[int, ...]:
        """The field of each id column of a batch: ``range(n_fields)``, or
        field ``f`` repeated ``bag_sizes[f]`` times."""
        sizes = self.bag_sizes or (1,) * self.n_fields
        return tuple(f for f, n in enumerate(sizes) for _ in range(n))

    @functools.cached_property
    def column_offsets(self) -> np.ndarray:
        """Per-column row offsets into the concatenated logical table."""
        return self.offsets[list(self.columns)]

    @property
    def param_count(self) -> int:
        return get_backend(self.kind).param_count(self)

    @property
    def compression(self) -> float:
        return (self.total_rows * self.dim) / max(1, self.param_count)


# ---------------------------------------------------------------------------
# thin compatibility wrappers over the backend protocol
# ---------------------------------------------------------------------------

def embedding_init(key: jax.Array, spec: EmbeddingSpec,
                   pad_rows_to: int = 1) -> dict:
    return get_backend(spec.kind).init(key, spec, pad_rows_to=pad_rows_to)


def embedding_lookup(params: dict, spec: EmbeddingSpec,
                     idx: jnp.ndarray,
                     fields: Optional[Tuple[int, ...]] = None) -> jnp.ndarray:
    """idx [B, F'] int32 per-field row ids -> [B, F', dim] embeddings.

    ``fields`` selects a subset of the spec's fields (default: all, in
    order) — e.g. the item-side fields for retrieval candidate scoring.
    """
    return get_backend(spec.kind).lookup(params, spec, idx, fields)


def embedding_lookup_bag(params: dict, spec: EmbeddingSpec,
                         idx: jnp.ndarray,
                         combiner: str = "sum",
                         weights: Optional[jnp.ndarray] = None
                         ) -> jnp.ndarray:
    """idx [B, F, bag] (−1 padded) -> [B, F, dim]; optional per-sample
    ``weights`` [B, F, bag] (mean divides by the weight mass)."""
    return get_backend(spec.kind).lookup_bag(params, spec, idx,
                                             combiner=combiner,
                                             weights=weights)


def pool_bags(emb: jnp.ndarray, spec: EmbeddingSpec) -> jnp.ndarray:
    """[B, ΣL, dim] rows of every id column -> [B, F, dim]: the sum, in
    float32 and column order, of each field's ``bag_sizes[f]`` columns (a
    static segment sum, under the ``bag_pool`` scope).  One-hot specs pass
    ``emb`` through.

    The sum adds ``dim``-wide slices of the rows' flat ``[B, ΣL·dim]``
    view (on a TPU a slice of the 3-D array along its column axis takes a
    layout padded up to 128 times), and writes each field's sum into its
    slot of the output: the compiler then keeps the sums in fusions of
    their own under this scope, where it merges a concatenation of them
    into the consumer's with no scope at all."""
    if not spec.bag_sizes:
        return emb
    with jax.named_scope("bag_pool"):
        b, c, d = emb.shape
        flat = emb.astype(jnp.float32).reshape(b, c * d)
        out = jnp.zeros((b, spec.n_fields * d), jnp.float32)
        col = 0
        for f, n in enumerate(spec.bag_sizes):
            acc = flat[:, col * d:(col + 1) * d]
            for j in range(col + 1, col + n):
                acc = acc + flat[:, j * d:(j + 1) * d]
            out = jax.lax.dynamic_update_slice(out, acc, (0, f * d))
            col += n
        return out.reshape(b, spec.n_fields, d)


def embedding_lookup_dist(params: dict, spec: EmbeddingSpec,
                          idx: jnp.ndarray,
                          compute_dtype=None) -> jnp.ndarray:
    """idx [B, ΣL] id columns -> [B, F, dim] under the active ``repro.dist``
    context (local lookup outside one).  The shard_map bodies live in the
    backends; multi-hot fields are pooled after the lookup."""
    emb = get_backend(spec.kind).lookup_dist(params, spec, idx,
                                             compute_dtype=compute_dtype)
    return pool_bags(emb, spec)
