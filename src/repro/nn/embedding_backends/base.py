"""The ``EmbeddingBackend`` protocol and registry.

An embedding *backend* is one substrate for the model's categorical
features: a way to store the logical [total_rows, dim] table and answer
row lookups.  The paper's comparison axis — full table vs ROBE array — is
two instances of this protocol; ``hashed`` (QR compositional hashing) and
``tt`` (tensor-train factorization) are the community baselines it is
benchmarked against.  Everything the rest of the stack needs to know about
a substrate hangs off the backend object:

* ``init(key, spec, pad_rows_to)``      -> parameter pytree
* ``lookup(params, spec, idx, fields)`` -> [B, F', dim] embeddings
* ``lookup_bag(params, spec, idx, ...)``-> pooled multi-hot lookups
* ``lookup_dist(params, spec, idx)``    -> the distributed lookup of every
  id column (``spec.columns``) under the active ``repro.dist`` context
  (shard_map bodies live in the backend, not in the model)
* ``param_specs(spec, rules, mesh=None)`` -> PartitionSpec tree for the
  parameter pytree (consumed by ``repro.dist.param_specs.recsys_specs``);
  ``mesh`` re-resolves the layout against a concrete — possibly degraded —
  mesh (the elastic re-slice contract, see ``repro.train.elastic``)
* ``cost(spec, batch)``                 -> {"params", "bytes_fetched",
  "flops"} — the roofline/benchmark cost model, owned by the substrate
* ``local_batch``                       — True when lookups need no
  model-axis exchange, so recsys batches may shard over the WHOLE mesh

Backends self-register at import (``repro.nn.embedding_backends``
imports all four); ``get_backend(name)`` is the only dispatch point —
no ``kind == "robe"`` string branches exist outside backend modules.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


# canonical axis-normalization helpers live in dist.api (the spec trees
# backends build must agree with the ones prune_specs re-resolves);
# re-exported here because every backend module imports them from base
from repro.dist.api import (axes_entry, axes_on_mesh,      # noqa: F401
                            axes_tuple)


class EmbeddingBackend:
    """Base class: generic bag pooling + replicated-local distribution."""

    name: str = ""
    #: lookups are device-local (no model-axis embedding exchange) — the
    #: batch may shard over every mesh axis (the "flat_batch" rule)
    local_batch: bool = True
    #: optional serving-tier hot-row-cache hook: a fetch-bound backend
    #: overrides this with a method ``cacheable_rows(params, spec, field,
    #: ids) -> [n, dim]`` float32 host rows that are BIT-IDENTICAL to what
    #: ``lookup`` would gather for those ids in that field — the contract
    #: ``serve/hot_cache.HotRowCache`` rests on for exact score parity.
    #: ``None`` (the default) declines the cache: robe declines because the
    #: whole array is already cache-resident (the paper's point — fronting
    #: it with another cache would muddy the full-vs-robe comparison); tt
    #: declines because its cost is the core contraction, not the fetch.
    cacheable_rows = None
    #: optional push-invalidation companion to ``cacheable_rows``: given the
    #: ids a model push *trained* in a field, which cached ids' composed
    #: rows changed?  A backend whose stored rows are shared across ids
    #: (``hashed``: training id x moves bucket rows x//m and x%m, so every
    #: id sharing either bucket recomposes differently) overrides this with
    #: a method ``affected_rows(spec, field, touched_ids, candidate_ids) ->
    #: [n] bool mask over candidate_ids``.  ``None`` means rows are private
    #: per id (``full``) and the cache invalidates by exact id match.
    affected_rows = None
    #: optional post-optimizer projection hook: a backend whose stored
    #: parameters are NOT what the math sees (quantized substrates —
    #: ``qrobe``'s int8 codes behind a learned dequant) overrides this with
    #: a method ``project(params, spec) -> params`` that folds the
    #: optimizer's float update back into the stored representation after
    #: every step (ALPT's dequantize → update → requantize cycle).  ``None``
    #: means "parameters are their own representation" and train loops skip
    #: the call (``repro.train.train_loop.build_train_step(project=...)``,
    #: wired via ``repro.models.recsys.make_project_fn``).
    project = None

    # -- construction ------------------------------------------------------

    def validate(self, spec) -> None:
        """Raise if ``spec`` is not usable with this backend."""

    def init(self, key: jax.Array, spec, pad_rows_to: int = 1) -> dict:
        raise NotImplementedError

    # -- lookups -----------------------------------------------------------

    def lookup(self, params: dict, spec, idx: jnp.ndarray,
               fields: Optional[Tuple[int, ...]] = None) -> jnp.ndarray:
        """idx [B, F'] int32 per-field row ids -> [B, F', dim]."""
        raise NotImplementedError

    def lookup_bag(self, params: dict, spec, idx: jnp.ndarray,
                   combiner: str = "sum",
                   weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """idx [B, F, bag] (−1 padded) -> [B, F, dim].

        JAX has no native EmbeddingBag: every backend pools via gather +
        masked (weighted) segment reduction.  ``weights`` [B, F, bag] are
        per-sample bag weights; ``combiner="mean"`` divides by the weight
        mass (matching ``repro.core.robe.robe_lookup_bag``).
        """
        b, f, bag = idx.shape
        mask = idx >= 0
        safe = jnp.where(mask, idx, 0)
        # fold the bag into the batch so each column keeps its field id
        # (per-field offsets/hashes stay aligned)
        flat = jnp.swapaxes(safe, 1, 2).reshape(b * bag, f)
        emb = jnp.swapaxes(
            self.lookup(params, spec, flat).reshape(b, bag, f, spec.dim),
            1, 2)                                    # [b, f, bag, dim]
        w = mask.astype(emb.dtype)
        if weights is not None:
            w = w * weights.astype(emb.dtype)
        emb = emb * w[..., None]
        out = emb.sum(axis=2)
        if combiner == "mean":
            # divide by the actual weight mass (fractional weights < 1 must
            # not be clamped away); empty bags (mass 0) pool to zero
            mass = w.sum(axis=2, keepdims=True).astype(out.dtype)
            out = jnp.where(mass > 0, out / jnp.where(mass > 0, mass, 1.0),
                            0.0)
        elif combiner != "sum":
            raise ValueError(f"unknown combiner {combiner}")
        return out

    def lookup_dist(self, params: dict, spec, idx: jnp.ndarray, *,
                    compute_dtype=None) -> jnp.ndarray:
        """Lookup under the active DistContext (no-op context → local).

        Default: parameters are replicated and lookups purely local, so the
        batch (and the [B, F, dim] activation) shards over the whole mesh
        when divisible — zero embedding collectives.  ``idx`` holds one
        column per id (``spec.columns`` names each column's field); the
        caller pools multi-hot fields.
        """
        from repro.dist import api as dist
        emb = self.lookup(params, spec, idx, spec.columns)
        ctx = dist.current()
        if ctx is not None and idx.shape[0] % ctx.n_devices == 0:
            emb = dist.shard(emb, "flat_batch", None, None)
        return emb

    # -- metadata ----------------------------------------------------------

    def param_specs(self, spec, rules: Dict, mesh=None) -> dict:
        """PartitionSpec tree matching ``init``'s parameter pytree.

        ``mesh`` (optional): re-resolve the layout against a concrete —
        possibly degraded — mesh instead of the production one the rules
        were written for: axes the mesh no longer carries are dropped
        (elastic re-slice, ``repro.train.elastic``).  Shape divisibility
        on the survivors is the caller's job (``dist.api.prune_specs``).
        """
        raise NotImplementedError

    def param_count(self, spec) -> int:
        raise NotImplementedError

    def cost(self, spec, batch: int) -> dict:
        """Per-step cost model for ``batch`` examples (each example reads
        ``n_fields`` rows of ``dim``): trained parameter count, HBM bytes
        fetched by the lookups, and lookup arithmetic FLOPs."""
        raise NotImplementedError


_REGISTRY: Dict[str, EmbeddingBackend] = {}


def register_backend(backend: EmbeddingBackend) -> EmbeddingBackend:
    if not backend.name:
        raise ValueError("backend must carry a non-empty .name")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> EmbeddingBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown embedding backend {name!r}; registered: "
                       f"{backend_names()}") from None


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
