"""``robe`` — the paper's Random Offset Block Embedding array.

One shared circular array of ``spec.robe.size`` float slots replaces every
table (``repro.core.robe`` holds the hash math; ``repro.kernels.ops`` the
Pallas lookup).  Placement (``spec.placement``):

* ``"default"`` / ``"replicated"`` — the array is tiny (~100 MB for the
  paper's CriteoTB model), so it is replicated and lookups are purely
  local (a ``shard_map`` over the batch axes): the embedding-exchange
  collective disappears and only the |M|-sized gradient all-reduce
  remains.  Batches shard over the whole mesh.
* ``"model"`` — ZeRO-3 style, for ROBE arrays beyond a replica's HBM
  (beyond-paper extension): the array is sharded over `model` and
  all-gathered once per step before the (still-local) lookups; the
  gather's transpose is a reduce-scatter of the slot gradients back to
  their owning shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.robe import init_memory
from repro.nn.embedding_backends.base import (EmbeddingBackend, axes_entry,
                                              axes_on_mesh, axes_tuple,
                                              register_backend)


def robe_allgather_body(mem_shard: jnp.ndarray, model_axis: str
                        ) -> jnp.ndarray:
    """ZeRO-3-style: gather the (sharded) ROBE array before local lookups.

    Called INSIDE shard_map.  Autodiff transposes the tiled all_gather into
    a psum_scatter — slot gradients reduce back to their owning shard.
    """
    return jax.lax.all_gather(mem_shard, model_axis, axis=0, tiled=True)


class RobeBackend(EmbeddingBackend):
    name = "robe"
    local_batch = True           # lookups never exchange over `model`
    #: declines the serving tier's hot-row cache, explicitly: the entire
    #: ROBE array is cache-resident by construction — that IS the paper's
    #: serving claim — so fronting it with a second exact-row cache would
    #: only duplicate rows and muddy the full-vs-robe benchmark
    cacheable_rows = None

    def validate(self, spec) -> None:
        if spec.robe is None:
            raise ValueError("robe spec required for kind='robe'")

    def init(self, key, spec, pad_rows_to: int = 1) -> dict:
        return {"memory": init_memory(key, spec.robe)}

    def lookup(self, params, spec, idx, fields=None):
        from repro.kernels.ops import robe_lookup
        fields = fields if fields is not None else tuple(range(spec.n_fields))
        return robe_lookup(params["memory"], idx, tuple(fields), spec.dim,
                           spec.robe, spec.use_kernel)

    def lookup_dist(self, params, spec, idx, *, compute_dtype=None):
        from repro.dist import api as dist
        ctx = dist.current()
        if ctx is None:
            return super().lookup_dist(params, spec, idx,
                                       compute_dtype=compute_dtype)
        if spec.placement != "model":
            return self._lookup_replicated(params, spec, idx, ctx)
        # ZeRO-3 path: memory sharded over `model`, gathered per step
        mem = params["memory"]
        n_model = ctx.mesh.shape["model"]
        batch = idx.shape[0]
        n_all = ctx.n_devices
        if mem.shape[0] % n_model != 0 or batch % n_all != 0:
            # non-divisible cases: local lookup; GSPMD gathers the memory
            return super().lookup_dist(params, spec, idx,
                                       compute_dtype=compute_dtype)
        dp = ctx.rules.get("batch")
        every = axes_tuple(dp) + ("model",)
        fields = spec.columns

        def body(mem_shard, ix):
            from repro.kernels.ops import robe_lookup
            full = robe_allgather_body(mem_shard, "model")
            # the gathered array is the same on every batch shard; marking
            # it varying there makes its cotangent (which the custom_vjp
            # computes per shard) psum over the batch axes on transpose
            dp_axes = axes_tuple(dp)
            if dp_axes:
                full = jax.lax.pcast(full, dp_axes, to="varying")
            return robe_lookup(full, ix, fields, spec.dim, spec.robe,
                               spec.use_kernel)

        return jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P("model"), P(every, None)),
            out_specs=P(every, None, None))(mem, idx)

    def _lookup_replicated(self, params, spec, idx, ctx):
        """Each device looks up its own rows of the batch in its own copy
        of the array: nothing crosses devices forward, and on transpose
        the per-device gradients of the array psum over the mesh (the
        data-parallel all-reduce).  A batch that does not split over every
        device is left to the compiler."""
        from repro.dist import api as dist
        every = axes_on_mesh(axes_tuple(ctx.rules.get("flat_batch")),
                             ctx.mesh)
        if not every or idx.shape[0] % ctx.n_devices:
            return super().lookup_dist(params, spec, idx)

        def body(mem, ix):
            from repro.kernels.ops import robe_lookup
            return robe_lookup(mem, ix, spec.columns, spec.dim, spec.robe,
                               spec.use_kernel)

        # unchecked: the transpose then psums the array's per-device
        # cotangent over the mesh (the Pallas lookup states no varying axes)
        emb = jax.shard_map(
            body, mesh=ctx.mesh, in_specs=(P(), P(every, None)),
            out_specs=P(every, None, None),
            check_vma=False)(params["memory"], idx)
        return dist.shard(emb, "flat_batch", None, None)

    def param_specs(self, spec, rules, mesh=None) -> dict:
        if spec.placement == "model":
            # ZeRO-3: on a degraded mesh the array re-shards over the
            # surviving model axis (the per-step gather simply spans
            # fewer shards); no surviving axis → back to replicated
            rows = axes_on_mesh(axes_tuple(rules.get("table_rows", "model")),
                                mesh)
            if rows:
                return {"memory": P(axes_entry(rows))}
        return {"memory": P()}

    def param_count(self, spec) -> int:
        return spec.robe.size


register_backend(RobeBackend())
