"""``robe`` — the paper's Random Offset Block Embedding array.

One shared circular array of ``spec.robe.size`` float slots replaces every
table (``repro.core.robe`` holds the hash math; ``repro.kernels.ops`` the
Pallas lookup).  Placement (``spec.placement``):

* ``"default"`` / ``"replicated"`` — the array is tiny (~100 MB for the
  paper's CriteoTB model), so it is replicated and lookups are purely
  local: the embedding-exchange collective disappears and only the
  |M|-sized gradient all-reduce remains.  Batches shard over the whole
  mesh.
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


def analytic_max_fetches(d: int, z: int, bus: int) -> float:
    """Paper Table 1 bound: max B-sized bus fetches per d-dim row at block
    size Z.  The substrate's memory-traffic model (see ``cost``)."""
    if z >= d:
        return d / bus + 2
    if z >= bus:
        return d / bus + d / z
    return 2 * d / z


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
        if ctx is None or spec.placement != "model":
            return super().lookup_dist(params, spec, idx,
                                       compute_dtype=compute_dtype)
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
        fields = tuple(range(spec.n_fields))

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

    def cost(self, spec, batch: int, bus: int = 16) -> dict:
        """``bytes_fetched`` models the paper's access pattern on the jnp
        path — block-coalesced reads, ≤ ``analytic_max_fetches`` bus lines
        per row (Table 1), an ideal reader and not a measurement of XLA's
        gather — and, with ``use_kernel``, counts what the Pallas DMA
        gather moves (``kernels.robe_lookup.gather_bytes``: the per-call
        padded f32 copy of the array plus 128-lane windows per row).
        Hashing is ~10 int ops per element, plus the optional sign
        multiply."""
        from repro.kernels.robe_lookup import gather_bytes
        z = spec.robe.block_size
        pairs = batch * spec.n_fields
        if spec.use_kernel:
            fetched = gather_bytes(spec.robe.size, spec.dim, z, pairs)
        else:
            fetched = int(pairs * analytic_max_fetches(spec.dim, z, bus)
                          * bus * 4)
        flops = 10 * pairs * spec.dim
        if spec.robe.use_sign:
            flops += pairs * spec.dim
        return {"params": self.param_count(spec),
                "bytes_fetched": fetched, "flops": flops}


register_backend(RobeBackend())
