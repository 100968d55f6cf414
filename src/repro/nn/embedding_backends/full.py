"""``full`` — the uncompressed baseline: one concatenated [total_rows, dim]
table (per-field row offsets), the paper's "Original (100GB)" substrate.

Placement (``spec.placement``):

* ``"default"`` / ``"model"`` — rows sharded over the `model` axis, the
  classic model-parallel DLRM layout.  The distributed lookup is a masked
  local gather + ``psum_scatter`` over `model` (semantically the Neo-style
  all_to_all embedding exchange: same bytes on the wire, one collective).
* ``"2d"`` — rows sharded over the WHOLE mesh (dp × model).  Each device
  all-gathers the (tiny) global index set, computes masked partials against
  its unique row slice, and one reduce-scatter over all axes delivers each
  device its batch slice; table gradients stay local to their owning shard,
  killing the data-axis table-grad all-reduce (§Perf, dlrm-rm2 hillclimb).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.nn.embedding_backends.base import (EmbeddingBackend, axes_entry,
                                              axes_on_mesh, axes_tuple,
                                              register_backend)


def full_lookup_sharded_body(table_shard: jnp.ndarray, idx: jnp.ndarray,
                             offsets: np.ndarray, model_axis: str,
                             shard_rows: int) -> jnp.ndarray:
    """Masked local gather + batch reduce-scatter over the model axis.

    Called INSIDE shard_map.
    table_shard: [rows/model, dim] this shard's rows.
    idx:         [B_data, F] global row ids for this data-shard's batch.
    returns      [B_data/model, F, dim] — batch now sharded over model too.
    """
    g = jnp.asarray(offsets, jnp.int32)[None, :] + idx        # global rows
    m_idx = jax.lax.axis_index(model_axis)
    lo = m_idx * shard_rows
    local = g - lo
    hit = (local >= 0) & (local < shard_rows)
    safe = jnp.clip(local, 0, shard_rows - 1)
    part = jnp.take(table_shard, safe, axis=0)                # [B, F, dim]
    part = jnp.where(hit[..., None], part, 0.0)
    # equivalent to the production all_to_all embedding exchange
    return jax.lax.psum_scatter(part, model_axis, scatter_dimension=0,
                                tiled=True)


class FullTableBackend(EmbeddingBackend):
    name = "full"
    local_batch = False          # lookups exchange over `model`

    def init(self, key, spec, pad_rows_to: int = 1) -> dict:
        rows = spec.total_rows
        rows = ((rows + pad_rows_to - 1) // pad_rows_to) * pad_rows_to
        scale = 1.0 / np.sqrt(spec.dim)
        table = jax.random.uniform(key, (rows, spec.dim), jnp.float32,
                                   -scale, scale)
        return {"table": table}

    def lookup(self, params, spec, idx, fields=None):
        fields = fields if fields is not None else tuple(range(spec.n_fields))
        off = jnp.asarray(spec.offsets[list(fields)], jnp.int32)
        return jnp.take(params["table"], idx + off[None, :], axis=0)

    def cacheable_rows(self, params, spec, field: int,
                       ids: np.ndarray) -> np.ndarray:
        """Hot-row-cache hook: the exact rows ``lookup`` would gather for
        ``ids`` in ``field`` — a host-side copy of the same f32 bits, so a
        cached serve score is bit-exact against the device gather.  Only
        the requested rows cross to the host: the table (52 GB at
        CriteoTB d=64, possibly sharded) is gathered where it lives."""
        rows = np.asarray(ids, np.int64) + int(spec.offsets[field])
        return np.asarray(jnp.take(params["table"],
                                   jnp.asarray(rows, jnp.int32), axis=0))

    def lookup_dist(self, params, spec, idx, *, compute_dtype=None):
        from repro.dist import api as dist
        ctx = dist.current()
        batch = idx.shape[0]
        cols = spec.columns
        if ctx is None:
            return self.lookup(params, spec, idx, cols)
        n_model = ctx.mesh.shape["model"]
        n_data = ctx.dp_size
        table = params["table"]
        dp = ctx.rules.get("batch")
        dp_t = axes_tuple(dp)
        cdt = compute_dtype or table.dtype

        if spec.placement == "2d" and batch % n_data == 0 \
                and batch % (n_data * n_model) == 0:
            all_axes = dp_t + ("model",)
            n_all = n_data * n_model
            shard_rows = table.shape[0] // n_all

            def body2d(tb, ix):
                # indices are model-replicated; gather the other data
                # shards' rows so this device can serve the whole global
                # batch
                ix_all = jax.lax.all_gather(ix, dp_t, axis=0, tiled=True)
                g = jnp.asarray(spec.column_offsets, jnp.int32)[None, :] \
                    + ix_all
                lin = jax.lax.axis_index(all_axes)
                local = g - lin * shard_rows
                hit = (local >= 0) & (local < shard_rows)
                part = jnp.take(tb.astype(cdt),
                                jnp.clip(local, 0, shard_rows - 1), axis=0)
                part = jnp.where(hit[..., None], part, 0)
                return jax.lax.psum_scatter(part, all_axes,
                                            scatter_dimension=0, tiled=True)

            return jax.shard_map(
                body2d, mesh=ctx.mesh,
                in_specs=(P(all_axes, None), P(dp, None)),
                out_specs=P(all_axes, None, None))(table, idx)

        if batch % n_data == 0:
            # rows sharded over `model`: masked local gather + batch
            # reduce-scatter (≡ the production all_to_all exchange).  When
            # the per-data-shard batch doesn't divide by `model`, fall back
            # to a psum (same semantics, all-reduce volume instead of RS).
            shard_rows = table.shape[0] // n_model
            scatter_ok = (batch // n_data) % n_model == 0

            def body(tb, ix):
                if scatter_ok:
                    return full_lookup_sharded_body(tb, ix,
                                                    spec.column_offsets,
                                                    "model", shard_rows)
                g = jnp.asarray(spec.column_offsets, jnp.int32)[None, :] + ix
                m_idx = jax.lax.axis_index("model")
                local = g - m_idx * shard_rows
                hit = (local >= 0) & (local < shard_rows)
                part = jnp.take(tb, jnp.clip(local, 0, shard_rows - 1),
                                axis=0)
                part = jnp.where(hit[..., None], part, 0.0)
                return jax.lax.psum(part, "model")

            out_spec = P(dp_t + ("model",), None, None) if scatter_ok \
                else P(dp, None, None)
            return jax.shard_map(
                body, mesh=ctx.mesh,
                in_specs=(P("model", None), P(dp, None)),
                out_specs=out_spec)(table, idx)

        return self.lookup(params, spec, idx, cols)

    def param_specs(self, spec, rules, mesh=None) -> dict:
        dp = axes_tuple(rules.get("batch"))
        rows = axes_tuple(rules.get("table_rows", "model"))
        table_axes = dp + rows if spec.placement == "2d" else rows
        table_axes = axes_on_mesh(table_axes, mesh)   # elastic: survivors
        if not table_axes:
            return {"table": P()}
        return {"table": P(axes_entry(table_axes), None)}

    def param_count(self, spec) -> int:
        return spec.total_rows * spec.dim

    def cost(self, spec, batch: int) -> dict:
        # one dim-row fetch per (example, field); dense tables stream from
        # HBM — the embedding exchange's wire bytes live in the dryrun
        return {"params": self.param_count(spec),
                "bytes_fetched": batch * spec.n_fields * spec.dim * 4,
                "flops": 0}


register_backend(FullTableBackend())
