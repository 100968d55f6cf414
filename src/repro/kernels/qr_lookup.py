"""Pallas TPU kernel: fused QR (quotient × remainder) embedding lookup.

The ``hashed`` backend's hot path.  The unfused jnp path is three HBM
round-trips per batch — gather Q rows, gather R rows, elementwise product.
Here the two gathers and the product run in one pass per tile of (row,
field) pairs:

  * ``q_idx = id // m + q_off[f]`` / ``r_idx = id % m + r_off[f]`` are
    computed before the kernel and arrive in SMEM, one tile at a time;
  * both tables stay in HBM (``pl.ANY``): each pair's Q row and R row are
    copied into VMEM by DMA — no vector gather, which Mosaic lacks;
  * the product of the two staged tiles is the only thing written back.

The tables are widened to f32 (exact) and their rows padded to a
multiple of 128 lanes before the kernel: a DMA moves whole lane tiles.
The product is rounded once into the table dtype, as in the reference.

Validated in interpret mode against ``repro.kernels.ref.qr_lookup_ref``
(tests/test_kernel_conformance.py sweeps dtype/shape/bag regimes).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.tiling import LANES, pair_tile, round_up


def _kernel(pairs: int, q_idx_ref, r_idx_ref, q_hbm, r_hbm, out_ref,
            q_stage, r_stage, sem):
    def copies(p):
        return (pltpu.make_async_copy(q_hbm.at[pl.ds(q_idx_ref[p], 1)],
                                      q_stage.at[pl.ds(p, 1)], sem),
                pltpu.make_async_copy(r_hbm.at[pl.ds(r_idx_ref[p], 1)],
                                      r_stage.at[pl.ds(p, 1)], sem))

    def issue(p, c):
        for cp in copies(p):
            cp.start()
        return c

    def drain(p, c):
        for cp in copies(p):
            cp.wait()
        return c

    jax.lax.fori_loop(0, pairs, issue, 0)
    jax.lax.fori_loop(0, pairs, drain, 0)
    out_ref[...] = q_stage[...] * r_stage[...]


@functools.partial(jax.jit, static_argnames=("q_off", "r_off", "m",
                                             "interpret"))
def qr_lookup_pallas(q_table: jnp.ndarray, r_table: jnp.ndarray,
                     idx: jnp.ndarray, q_off: Tuple[int, ...],
                     r_off: Tuple[int, ...], m: int,
                     interpret: bool = True) -> jnp.ndarray:
    """Fused QR lookup: [B, F] int rows -> [B, F, dim] embeddings.

    ``q_off``/``r_off`` are the per-field row offsets into the concatenated
    Q/R tables (static: they come from the host-side ``qr_layout``).
    """
    b, f = idx.shape
    dim = q_table.shape[1]
    lanes = round_up(dim, LANES)
    q_idx, r_idx = _ref.qr_indices(idx, q_off, r_off, m)
    widen = lambda t: jnp.pad(t.astype(jnp.float32),
                              ((0, 0), (0, lanes - dim)))
    n = b * f
    pairs, n_pad = pair_tile(n)
    # padded pairs fetch row 0 of each table and are sliced off below
    q_idx = jnp.pad(q_idx.reshape(-1).astype(jnp.int32), (0, n_pad - n))
    r_idx = jnp.pad(r_idx.reshape(-1).astype(jnp.int32), (0, n_pad - n))

    out = pl.pallas_call(
        functools.partial(_kernel, pairs),
        grid=(n_pad // pairs,),
        in_specs=[
            pl.BlockSpec((pairs,), lambda i: (i,),
                         memory_space=pltpu.SMEM),           # Q rows
            pl.BlockSpec((pairs,), lambda i: (i,),
                         memory_space=pltpu.SMEM),           # R rows
            pl.BlockSpec(memory_space=pl.ANY),               # Q in HBM
            pl.BlockSpec(memory_space=pl.ANY),               # R in HBM
        ],
        out_specs=pl.BlockSpec((pairs, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((pairs, lanes), jnp.float32),
                        pltpu.VMEM((pairs, lanes), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="qr_gather",
    )(q_idx, r_idx, widen(q_table), widen(r_table))
    return out[:n, :dim].reshape(b, f, dim).astype(q_table.dtype)
