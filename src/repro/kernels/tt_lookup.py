"""Pallas TPU kernel: fused tensor-train (TT-Rec) embedding lookup.

The ``tt`` backend's hot path.  The unfused jnp path materializes three
[B, F, ...] core gathers in HBM and runs two whole-batch einsums over
them; the mixed-radix index decomposition adds two more [B, F] int
intermediates.  Here everything runs per VMEM tile:

  * the three TT cores are tiny by construction (O(n^(1/3)·d·r²) total)
    and stay **VMEM-resident** across the whole grid;
  * the mixed-radix split ``g -> (i1, i2, i3)`` (i3 fastest) is a few VPU
    integer ops computed in-kernel from the tiled row ids;
  * the per-row chain contraction ``G1[i1] · G2[i2] · G3[i3]`` runs as two
    MXU-batched einsums over the [TB·F, ...] gathered core slices, f32
    accumulation, and only the final [TB, F, dim] tile is written to HBM.

Batching reuses ``pick_batch_tile``'s pad-and-slice scheme, sized by the
larger of the output row and the gathered core slices per element so the
working set stays inside the VMEM budget.

Validated in interpret mode against ``repro.kernels.ref.tt_lookup_ref``
(tests/test_kernel_conformance.py sweeps dtype/shape/bag regimes).  The
TPU compiler refuses it ("Only 2D gather is supported": the in-kernel
``jnp.take`` of core slices), so ``kernels/ops.tt_lookup`` raises on a
TPU (``REFUSED_ON_TPU``; ROADMAP queue 1).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import pad_batch, pick_batch_tile, round_up


def _kernel(n2: int, n3: int, dim: int,
            idx_ref, off_ref, c0_ref, c1_ref, c2_ref, out_ref):
    idx = idx_ref[...]                                   # [TB, F] int32
    g = idx + off_ref[...][None, :]                      # global row ids
    i3 = g % n3
    rest = g // n3
    i2 = rest % n2
    i1 = rest // n2
    tb, f = idx.shape
    c1 = jnp.take(c0_ref[...], i1.reshape(-1), axis=0)   # [TB·F, d1, r]
    c2 = jnp.take(c1_ref[...], i2.reshape(-1), axis=0)   # [TB·F, r, d2, r]
    c3 = jnp.take(c2_ref[...], i3.reshape(-1), axis=0)   # [TB·F, r, d3]
    t = jnp.einsum("xap,xpbq->xabq", c1, c2,
                   preferred_element_type=jnp.float32)   # [TB·F, d1, d2, r]
    e = jnp.einsum("xabq,xqc->xabc", t, c3,
                   preferred_element_type=jnp.float32)   # [TB·F, d1, d2, d3]
    out_ref[...] = e.reshape(tb, f, dim).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("offsets", "factors", "dim",
                                             "interpret"))
def tt_lookup_pallas(core0: jnp.ndarray, core1: jnp.ndarray,
                     core2: jnp.ndarray, idx: jnp.ndarray,
                     offsets: Tuple[int, ...], factors: Tuple[int, int, int],
                     dim: int, interpret: bool = True) -> jnp.ndarray:
    """Fused TT lookup: [B, F] int rows -> [B, F, dim] embeddings.

    ``offsets`` are the per-field row offsets into the concatenated logical
    table; ``factors`` = (n1, n2, n3) is its mixed-radix row factorization.
    Both are static (they come from the spec, not the data).
    """
    b, f = idx.shape
    _, n2, n3 = factors
    d1, r = core0.shape[1:]
    d2, d3 = core1.shape[2], core2.shape[2]
    # VMEM working set per (row, field): the gathered core slices + the
    # contracted output row — size the batch tile by the larger of the two
    per_elem = max(dim, d1 * r + r * d2 * r + r * d3)
    tb = pick_batch_tile(b, f, per_elem)
    b_pad = round_up(b, tb)
    # pad with row 0 (any valid id) and slice the output back below
    idx = pad_batch(idx, b_pad)

    out = pl.pallas_call(
        functools.partial(_kernel, n2, n3, dim),
        grid=(b_pad // tb,),
        in_specs=[
            pl.BlockSpec((tb, f), lambda i: (i, 0)),               # row ids
            pl.BlockSpec((f,), lambda i: (0,)),                    # offsets
            pl.BlockSpec(core0.shape, lambda i: (0, 0, 0)),        # G1
            pl.BlockSpec(core1.shape, lambda i: (0, 0, 0, 0)),     # G2
            pl.BlockSpec(core2.shape, lambda i: (0, 0, 0)),        # G3
        ],
        out_specs=pl.BlockSpec((tb, f, dim), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, f, dim), core0.dtype),
        interpret=interpret,
        name="tt_lookup",
    )(idx, jnp.asarray(offsets, jnp.int32), core0, core1, core2)
    return out[:b] if b_pad != b else out
