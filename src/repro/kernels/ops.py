"""Jit'd public wrappers around the Pallas kernels, with autodiff.

``robe_lookup``: forward = the jnp block gather (the default:
``kernels.robe_lookup.robe_lookup_blocks``, one hash and one gathered
table row per Z-block, bit-identical to the element-wise definition in
``core.robe``) or the Pallas kernel (a DMA gather of Z-blocks from the
array in HBM); backward = the paper's Fig.-2 scatter-add of output grads
into the shared array, for either forward the transpose of the block
gather (``kernels.robe_lookup.robe_scatter_blocks``: one hash and one
scatter-added table row per Z-block, summed in f32).  The scatter IS the
semantics of weight sharing — every aliased parameter's gradient
accumulates into its slot.

``qr_lookup`` / ``tt_lookup`` follow the identical contract for the two
baseline substrates: fused Pallas forward, custom-VJP backward as an XLA
scatter-add into the tables/cores.

Selection: ``use_kernel`` picks the Pallas forward.  On a TPU the kernel
is compiled by Mosaic; elsewhere it runs in Pallas interpret mode.  A
kernel the TPU compiler refuses raises on a TPU with the compiler's
reason (``REFUSED_ON_TPU``) — it is never swapped for interpret mode or
the jnp path there.  Every fused op must pass the conformance harness
(tests/test_kernel_conformance.py) before it ships, and the ones that
compile are compiled for a v5e at published widths by
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.robe import RobeSpec, robe_slots, robe_signs
from repro.kernels import ref as _ref
from repro.kernels.robe_lookup import (qrobe_lookup_pallas,
                                       robe_lookup_blocks,
                                       robe_lookup_pallas,
                                       robe_scatter_blocks)
from repro.kernels.dot_interaction import dot_interaction_pallas
from repro.kernels.qr_lookup import qr_lookup_pallas
from repro.kernels.tt_lookup import tt_lookup_pallas


#: kernels that Mosaic refuses for a TPU, with the compiler's reason
#: (ROADMAP queue 1 lists each)
REFUSED_ON_TPU = {
    "tt_lookup": "Mosaic refuses kernels/tt_lookup.py: NotImplementedError: "
                 "Only 2D gather is supported (the in-kernel jnp.take of "
                 "core slices from the VMEM-resident TT cores)",
}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(op: str) -> bool:
    """Interpret mode off a TPU; on a TPU, compile — or raise the
    compiler's refusal for a kernel that cannot compile there."""
    if not _on_tpu():
        return True
    if op in REFUSED_ON_TPU:
        raise NotImplementedError(f"{op}: no TPU kernel: "
                                  f"{REFUSED_ON_TPU[op]}")
    return False


# ---------------------------------------------------------------------------
# robe_lookup with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def robe_lookup(memory: jnp.ndarray, rows: jnp.ndarray,
                table_ids: Tuple[int, ...], dim: int, spec: RobeSpec,
                use_kernel: bool = False) -> jnp.ndarray:
    """[B, F] int rows -> [B, F, dim] embeddings through the ROBE array."""
    if use_kernel:
        return robe_lookup_pallas(memory, rows,
                                  table_ids, dim, spec,
                                  interpret=_interpret("robe_lookup"))
    return robe_lookup_blocks(memory, rows, table_ids, dim, spec)


def _lookup_fwd(memory, rows, table_ids, dim, spec, use_kernel):
    out = robe_lookup(memory, rows, table_ids, dim, spec, use_kernel)
    return out, (rows, memory.shape[0])


def _lookup_bwd(table_ids, dim, spec, use_kernel, res, g):
    rows, m = res
    # the cotangent's dtype IS the memory dtype: custom_vjp cotangents match
    # the primal output aval, and both lookup paths emit memory.dtype.
    # Every element's grad adds into its shared slot (paper Fig. 2), a whole
    # Z-block at a time (the transpose of the block gather); accumulate in
    # f32, deliver in the memory's dtype (custom_vjp contract)
    gmem = robe_scatter_blocks(g, rows, table_ids, dim, spec, m)
    return gmem.astype(g.dtype), None


robe_lookup.defvjp(_lookup_fwd, _lookup_bwd)


# ---------------------------------------------------------------------------
# qrobe_lookup: int8 ROBE array + learned per-group f32 scales, dequantized
# inside the kernel (ALPT-style quantization-aware training).  The scales
# are real trainable leaves — the backward delivers their analytic gradient
# (d out/d scale[g] = Σ codes·sign over the group's touched elements).  The
# int8 codes get a float0 cotangent: integer leaves cannot carry float
# tangents through jax.grad, so the straight-through update rides on the
# qrobe backend's zero-valued f32 "delta" carrier (see
# nn/embedding_backends/qrobe.py) and is folded back into the codes by the
# backend's post-optimizer projection.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def qrobe_lookup(codes: jnp.ndarray, scale: jnp.ndarray, rows: jnp.ndarray,
                 table_ids: Tuple[int, ...], dim: int, spec: RobeSpec,
                 group_log2: int, use_kernel: bool = False) -> jnp.ndarray:
    """[B, F] int rows -> [B, F, dim] embeddings dequantized from the int8
    ROBE array, delivered in ``scale.dtype`` (single-rounding contract)."""
    if use_kernel:
        return qrobe_lookup_pallas(codes, scale, rows, table_ids, dim, spec,
                                   group_log2, interpret=_interpret("qrobe_lookup"))
    return _ref.qrobe_lookup_ref(codes, scale, rows,
                                 jnp.asarray(table_ids, jnp.uint32), dim,
                                 spec, group_log2)


def _qrobe_fwd(codes, scale, rows, table_ids, dim, spec, group_log2,
               use_kernel):
    out = qrobe_lookup(codes, scale, rows, table_ids, dim, spec, group_log2,
                       use_kernel)
    return out, (codes, scale, rows)


def _qrobe_bwd(table_ids, dim, spec, group_log2, use_kernel, res, g):
    codes, scale, rows = res
    tids = jnp.asarray(table_ids, jnp.uint32)[None, :]
    slots = robe_slots(spec, tids, rows, dim)            # [B, F, dim]
    g32 = g.astype(jnp.float32)
    if spec.use_sign:
        g32 = g32 * robe_signs(spec, tids, rows, dim)
    # scale grad: d out/d scale[g] = codes_f32 at the element's slot — every
    # touched element's (cotangent · code) accumulates into its group (f32
    # accumulate, scale-dtype delivery, as in _lookup_bwd)
    flat = slots.reshape(-1).astype(jnp.int32)
    cvals = jnp.take(codes, flat, axis=0).astype(jnp.float32)
    gidx = (slots.reshape(-1) >> group_log2).astype(jnp.int32)
    gscale = jnp.zeros(scale.shape, jnp.float32
                       ).at[gidx].add(g32.reshape(-1) * cvals)
    # int8 codes: float0 cotangent (the only tangent type an integer primal
    # may carry); the STE path runs through the backend's delta carrier
    gcodes = np.zeros(codes.shape, jax.dtypes.float0)
    return gcodes, gscale.astype(scale.dtype), None


qrobe_lookup.defvjp(_qrobe_fwd, _qrobe_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def dot_interaction(feats: jnp.ndarray, self_interaction: bool = False,
                    use_kernel: bool = False) -> jnp.ndarray:
    """[B, F, D] -> [B, F*(F±1)/2] pairwise dots (DLRM interaction)."""
    if use_kernel:
        return dot_interaction_pallas(feats, self_interaction,
                                      interpret=_interpret("dot_interaction"))
    return _ref.dot_interaction_ref(feats, self_interaction)


def _dot_fwd(feats, self_interaction, use_kernel):
    out = dot_interaction(feats, self_interaction, use_kernel)
    return out, (feats,)


def _dot_bwd(self_interaction, use_kernel, res, g):
    # d gram[i,j]/d feats[i] = feats[j]: scatter the triangle cotangent into
    # a symmetric [F, F] matrix (the transpose add doubles the diagonal,
    # which IS the self-interaction derivative 2·feats[i]) and contract.
    # Needed explicitly: the Pallas forward has no autodiff rule, and this
    # keeps the backward one fused matmul either way.
    (feats,) = res
    b, f, _ = feats.shape
    rows, cols = np.tril_indices(f, k=0 if self_interaction else -1)
    g32 = g.astype(jnp.float32)
    sym = jnp.zeros((b, f, f), jnp.float32
                    ).at[:, rows, cols].add(g32).at[:, cols, rows].add(g32)
    df = jnp.einsum("bfg,bgd->bfd", sym, feats.astype(jnp.float32))
    return (df.astype(feats.dtype),)


dot_interaction.defvjp(_dot_fwd, _dot_bwd)


# ---------------------------------------------------------------------------
# compressed-substrate lookups (hashed / tensor-train backends).  Same
# contract as robe_lookup: forward = fused Pallas kernel or the jnp
# reference path; backward = an explicit XLA
# scatter-add of the output grads into the tables/cores, f32-accumulated and
# delivered in the parameter dtype (mirrors _lookup_bwd).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def qr_lookup(q_table: jnp.ndarray, r_table: jnp.ndarray,
              idx: jnp.ndarray, q_off: Tuple[int, ...],
              r_off: Tuple[int, ...], m: int,
              use_kernel: bool = False) -> jnp.ndarray:
    """Fused QR compositional lookup.

    [B, F] int rows -> [B, F, dim] via ``Q[id // m + q_off[f]] *
    R[id % m + r_off[f]]`` — both row gathers (DMA on the Pallas side)
    and the product in one pass.
    """
    if use_kernel:
        return qr_lookup_pallas(q_table, r_table, idx, q_off, r_off, m,
                                interpret=_interpret("qr_lookup"))
    return _ref.qr_lookup_ref(q_table, r_table, idx, q_off, r_off, m)


def _qr_fwd(q_table, r_table, idx, q_off, r_off, m, use_kernel):
    out = qr_lookup(q_table, r_table, idx, q_off, r_off, m, use_kernel)
    return out, (q_table, r_table, idx)


def _qr_bwd(q_off, r_off, m, use_kernel, res, g):
    q_table, r_table, idx = res
    q_idx, r_idx = _ref.qr_indices(idx, q_off, r_off, m)
    # product rule: each factor's row grad is the cotangent times the OTHER
    # factor's row, scatter-added into its table (f32 accumulate, parameter
    # dtype delivery — the custom_vjp contract, as in _lookup_bwd)
    g32 = g.astype(jnp.float32)
    qv = jnp.take(q_table, q_idx, axis=0).astype(jnp.float32)
    rv = jnp.take(r_table, r_idx, axis=0).astype(jnp.float32)
    gq = jnp.zeros(q_table.shape, jnp.float32).at[q_idx].add(g32 * rv)
    gr = jnp.zeros(r_table.shape, jnp.float32).at[r_idx].add(g32 * qv)
    return gq.astype(q_table.dtype), gr.astype(r_table.dtype), None


qr_lookup.defvjp(_qr_fwd, _qr_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def tt_lookup(core0: jnp.ndarray, core1: jnp.ndarray, core2: jnp.ndarray,
              idx: jnp.ndarray, offsets: Tuple[int, ...],
              factors: Tuple[int, int, int], dim: int,
              use_kernel: bool = False) -> jnp.ndarray:
    """Fused tensor-train lookup.

    core0 [n1, d1, r], core1 [n2, r, d2, r], core2 [n3, r, d3]; [B, F] int
    rows (+ static per-field ``offsets``) decompose mixed-radix over
    ``factors`` = (n1, n2, n3) in-path (in-kernel on the Pallas side) and
    contract G1[i1]·G2[i2]·G3[i3] to [B, F, dim] without ever materializing
    the table.
    """
    if use_kernel:
        return tt_lookup_pallas(core0, core1, core2, idx, offsets, factors,
                                dim, interpret=_interpret("tt_lookup"))
    return _ref.tt_lookup_ref(core0, core1, core2, idx, offsets, factors,
                              dim)


def _tt_fwd(core0, core1, core2, idx, offsets, factors, dim, use_kernel):
    out = tt_lookup(core0, core1, core2, idx, offsets, factors, dim,
                    use_kernel)
    return out, (core0, core1, core2, idx)


def _tt_bwd(offsets, factors, dim, use_kernel, res, g):
    core0, core1, core2, idx = res
    i1, i2, i3 = _ref.tt_indices(idx, offsets, factors)
    d1, r = core0.shape[1:]
    d2, d3 = core1.shape[2], core2.shape[2]
    c1 = jnp.take(core0, i1, axis=0).astype(jnp.float32)  # [B, F, d1, r]
    c2 = jnp.take(core1, i2, axis=0).astype(jnp.float32)  # [B, F, r, d2, r]
    c3 = jnp.take(core2, i3, axis=0).astype(jnp.float32)  # [B, F, r, d3]
    g32 = g.astype(jnp.float32).reshape(g.shape[:-1] + (d1, d2, d3))
    # chain-rule through e = (c1·c2)·c3, then scatter-add each row's core
    # grad into its core slice (f32 accumulate, core dtype delivery)
    t = jnp.einsum("...ap,...pbq->...abq", c1, c2)
    dc3 = jnp.einsum("...abq,...abc->...qc", t, g32)
    dt = jnp.einsum("...abc,...qc->...abq", g32, c3)
    dc1 = jnp.einsum("...abq,...pbq->...ap", dt, c2)
    dc2 = jnp.einsum("...ap,...abq->...pbq", c1, dt)
    g0 = jnp.zeros(core0.shape, jnp.float32).at[i1].add(dc1)
    g1 = jnp.zeros(core1.shape, jnp.float32).at[i2].add(dc2)
    g2 = jnp.zeros(core2.shape, jnp.float32).at[i3].add(dc3)
    return (g0.astype(core0.dtype), g1.astype(core1.dtype),
            g2.astype(core2.dtype), None)


tt_lookup.defvjp(_tt_fwd, _tt_bwd)
