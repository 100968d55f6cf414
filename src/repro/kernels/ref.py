"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are allclose-checked
against (tests/test_kernels.py sweeps shapes/dtypes/Z).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.robe import (RobeSpec, robe_lookup as _core_lookup,
                             robe_signs, robe_slots)


def robe_lookup_ref(memory: jnp.ndarray, rows: jnp.ndarray,
                    table_ids: jnp.ndarray, dim: int,
                    spec: RobeSpec) -> jnp.ndarray:
    """[B, F] rows (+ per-field table ids) -> [B, F, dim] embeddings."""
    return _core_lookup(memory, spec, table_ids[None, :], rows, dim)


def qrobe_dequant_ref(codes: jnp.ndarray, scale: jnp.ndarray,
                      group_log2: int) -> jnp.ndarray:
    """Materialize the f32 array an int8 ROBE substrate represents.

    codes: [|M|] int8; scale: [ceil(|M| / 2**group_log2)] learned per-group
    scales.  Slot s dequantizes as ``codes[s] · scale[s >> group_log2]`` —
    computed entirely in f32 (scale upcast first), no intermediate rounding.
    """
    gidx = jnp.arange(codes.shape[0], dtype=jnp.int32) >> group_log2
    return codes.astype(jnp.float32) * jnp.take(scale.astype(jnp.float32),
                                                gidx, axis=0)


def qrobe_lookup_ref(codes: jnp.ndarray, scale: jnp.ndarray,
                     rows: jnp.ndarray, table_ids: jnp.ndarray, dim: int,
                     spec: RobeSpec, group_log2: int) -> jnp.ndarray:
    """The single-rounding int8-dequant contract for ``qrobe_lookup``.

    [B, F] rows -> [B, F, dim]: gather int8 codes through the ROBE hash,
    dequantize each element in f32 against its group's scale
    (``codes_f32 · scale_f32[slot >> group_log2]``), apply the ±1 sign
    hash, and round ONCE on delivery into ``scale.dtype`` (the activation
    dtype — bf16 activations over int8 params included).
    """
    tids = jnp.asarray(table_ids, jnp.uint32)[None, :]
    slots = robe_slots(spec, tids, rows, dim)             # [B, F, dim] uint32
    c = jnp.take(codes, slots.astype(jnp.int32), axis=0).astype(jnp.float32)
    s = jnp.take(scale.astype(jnp.float32),
                 (slots >> group_log2).astype(jnp.int32), axis=0)
    out = c * s
    if spec.use_sign:
        out = out * robe_signs(spec, tids, rows, dim)
    return out.astype(scale.dtype)


def dot_interaction_ref(feats: jnp.ndarray, self_interaction: bool = False
                        ) -> jnp.ndarray:
    """DLRM pairwise-dot feature interaction.

    feats: [B, F, D] -> [B, F*(F-1)/2] (strictly-lower triangle of the gram
    matrix; +F diagonal terms if self_interaction).
    """
    b, f, _ = feats.shape
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
    rows, cols = jnp.tril_indices(f, k=0 if self_interaction else -1)
    return gram[:, rows, cols]


def cin_layer_ref(x0: jnp.ndarray, xk: jnp.ndarray, w: jnp.ndarray
                  ) -> jnp.ndarray:
    """xDeepFM Compressed Interaction Network layer.

    x0: [B, F0, D] base field embeddings; xk: [B, Fk, D] previous layer;
    w: [H, F0, Fk] compression weights -> [B, H, D].
    z[b,i,j,d] = x0[b,i,d] * xk[b,j,d]; out[b,h,d] = Σ_ij w[h,i,j] z[b,i,j,d].
    """
    return jnp.einsum("bid,bjd,hij->bhd", x0, xk, w)


def qr_indices(idx: jnp.ndarray, q_off, r_off, m: int):
    """[B, F] ids -> (q_idx, r_idx) rows into the concatenated Q/R tables.

    The ONE copy of the quotient/remainder decomposition the jnp forward
    and the custom_vjp backward both use — forward/backward index math must
    stay bit-identical or grads scatter into the wrong rows.  (The Pallas
    kernel re-states it in-kernel; the conformance harness pins the two
    together.)
    """
    q_idx = idx // m + jnp.asarray(q_off, idx.dtype)[None, :]
    r_idx = idx % m + jnp.asarray(r_off, idx.dtype)[None, :]
    return q_idx, r_idx


def tt_indices(idx: jnp.ndarray, offsets, factors):
    """[B, F] ids -> (i1, i2, i3) core rows, mixed-radix with i3 fastest.

    Shared by the jnp forward and the custom_vjp backward (see
    ``qr_indices`` on why there is exactly one copy outside the kernel).
    """
    _, n2, n3 = factors
    g = idx + jnp.asarray(offsets, idx.dtype)[None, :]
    i3 = g % n3
    rest = g // n3
    return rest // n2, rest % n2, i3


def qr_lookup_ref(q_table: jnp.ndarray, r_table: jnp.ndarray,
                  idx: jnp.ndarray, q_off, r_off, m: int) -> jnp.ndarray:
    """Per-row QR path: ``Q[id // m + q_off[f]] * R[id % m + r_off[f]]``.

    idx: [B, F] per-field row ids; q_off/r_off: per-field offsets into the
    concatenated tables -> [B, F, dim].  The unfused oracle the fused
    ``qr_lookup_pallas`` kernel is checked against (autodiff-able).
    """
    q_idx, r_idx = qr_indices(idx, q_off, r_off, m)
    return jnp.take(q_table, q_idx, axis=0) * jnp.take(r_table, r_idx,
                                                       axis=0)


def tt_lookup_ref(core0: jnp.ndarray, core1: jnp.ndarray,
                  core2: jnp.ndarray, idx: jnp.ndarray, offsets,
                  factors, dim: int) -> jnp.ndarray:
    """Per-row TT chain contraction with in-path index decomposition.

    idx: [B, F] per-field row ids; offsets: per-field offsets into the
    concatenated logical table; factors = (n1, n2, n3) its mixed-radix row
    factorization (i3 fastest) -> [B, F, dim].  The unfused oracle the
    fused ``tt_lookup_pallas`` kernel is checked against (autodiff-able).
    """
    i1, i2, i3 = tt_indices(idx, offsets, factors)
    c1 = jnp.take(core0, i1, axis=0)                # [B, F, d1, r]
    c2 = jnp.take(core1, i2, axis=0)                # [B, F, r, d2, r]
    c3 = jnp.take(core2, i3, axis=0)                # [B, F, r, d3]
    # f32 accumulation through the chain, core dtype on delivery — the
    # same single-rounding contract as the fused kernel
    t = jnp.einsum("...ap,...pbq->...abq", c1, c2,
                   preferred_element_type=jnp.float32)
    e = jnp.einsum("...abq,...qc->...abc", t, c3,
                   preferred_element_type=jnp.float32)
    return e.reshape(e.shape[:-3] + (dim,)).astype(core0.dtype)


def qr_materialize_ref(q_table: jnp.ndarray, r_table: jnp.ndarray,
                       vocab_sizes, m: int) -> jnp.ndarray:
    """Materialize the full [total_rows, dim] table a QR (quotient ×
    remainder) substrate represents — the oracle the ``hashed`` backend's
    per-row path is checked against (autodiff-able)."""
    out = []
    q_off = 0
    for f, v in enumerate(vocab_sizes):
        x = jnp.arange(int(v))
        q = jnp.take(q_table, q_off + x // m, axis=0)
        r = jnp.take(r_table, f * m + x % m, axis=0)
        out.append(q * r)
        q_off += -(-int(v) // m)
    return jnp.concatenate(out, axis=0)


def tt_materialize_ref(core0: jnp.ndarray, core1: jnp.ndarray,
                       core2: jnp.ndarray) -> jnp.ndarray:
    """Materialize the full [n1·n2·n3, d1·d2·d3] table a tensor-train
    substrate represents, via one whole-tensor einsum (autodiff-able) —
    the oracle for the ``tt`` backend's per-row chain contraction.  Row
    g ↔ (i1, i2, i3) with i3 fastest, matching the backend's mixed-radix
    decomposition."""
    n1, d1, r1 = core0.shape
    n2, _, d2, r2 = core1.shape
    n3, _, d3 = core2.shape
    t = jnp.einsum("iap,jpbq,kqc->ijkabc", core0, core1, core2)
    return t.reshape(n1 * n2 * n3, d1 * d2 * d3)
