"""Pallas TPU kernel: fused DLRM pairwise-dot feature interaction.

The second hot op in the DLRM family after the embedding fetch: for each
sample, the gram matrix of its F field-embedding vectors, lower triangle
flattened.  Fusing the gram matmul with the triangle extraction avoids
materializing [B, F, F] in HBM.

Tile layout: grid over batch tiles; per step the [TB, F, D] tile lives in
VMEM and the gram is a batched [F, F] MXU matmul.  The triangle is taken
without a gather — Mosaic has no general vector gather — by two static
one-hot selections: a matmul against ``C[j, p] = [cols[p] == j]`` moves
column ``cols[p]`` of every gram row to output lane ``p``, and a masked
sublane sum against ``R[i, p] = [rows[p] == i]`` keeps row ``rows[p]``.
Both run at ``Precision.HIGHEST``, so selection by 0/1 is exact in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.tiling import pad_batch, pick_batch_tile, round_up

_HI = jax.lax.Precision.HIGHEST


def triangle_selectors(f: int, k: int) -> tuple:
    """(R, C) f32 [f, n_pairs] one-hot row/column selectors of
    ``np.tril_indices(f, k)``."""
    rows, cols = np.tril_indices(f, k=k)
    eye = np.eye(f, dtype=np.float32)
    return jnp.asarray(eye[rows].T), jnp.asarray(eye[cols].T)


def gram_triangle(feats: jnp.ndarray, r_sel: jnp.ndarray,
                  c_sel: jnp.ndarray) -> jnp.ndarray:
    """In-kernel: feats [TB, F, D] -> [TB, 1, n_pairs] f32 gram triangle."""
    feats = feats.astype(jnp.float32)
    tb, f, _ = feats.shape
    gram = jax.lax.dot_general(
        feats, feats, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=_HI)    # [TB, F, F]
    c = jnp.broadcast_to(c_sel[None], (tb,) + c_sel.shape)
    t = jax.lax.dot_general(
        gram, c, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=_HI)    # [TB, F, P]
    return jnp.sum(t * r_sel[None], axis=1, keepdims=True)


def _kernel(feats_ref, r_ref, c_ref, out_ref):
    out_ref[...] = gram_triangle(feats_ref[...], r_ref[...], c_ref[...]
                                 ).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("self_interaction", "interpret"))
def dot_interaction_pallas(feats: jnp.ndarray, self_interaction: bool = False,
                           interpret: bool = True) -> jnp.ndarray:
    """[B, F, D] -> [B, n_pairs] with n_pairs = F*(F±1)/2."""
    b, f, d = feats.shape
    r_sel, c_sel = triangle_selectors(f, 0 if self_interaction else -1)
    n_pairs = r_sel.shape[1]

    # the tile holds the features, the gram and two [F, n_pairs] selector
    # products per sample
    tb = pick_batch_tile(b, f, d + f + 2 * n_pairs)
    b_pad = round_up(b, tb)
    feats = pad_batch(feats, b_pad)

    out = pl.pallas_call(
        _kernel,
        grid=(b_pad // tb,),
        in_specs=[pl.BlockSpec((tb, f, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((f, n_pairs), lambda i: (0, 0)),
                  pl.BlockSpec((f, n_pairs), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((tb, 1, n_pairs), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, 1, n_pairs), feats.dtype),
        interpret=interpret,
        name="dot_interaction",
    )(feats, r_sel, c_sel)
    return out[:b, 0]
