"""Shared batch-tiling helpers for the Pallas kernels.

The kernels that tile their grid over the batch share one pad-and-slice
scheme: pick a tile that keeps the per-step VMEM working set bounded, pad
the batch up to the next tile multiple, and slice the output back.  The
tile need NOT divide the batch — a divisor search degrades to one-row
tiles for prime batch sizes (one grid step per row).

The tile is always a multiple of 8: the batch is the second-to-last
dimension of the kernels' 2-D blocks, and the TPU tiles that dimension
in 8 sublanes (tests/test_tiling.py pins the semantics).

The DMA gathers (``robe_lookup``, ``qr_lookup``) tile (row, field) pairs
instead, ``pair_tile``: their per-step row indices are 1-D SMEM blocks,
which the TPU lays out in tiles of 1024 words.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["pick_batch_tile", "pair_tile", "round_up", "pad_batch"]

SUBLANES = 8
LANES = 128
#: (row, field) pairs per grid step of a DMA gather: a partial 1-D SMEM
#: block must hold whole 1024-word tiles
PAIRS_PER_STEP = 1024


def pick_batch_tile(batch: int, f: int, dim: int) -> int:
    """Batch tile, a multiple of 8, so a [tile, f, dim] f32 working set
    stays ≲ 2 MB of VMEM (never below 8 rows, never above 1024, and no
    larger than the batch rounded up to 8).  Callers pad the batch up to
    the next tile multiple and slice the output back."""
    budget = 2 * 1024 * 1024 // 4
    tb = budget // max(1, f * dim) // SUBLANES * SUBLANES
    return min(max(SUBLANES, tb), round_up(batch, SUBLANES), 1024)


def pair_tile(n: int) -> tuple:
    """(pairs per grid step, padded pair count) for ``n`` gathered pairs:
    one step holding them all (rounded up to 8) when ``n`` fits in
    PAIRS_PER_STEP — a block equal to the whole array needs no tile
    alignment — else PAIRS_PER_STEP per step."""
    pairs = round_up(n, SUBLANES) if n <= PAIRS_PER_STEP else PAIRS_PER_STEP
    return pairs, round_up(n, pairs)


def round_up(n: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is ≥ ``n``."""
    return ((n + mult - 1) // mult) * mult


def pad_batch(x: jnp.ndarray, b_pad: int, fill=0) -> jnp.ndarray:
    """Pad the leading (batch) axis of ``x`` up to ``b_pad`` rows with
    ``fill`` (no-op when already there).  The inverse is ``out[:b]``."""
    b = x.shape[0]
    if b_pad == b:
        return x
    pad = jnp.full((b_pad - b,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad])
