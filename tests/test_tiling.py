"""Unit tests pinning the shared tiling policy in ``kernels/tiling.py``.

Every fused kernel imports its batch-tile / pad-and-slice arithmetic from
this one module, so its semantics are load-bearing: the VMEM budget, the
min() clamps, and the exact pad/slice round-trip are asserted here once
instead of implicitly in four kernels.
"""

import jax.numpy as jnp
import numpy as np

from repro.kernels.tiling import (PAIRS_PER_STEP, pad_batch, pair_tile,
                                  pick_batch_tile, round_up)


def test_pick_batch_tile_vmem_budget():
    # budget is 2 MiB of f32: tb = (2·1024·1024/4) // (f·dim), rounded
    # down to a multiple of 8 sublanes, clamped
    assert pick_batch_tile(13, 8, 6000) == (2 * 1024 * 1024 // 4) // 48000 \
        // 8 * 8
    assert pick_batch_tile(13, 8, 6000) == 8           # < b → pad branch
    assert pick_batch_tile(4096, 26, 64) == 312        # 315 → 312


def test_pick_batch_tile_clamps_to_batch():
    # tiny rows: budget allows a huge tile, but never exceed the batch
    # rounded up to 8 sublanes
    assert pick_batch_tile(3, 4, 16) == 8
    assert pick_batch_tile(20, 4, 16) == 24
    # ...and never exceed the 1024 hard cap even for huge batches
    assert pick_batch_tile(1 << 20, 1, 1) == 1024


def test_pick_batch_tile_depends_only_on_row_bytes():
    # the tile is a function of (f·dim), not of the batch, once unclamped
    assert pick_batch_tile(8191, 26, 64) == pick_batch_tile(8192, 26, 64)
    assert pick_batch_tile(8191, 26, 64) > 1


def test_pick_batch_tile_never_zero():
    # a row bigger than the whole budget still yields an 8-row tile
    assert pick_batch_tile(64, 4096, 4096) == 8


def test_pick_batch_tile_is_sublane_aligned():
    # the TPU compiler refuses a batch block that is not a multiple of 8
    for b, f, d in ((512, 26, 64), (65536, 27, 128), (13, 8, 6000),
                    (1, 1, 1), (8191, 3, 7)):
        assert pick_batch_tile(b, f, d) % 8 == 0, (b, f, d)


def test_round_up():
    assert round_up(13, 10) == 20
    assert round_up(20, 10) == 20
    assert round_up(1, 512) == 512
    assert round_up(0, 8) == 0


def test_pad_batch_round_trip():
    x = jnp.asarray(np.arange(13 * 3).reshape(13, 3), jnp.int32)
    y = pad_batch(x, 20, fill=-1)
    assert y.shape == (20, 3) and y.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(y[:13]), np.asarray(x))
    assert int(y[13:].min()) == int(y[13:].max()) == -1
    # no-op when already sized: the same array comes back
    assert pad_batch(x, 13) is x


def test_pair_tile_whole_or_1024():
    # up to PAIRS_PER_STEP pairs: one step, the whole (8-aligned) array
    assert pair_tile(15) == (16, 16)
    assert pair_tile(PAIRS_PER_STEP) == (PAIRS_PER_STEP, PAIRS_PER_STEP)
    # beyond: whole 1024-word SMEM tiles, the last one padded
    assert pair_tile(1300) == (1024, 2048)
    assert pair_tile(512 * 26) == (1024, 13312)     # dlrm-rm2 serve_p99
