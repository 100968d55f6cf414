"""ROBE lookup as a gather of whole Z-blocks: the jnp block gather (the
default forward) and the Pallas TPU kernel (a DMA gather from HBM).

This is the paper's hot path (inference is memory-bound on embedding
fetches; §2.3 Table 1).  Element ``i`` of row ``x`` lives at
``(h(e, Z_id) + Z_off) mod |M|``: a row of ``d`` elements is a handful of
contiguous runs of the array, one per Z-block it touches.  The kernel
reads exactly those runs — the "coalesced block read" of Table 1 — and
never holds the array in VMEM (it is 52–105 MB at the paper's sizes):

  * **before the kernel** (XLA, vectorised): the hash of every block each
    (row, field) pair touches, turned into one *window start* per block
    into a circularly padded copy of the array laid out as ``[R, 128]``
    lanes.  Window ``k`` is placed so that its lane ``i`` holds row
    element ``i`` whenever element ``i`` falls in block ``k``;
  * **in the kernel** (grid over tiles of pairs): the starts arrive in
    SMEM, each window's ``W`` array rows are copied HBM→VMEM by DMA, a
    dynamic lane rotate aligns the window, and a static-shape select keeps
    the lanes that belong to block ``k``.  No vector gather and no dynamic
    index into a value, for every ``(Z, d)``.

The ±1 sign hash (``use_sign``) is applied after the kernel, and bf16 or
int8 arrays are widened to f32 in the padded copy (exact), so the kernel
itself only ever moves f32.  ``qrobe_lookup_pallas`` reuses the same
gather over the dequantized array.

Validated in interpret mode against ``repro.kernels.ref`` (tests/
test_kernels.py, tests/test_kernel_conformance.py) and compiled for a
v5e at published widths by tests/test_tpu_compile.py.

The jnp block gather (``robe_lookup_blocks``) reads the same slots with
the compiler's own gather, which is fast on a TPU only for an element or
a whole row of a 2-D array: a slice of Z slots from the 1-D array, or two
rows in one slice, becomes a loop of one dynamic slice per block, slower
than the element gather.  So the array is laid out as a table of
overlapping 128-slot rows (``strided_rows``), every block lies inside one
row, and a block costs one hash, one gathered row and log2(ROW_STRIDE)
select stages that align it.  The table is built anew on every call, so
its 16 copies of the array are paid whatever the batch.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import add64, mul32
from repro.core.robe import RobeSpec, robe_signs
from repro.kernels.tiling import LANES, pair_tile, round_up


def n_segments(dim: int, z: int) -> int:
    """Most Z-blocks one ``dim``-element row can touch.

    A row starts at ``x·d``, whose offset inside its block is a multiple
    of ``gcd(d, Z)``; the worst start is ``Z − gcd`` and spans
    ``(Z − gcd + d − 1) // Z + 1`` blocks (``d/Z`` when Z | d, 1 when
    d | Z)."""
    g = math.gcd(dim, z)
    return (z - g + dim - 1) // z + 1


def window_rows(dim: int) -> int:
    """``[*, 128]`` array rows one ``dim``-element window can straddle."""
    return (dim + LANES - 2) // LANES + 1


def _padded_len(m: int, dim: int, z: int) -> int:
    """Slots in the padded ``[R, 128]`` copy of an ``m``-slot array: room
    for the farthest window (start ≤ front + m + Z − 2, plus its rows)."""
    front = n_segments(dim, z) * z
    return round_up(front + m + z + LANES * window_rows(dim), LANES)


def gather_bytes(m: int, dim: int, z: int, n_pairs: int,
                 itemsize: int = 4) -> int:
    """HBM bytes one ``robe_gather`` call moves for ``n_pairs`` (row,
    field) pairs over an ``m``-slot array of ``itemsize``-byte slots: the
    per-call padded f32 copy (the array read, the copy written), every
    pair's ``n_segments`` windows of ``window_rows · 128`` f32 read by DMA,
    its SMEM starts, and the ``[n_pairs, dim]`` f32 result written."""
    s = n_segments(dim, z)
    copy = m * itemsize + _padded_len(m, dim, z) * 4
    windows = n_pairs * s * window_rows(dim) * LANES * 4
    return copy + windows + n_pairs * (s + 1) * 4 + n_pairs * dim * 4


def _circular_rows(memory: jnp.ndarray, front: int, min_len: int
                   ) -> jnp.ndarray:
    """Copy of the circular array as ``[R, 128]``, in its dtype: padded
    index ``j`` holds slot ``(j − front) mod |M|`` for ``j < R·128``
    (R·128 ≥ ``min_len``)."""
    m = memory.shape[0]
    total = round_up(max(min_len, front + m), LANES)
    back = total - front - m
    reps = -(-max(front, back) // m)
    ext = jnp.tile(memory, reps) if reps > 1 else memory
    flat = jnp.concatenate([ext[ext.shape[0] - front:], memory, ext[:back]])
    return flat.reshape(total // LANES, LANES)


def block_starts(spec: RobeSpec, table_ids: Tuple[int, ...],
                 rows: jnp.ndarray, dim: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """First slots [N, S] (int32, in [0, |M|)) of the Z-blocks each pair
    touches, and each pair's offset inside its first block [N], for rows
    [B, F] (N = B·F, row-major): one hash per block.

    Block ``k`` of row ``x`` holds elements ``i`` with
    ``(off0 + i) >> log2 Z == k``, at slots ``h(e, b0 + k) + j`` for
    ``j < Z`` (mod |M|); ``off0`` is 0 when Z | d."""
    s = n_segments(dim, spec.block_size)
    lz = spec.log2_z
    x = rows.astype(jnp.uint32).reshape(-1)
    t = jnp.broadcast_to(jnp.asarray(table_ids, jnp.uint32)[None, :],
                         rows.shape).reshape(-1)
    hi, lo = mul32(x, jnp.uint32(dim))                    # x·d, exact
    if lz == 0:
        b_hi, b_lo = hi, lo
    else:
        b_lo = (lo >> lz) | (hi << (32 - lz))
        b_hi = hi >> lz
    off0 = (lo & jnp.uint32(spec.block_size - 1)).astype(jnp.int32)
    h = spec.hash_fn()
    starts = []
    for k in range(s):
        k_hi, k_lo = add64(b_hi, b_lo, jnp.uint32(k))
        starts.append(h(t, k_hi, k_lo).astype(jnp.int32))
    return jnp.stack(starts, axis=1), off0


def window_starts(spec: RobeSpec, table_ids: Tuple[int, ...],
                  rows: jnp.ndarray, dim: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Window starts [N, S] (int32, into the padded copy) and each pair's
    offset inside its first block [N] for rows [B, F].

    Window ``k`` starts at ``front + h(e, b0 + k) + off0 − k·Z`` so that
    window lane ``i`` is element ``i`` (``front = S·Z`` keeps every start
    ≥ 0)."""
    base, off0 = block_starts(spec, table_ids, rows, dim)
    z = spec.block_size
    s = base.shape[1]
    k = jnp.arange(s, dtype=jnp.int32) * z
    return s * z + base + off0[:, None] - k, off0


def _gather_kernel(n_seg: int, log2_z: int, dim: int, n_rows: int,
                   pairs: int, starts_ref, off0_ref, mem_hbm, out_ref,
                   stage, sem):
    """One tile of ``pairs`` (row, field) pairs -> out_ref [pairs, dim]."""
    n_blk = -(-dim // LANES)                 # 128-lane blocks per row
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def copy(r, k):
        t = starts_ref[r * n_seg + k]
        return pltpu.make_async_copy(
            mem_hbm.at[pl.ds(t // LANES, n_rows)], stage.at[r], sem)

    def segment(k, c):
        def issue(r, c):
            copy(r, k).start()
            return c

        def drain(r, c):
            copy(r, k).wait()
            return c

        def place(r, c):
            t = starts_ref[r * n_seg + k]
            sh = t % LANES
            # rolled[w, j] = window row w at lane (j + sh) % 128
            rolled = pltpu.roll(stage[r], (LANES - sh) % LANES, 1)
            for a in range(n_blk):
                w = min(LANES, dim - a * LANES)
                win = jnp.where(lane + sh < LANES, rolled[a:a + 1],
                                rolled[a + 1:a + 2])[:, :w]
                dst = (pl.ds(r, 1), pl.ds(a * LANES, w))
                if n_seg > 1:
                    # keep only the lanes whose element falls in block k
                    seg = (off0_ref[r] + lane[:, :w] + a * LANES) >> log2_z
                    win = jnp.where(seg == k, win, out_ref[dst])
                out_ref[dst] = win
            return c

        jax.lax.fori_loop(0, pairs, issue, 0)
        jax.lax.fori_loop(0, pairs, drain, 0)
        return jax.lax.fori_loop(0, pairs, place, c)

    jax.lax.fori_loop(0, n_seg, segment, 0)


def gather_windows(mem_rows: jnp.ndarray, starts: jnp.ndarray,
                   off0: jnp.ndarray, dim: int, log2_z: int,
                   interpret: bool, name: str) -> jnp.ndarray:
    """[N, S] window starts into the padded ``[R, 128]`` array -> [N, dim]
    f32 rows (the Pallas call, named ``name``; ``N`` is padded
    internally)."""
    n, n_seg = starts.shape
    n_rows = window_rows(dim)
    pairs, n_pad = pair_tile(n)
    if n_pad != n:           # padded pairs re-read pair 0's windows
        starts = jnp.concatenate(
            [starts, jnp.broadcast_to(starts[:1], (n_pad - n, n_seg))])
        off0 = jnp.concatenate([off0, jnp.broadcast_to(off0[:1],
                                                       (n_pad - n,))])
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n_seg, log2_z, dim, n_rows,
                          pairs),
        grid=(n_pad // pairs,),
        in_specs=[
            pl.BlockSpec((pairs * n_seg,), lambda i: (i,),
                         memory_space=pltpu.SMEM),           # window starts
            pl.BlockSpec((pairs,), lambda i: (i,),
                         memory_space=pltpu.SMEM),           # block offsets
            pl.BlockSpec(memory_space=pl.ANY),               # M stays in HBM
        ],
        out_specs=pl.BlockSpec((pairs, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((pairs, n_rows, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name=name,
    )(starts.reshape(-1), off0, mem_rows)
    return out[:n] if n_pad != n else out


def robe_gather(memory: jnp.ndarray, rows: jnp.ndarray,
                table_ids: Tuple[int, ...], dim: int, spec: RobeSpec,
                interpret: bool, name: str) -> jnp.ndarray:
    """[B, F] rows -> [B, F, dim] f32 (signs applied) from any-dtype M,
    through the gather kernel named ``name``."""
    b, f = rows.shape
    starts, off0 = window_starts(spec, table_ids, rows, dim)
    front = starts.shape[1] * spec.block_size
    mem_rows = _circular_rows(memory.astype(jnp.float32), front,
                              _padded_len(memory.shape[0], dim,
                                          spec.block_size))
    out = gather_windows(mem_rows, starts, off0, dim, spec.log2_z,
                         interpret, name).reshape(b, f, dim)
    if spec.use_sign:
        tids = jnp.asarray(table_ids, jnp.uint32)[None, :]
        out = out * robe_signs(spec, tids, rows, dim)
    return out


#: bytes of gathered table rows above which a batch is gathered in pieces
CHUNK_BYTES = 1 << 31


#: slots between neighbouring rows of the block gather's table, which then
#: holds the array 128 / ROW_STRIDE = 16 times; a block takes
#: log2(ROW_STRIDE) = 3 select stages (on a v5e, 8 was faster than 16 at
#: 65,536 rows of d = 128 and 262,144 rows of d = 64)
ROW_STRIDE = 8


def strided_rows(memory: jnp.ndarray, stride: int, width: int
                 ) -> jnp.ndarray:
    """The circular array as overlapping rows, in memory.dtype: row
    ``i·R + a`` holds slots ``128·a + stride·i + j`` (mod |M|) for
    ``j < width``, ``a < R = ceil(|M| / 128)`` and ``i < 128 / stride``
    (``stride`` divides 128, ``width`` is a multiple of 128).  Each of the
    ``128 / stride`` parts is a lane-dense copy of the ``[R, 128]``
    array, shifted by ``stride·i`` slots."""
    m = memory.shape[0]
    q = width // LANES
    n = (m - 1) // LANES + 1                   # 128-slot rows holding a start
    flat = _circular_rows(memory, 0, (n + q) * LANES)
    ext = jnp.concatenate([flat[j:j + n] for j in range(q + 1)], axis=1)
    return jnp.concatenate([ext[:, stride * i:stride * i + width]
                            for i in range(LANES // stride)])


def _shift_lanes(x: jnp.ndarray, shift: jnp.ndarray, width: int, top: int,
                 unit: int = 1) -> jnp.ndarray:
    """``out[n, j] = x[n, shift[n] + j]`` for ``j < width``, where each
    ``shift[n]`` is a multiple of ``unit`` below ``top`` (powers of two)
    and ``x`` has at least ``width + top − unit`` lanes: one select stage
    per bit of the shift, each narrowing ``x`` to what is left."""
    s = top // 2
    while s >= unit:
        need = width + s - unit
        x = jnp.where((shift & s)[:, None] != 0, x[:, s:s + need],
                      x[:, :need])
        s //= 2
    return x[:, :width]


def gather_blocks(table: jnp.ndarray, starts: jnp.ndarray, z: int,
                  stride: int) -> jnp.ndarray:
    """[K] block starts in [0, |M|) -> [K, Z]: slots ``start + j`` (mod
    |M|) for ``j < Z``, one row of ``strided_rows`` per block (the row
    that begins at ``start`` rounded down to ``stride``), aligned by
    ``start % stride``."""
    log2_lanes = LANES.bit_length() - 1
    part = (starts & (LANES - 1)) >> (stride.bit_length() - 1)
    row = part * (table.shape[0] * stride // LANES) + (starts >> log2_lanes)
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))
    win = jax.lax.gather(table, row[:, None], dnums, (1, table.shape[1]),
                         mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return _shift_lanes(win, starts & (stride - 1), z, stride)


def robe_lookup_blocks(memory: jnp.ndarray, rows: jnp.ndarray,
                       table_ids: Tuple[int, ...], dim: int,
                       spec: RobeSpec) -> jnp.ndarray:
    """[B, F] rows -> [B, F, dim] in memory.dtype by whole Z-blocks: one
    hash and one gathered table row per block, bit-identical to
    ``repro.core.robe.robe_lookup``.  When Z | d every row is its
    ``d / Z`` blocks end to end; otherwise its ``d`` elements start at
    ``off0`` inside the blocks laid end to end.  A batch whose gathered
    rows pass ``CHUNK_BYTES`` is gathered in pieces of rows, which bounds
    the temporaries."""
    b, f = rows.shape
    z = spec.block_size
    s = n_segments(dim, z)
    width = round_up(z + ROW_STRIDE - 1, LANES)
    pieces = -(-b * f * s * width * memory.dtype.itemsize // CHUNK_BYTES)
    b_c = -(-b // pieces)

    def piece(part):                                    # [b_c, F]
        starts, off0 = block_starts(spec, table_ids, part, dim)
        n = starts.shape[0]
        # block-major order: the starts and the gathered rows stay in the
        # layout the hash computes them in
        out = gather_blocks(table, starts.T.reshape(-1), z, ROW_STRIDE)
        out = out.reshape(s, n, z).transpose(1, 0, 2).reshape(n, s * z)
        if dim % z:
            out = _shift_lanes(out, off0, dim, z, math.gcd(dim, z))
        return out.reshape(part.shape[0], f * dim)

    with jax.named_scope("robe_blocks"):
        table = strided_rows(memory, ROW_STRIDE, width)
        out = [piece(rows[k:k + b_c]) for k in range(0, b, b_c)]
        out = (jnp.concatenate(out) if len(out) > 1 else out[0]
               ).reshape(b, f, dim)
    if spec.use_sign:
        tids = jnp.asarray(table_ids, jnp.uint32)[None, :]
        out = out * robe_signs(spec, tids, rows, dim).astype(out.dtype)
    return out


@functools.partial(jax.jit, static_argnames=("spec", "dim", "table_ids",
                                             "interpret"))
def robe_lookup_pallas(memory: jnp.ndarray, rows: jnp.ndarray,
                       table_ids: Tuple[int, ...], dim: int, spec: RobeSpec,
                       interpret: bool = True) -> jnp.ndarray:
    """Fused ROBE lookup: [B, F] int rows -> [B, F, dim] in memory.dtype."""
    return robe_gather(memory, rows, table_ids, dim, spec, interpret,
                       "robe_gather").astype(memory.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "dim", "table_ids",
                                             "group_log2", "interpret"))
def qrobe_lookup_pallas(codes: jnp.ndarray, scale: jnp.ndarray,
                        rows: jnp.ndarray, table_ids: Tuple[int, ...],
                        dim: int, spec: RobeSpec, group_log2: int,
                        interpret: bool = True) -> jnp.ndarray:
    """int8 ROBE lookup: the same DMA gather over the dequantized array.

    codes: [|M|] int8; scale: [ceil(|M| / 2**group_log2)] learned per-group
    scales.  Slot ``s`` dequantizes in f32 as ``codes[s] ·
    scale[s >> group_log2]`` before the gather, so the result is
    bit-identical to ``repro.kernels.ref.qrobe_lookup_ref`` (one rounding,
    into ``scale.dtype``).  The dequantized copy is 4 B per slot: this
    path does not yet read int8 from HBM (ROADMAP queue 1).
    """
    m = codes.shape[0]
    group = jnp.repeat(scale.astype(jnp.float32), 1 << group_log2)[:m]
    deq = codes.astype(jnp.float32) * group
    return robe_gather(deq, rows, table_ids, dim, spec, interpret,
                       "qrobe_gather").astype(scale.dtype)
