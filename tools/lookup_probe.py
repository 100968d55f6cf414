"""Times formulations of the ROBE lookup's forward alone, and of its
backward (the VJP with respect to the array) alone, at a benchmark
configuration's widths, and checks each against the element gather or
the element scatter.

    python tools/lookup_probe.py bench/configs/dlrm-rm2-robe.json \\
        --batch 65536 --variants elem,a,b,f,s64,blocks [--calls 5] [--seed 1]
    python tools/lookup_probe.py bench/configs/dlrm-criteo-tb-robe.json \\
        --batch 65536,16384 --variants elem-bwd,blocks-bwd,sorted-bwd

``--batch`` may list several sizes (``--batch 1,16,512``), each timed in
turn.  Each variant is jitted alone for ``[batch, fields]`` ids drawn as the
benchmark's traffic draws them (Zipf 1.05 per field) and an array of
N(0, 0.01²) slots.  A variant's time is the median, over ``--calls``
calls on each of two batches, of the host clock around one call and its
``block_until_ready``.  ``exact`` says whether a forward returned the
element gather's values bit for bit (``null`` when ``elem`` was not run
first).  A backward takes a cotangent made from the ids and returns the
array's f32 gradient; ``gap`` is its largest gap to ``elem-bwd``'s over
``elem-bwd``'s largest magnitude (``null`` when ``elem-bwd`` was not run
first; the sums run in another order, so it is not 0).  One JSON line
per variant goes to stdout.

Variants (the last four need Z | d):

- ``elem``: one hash and one gathered slot per element
  (``kernels.ref.robe_lookup_ref``, the default forward before the
  block gather);
- ``blocks``: the default forward, ``kernels.robe_lookup.
  robe_lookup_blocks`` (a table of 128-slot rows every ``ROW_STRIDE``
  slots, one gathered row per Z-block);
- ``blocks-bf16``: the same, cast to bfloat16 inside the program, as the
  model casts the embeddings;
- ``a``: a gather of Z-slot slices from the circular 1-D array;
- ``b``: a gather of the two 128-slot rows each block straddles, as one
  ``(2, 128)`` slice of the ``[R, 128]`` array, aligned by 7 select
  stages;
- ``f``: the same two rows as two ``(1, 128)`` row gathers;
- ``s<k>``: ``blocks`` with a table of rows every ``k`` slots (``k`` a
  power of two from 8 to 64; ``s64`` holds the array twice), whole batch
  in one piece.

Backward variants (``sorted-bwd`` and ``s<k>-bwd`` need Z | d):

- ``elem-bwd``: one hash and one scatter-add per element (``core.robe.
  robe_slots``, the backward before the block scatter);
- ``blocks-bwd``: the default backward, ``kernels.ops.robe_lookup``'s VJP
  (``kernels.robe_lookup.robe_scatter_blocks``: the transpose of the block
  gather, one row scatter-add per Z-block into the table of strided rows,
  then the fold of its copies into the array);
- ``sorted-bwd``: the same with the block keys sorted first, all of the
  batch's at once with an iota payload: the blocks gathered in that
  order, one row scatter-add that states its indices sorted;
- ``s<k>-bwd``: ``blocks-bwd`` with a table of rows every ``k`` slots,
  whole batch in one piece.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.core.robe import RobeSpec, robe_slots            # noqa: E402
from repro.kernels import ref                               # noqa: E402
from repro.kernels.ops import robe_lookup                   # noqa: E402
from repro.kernels.tiling import LANES                       # noqa: E402
from repro.kernels.robe_lookup import (                     # noqa: E402
    ROW_STRIDE, _ROW_SCATTER, _block_rows, _circular_rows, _shift_lanes,
    _transpose, block_starts, gather_blocks, robe_lookup_blocks,
    scatter_blocks, strided_rows)

PROMISE = jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS


def _slices(memory, starts, z):
    """(a) [K] block starts -> [K, Z] by Z-slot slices of the 1-D array."""
    padded = jnp.concatenate([memory, memory[:z - 1]])
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
    return jax.lax.gather(padded, starts[:, None], dn, (z,), mode=PROMISE)


def _two_rows(memory, starts, z, separate):
    """(b), (f) [K] block starts -> [K, Z] from the two 128-slot rows of
    the ``[R, 128]`` array that each block straddles."""
    m = memory.shape[0]
    rows = _circular_rows(memory, 0, m - 1 + 2 * LANES)
    r = starts // LANES
    if separate:
        idx = jnp.stack([r, r + 1], axis=1)[..., None]
        dn = jax.lax.GatherDimensionNumbers(
            offset_dims=(2,), collapsed_slice_dims=(0,),
            start_index_map=(0,))
        win = jax.lax.gather(rows, idx, dn, (1, LANES), mode=PROMISE)
    else:
        dn = jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 2), collapsed_slice_dims=(),
            start_index_map=(0,))
        win = jax.lax.gather(rows, r[:, None], dn, (2, LANES), mode=PROMISE)
    return _shift_lanes(win.reshape(-1, 2 * LANES), starts % LANES, z, LANES)


def _strided(memory, starts, z, stride):
    """(s<k>) [K] block starts -> [K, Z]: one row of a table of rows every
    ``stride`` slots per block."""
    width = -(-(z + stride - 1) // LANES) * LANES
    return gather_blocks(strided_rows(memory, stride, width), starts, z,
                         stride)


def cotangent(ids, dim: int):
    """[B, F, dim] f32 cotangent made from the ids, one value in 13 per
    element, of the size of a train step's embedding gradients."""
    lane = jnp.arange(dim, dtype=jnp.int32)
    return (((ids[..., None] % 1009) * 7 + lane * 3) % 13 - 6
            ).astype(jnp.float32) * 1e-3


def _blocks_bwd(spec, tids, dim, stride, sort):
    """(s<k>-bwd, sorted-bwd) ``(memory, ids) -> [|M|]`` f32: the
    transpose of the block gather, whole batch in one piece; ``sort``
    sorts the block keys first and states the scatter's indices
    sorted."""
    z = spec.block_size
    width = -(-(z + stride - 1) // LANES) * LANES
    n_rows = ((spec.size - 1) // LANES + 1) * (LANES // stride)

    def fn(m, ids):
        starts, _ = block_starts(spec, tids, ids, dim)          # [N, S]
        starts = starts.reshape(-1)
        blocks = cotangent(ids, dim).reshape(-1, z)
        table = jnp.zeros((n_rows, width), jnp.float32)
        if sort:
            row = _block_rows(n_rows, starts, stride)
            row, perm = jax.lax.sort_key_val(
                row, jnp.arange(row.shape[0], dtype=jnp.int32))
            starts, blocks = starts[perm], blocks[perm]
            win = _transpose(lambda w: _shift_lanes(
                w, starts & (stride - 1), z, stride),
                (blocks.shape[0], width), blocks)
            table = jax.lax.scatter_add(table, row[:, None], win,
                                        _ROW_SCATTER,
                                        indices_are_sorted=True,
                                        mode=PROMISE)
        else:
            table = scatter_blocks(table, starts, blocks, stride)
        return _transpose(lambda mem: strided_rows(mem, stride, width),
                          (spec.size,), table)
    return fn


def _backward(name: str, spec: RobeSpec, tids, dim: int):
    """The jittable ``(memory, ids) -> [|M|]`` f32 of a backward variant:
    the array's gradient for ``cotangent(ids)``."""
    if name == "elem-bwd":
        def fn(m, ids):
            slots = robe_slots(spec, jnp.asarray(tids, jnp.uint32)[None],
                               ids, dim).reshape(-1).astype(jnp.int32)
            return jnp.zeros(m.shape, jnp.float32).at[slots].add(
                cotangent(ids, dim).reshape(-1))
        return fn
    if name == "blocks-bwd":
        def fn(m, ids):
            _, vjp = jax.vjp(
                lambda mm: robe_lookup(mm, ids, tids, dim, spec, False), m)
            return vjp(cotangent(ids, dim))[0]
        return fn
    if dim % spec.block_size:
        raise SystemExit(f"variant {name} needs Z | d "
                         f"(Z={spec.block_size}, d={dim})")
    if name == "sorted-bwd":
        return _blocks_bwd(spec, tids, dim, ROW_STRIDE, True)
    if name[0] == "s" and name[1:-4] in ("8", "16", "32", "64"):
        return _blocks_bwd(spec, tids, dim, int(name[1:-4]), False)
    raise SystemExit(f"unknown variant {name}")


def variant(name: str, spec: RobeSpec, fields: int, dim: int):
    """The jittable ``(memory, ids) -> [B, F, dim]`` of a forward variant,
    or ``-> [|M|]`` of a backward one (its name ends in ``-bwd``)."""
    tids = tuple(range(fields))
    z = spec.block_size
    if name.endswith("-bwd"):
        return _backward(name, spec, tids, dim)
    if name == "elem":
        return lambda m, ids: ref.robe_lookup_ref(
            m, ids, jnp.asarray(tids, jnp.uint32), dim, spec)
    if name == "blocks":
        return lambda m, ids: robe_lookup_blocks(m, ids, tids, dim, spec)
    if name == "blocks-bf16":
        return lambda m, ids: robe_lookup_blocks(
            m, ids, tids, dim, spec).astype(jnp.bfloat16)
    if dim % z:
        raise SystemExit(f"variant {name} needs Z | d (Z={z}, d={dim})")
    per_block = {"a": lambda m, st: _slices(m, st, z),
                 "b": lambda m, st: _two_rows(m, st, z, False),
                 "f": lambda m, st: _two_rows(m, st, z, True)}
    if name in per_block:
        blocks = per_block[name]
    elif name.startswith("s") and name[1:] in ("8", "16", "32", "64"):
        blocks = lambda m, st: _strided(m, st, z, int(name[1:]))  # noqa: E731
    else:
        raise SystemExit(f"unknown variant {name}")

    def fn(m, ids):
        starts, _ = block_starts(spec, tids, ids, dim)          # [N, S]
        return blocks(m, starts.reshape(-1)).reshape(*ids.shape, dim)
    return fn


def draw_ids(vocab: np.ndarray, batch: int, seed, zipf=1.05):
    """[batch, F] int32 ids, as the benchmark's traffic draws them;
    ``seed`` is a whole number ≥ 0 of any size, or a sequence of them."""
    u = np.random.default_rng(seed).random((batch, len(vocab)))
    skew = u ** (1.0 / zipf)
    ids = (skew * skew * vocab[None, :]).astype(np.int64)
    return np.minimum(ids, vocab[None, :] - 1).astype(np.int32)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a benchmark configuration's JSON file")
    ap.add_argument("--batch", required=True,
                    help="rows per call; a comma-separated list for several")
    ap.add_argument("--variants", default="elem,blocks")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    with open(args.config) as fh:
        cfg = json.load(fh)
    vocab = np.asarray(cfg["vocab_sizes"], np.int64)
    dim = cfg["embed_dim"]
    spec = RobeSpec(size=cfg["robe_size"], block_size=cfg["robe_block"],
                    seed=cfg["robe_hash"]["seed"])
    memory = jnp.asarray(np.random.default_rng([args.seed, 2]).normal(
        0.0, 0.01, spec.size).astype(np.float32))
    for batch in (int(b) for b in args.batch.split(",")):
        ids = [jnp.asarray(draw_ids(vocab, batch, [args.seed, k]))
               for k in range(2)]
        time_variants(cfg["name"], spec, dim, memory, ids,
                      args.variants.split(","), args.calls)


def time_variants(config, spec, dim, memory, ids, names, calls):
    """Compile, check and time each variant on the two batches ``ids``;
    one JSON line each."""
    want = want_bwd = None
    for name in names:
        t0 = time.perf_counter()
        fn = jax.jit(variant(name, spec, ids[0].shape[1], dim))
        compiled = fn.lower(memory, ids[0]).compile()
        compile_s = time.perf_counter() - t0
        out = compiled(memory, ids[0]).block_until_ready()
        check = {}
        if name == "elem":
            want, check["exact"] = out, True
        elif name == "elem-bwd":
            want_bwd, check["gap"] = out, 0.0
        elif name.endswith("-bwd"):
            check["gap"] = None if want_bwd is None else float(
                jnp.max(jnp.abs(out - want_bwd))
                / jnp.max(jnp.abs(want_bwd)))
        else:
            check["exact"] = (None if want is None else bool(jnp.array_equal(
                out.astype(jnp.float32), want.astype(out.dtype)
                .astype(jnp.float32))))
        del out
        times = []
        for _ in range(calls):
            for x in ids:
                t0 = time.perf_counter()
                compiled(memory, x).block_until_ready()
                times.append(time.perf_counter() - t0)
        print(json.dumps({
            "config": config, "batch": ids[0].shape[0], "variant": name,
            "device": jax.devices()[0].device_kind,
            "median_ms": statistics.median(times) * 1e3,
            "min_ms": min(times) * 1e3, "calls": len(times),
            "compile_s": compile_s, **check}), flush=True)


if __name__ == "__main__":
    main()
