"""ROBE — Random Offset Block Embedding Array (paper §2).

A single 1-D circular array ``M`` of ``spec.size`` float slots replaces every
embedding table in the model.  Element ``i`` of row ``x`` of table ``e`` is
stored at

    slot(e, x, i) = ( h(e, Z_id) + Z_off ) mod |M|
    Z_id  = (x*d + i) >> log2(Z)          # block id  (Eq. 3)
    Z_off = (x*d + i) &  (Z - 1)          # offset inside block

with ``h`` a 2-universal hash into [0, |M|).  ``Z`` must be a power of two
(every setting in the paper — 1/2/4/8/16/32 — is), which lets the 64-bit
block-id computation be a limb-wise shift instead of a 64-bit division.

The jnp path below is the element-wise definition: the reference that the
lookups of the model and their backward are tested against, and the path
of the bag lookup and the LM stack.  The model's lookup, ``repro.kernels.
ops.robe_lookup``, reads the same slots a whole Z-block at a time: by
default the jnp block gather (``repro.kernels.robe_lookup.
robe_lookup_blocks``: one hash and one gathered row per block), or the
Pallas kernel (block-coalesced DMA reads); its backward adds into them a
whole Z-block at a time too (``robe_scatter_blocks``); all are validated
against this module.
Models never call this module directly: the consumer-facing surface is the
``robe`` ``EmbeddingBackend`` (``repro.nn.embedding_backends.robe``), which
owns placement, PartitionSpecs, and the roofline cost model on top of the
hash math here.

Backward pass: JAX autodiff through the gather produces exactly the paper's
Fig. 2 scatter-add — gradients of all aliased parameters accumulate into the
shared slot.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import UHash, add64, mul32

__all__ = ["RobeSpec", "init_memory", "robe_slots", "robe_signs",
           "robe_lookup", "robe_lookup_bag"]


@dataclasses.dataclass(frozen=True)
class RobeSpec:
    """Static configuration of one ROBE array."""
    size: int                 # |M|: number of float32 slots
    block_size: int = 32      # Z (power of two)
    seed: int = 0
    use_sign: bool = False    # paper's optional g(e,x,i) ∈ {±1}
    init_scale: float = 0.01

    def __post_init__(self):
        z = self.block_size
        if z < 1 or (z & (z - 1)) != 0:
            raise ValueError(f"block_size must be a power of two, got {z}")
        if self.size <= z:
            raise ValueError("ROBE array must be larger than one block")

    @property
    def log2_z(self) -> int:
        return int(self.block_size).bit_length() - 1

    def hash_fn(self) -> UHash:
        return UHash.draw(self.seed, self.size, salt=1)

    def sign_fn(self) -> UHash:
        return UHash.draw(self.seed, 2, salt=2)

    @property
    def bytes(self) -> int:
        return self.size * 4


def init_memory(rng: jax.Array, spec: RobeSpec,
                dtype=jnp.float32) -> jnp.ndarray:
    """The learnable array M (the entire embedding memory of the model)."""
    return (jax.random.normal(rng, (spec.size,), dtype=jnp.float32)
            * spec.init_scale).astype(dtype)


def _element_index64(rows: jnp.ndarray, dim: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) uint32 limbs of x*d + i for i in [0, dim). Shape rows+(dim,)."""
    rows = rows.astype(jnp.uint32)[..., None]
    hi, lo = mul32(rows, jnp.uint32(dim))
    shape = lo.shape[:-1] + (dim,)
    hi = jnp.broadcast_to(hi, shape)
    lo = jnp.broadcast_to(lo, shape)
    i = jnp.broadcast_to(jnp.arange(dim, dtype=jnp.uint32), shape)
    return add64(hi, lo, i)


def robe_slots(spec: RobeSpec, table_ids, rows: jnp.ndarray,
               dim: int) -> jnp.ndarray:
    """Slot indices into M for each element of each requested row.

    table_ids: scalar or broadcastable-to-``rows`` int array (table id e).
    rows:      int array [...] of row indices x.
    returns:   uint32 array [..., dim] of slots in [0, |M|).
    """
    h = spec.hash_fn()
    hi, lo = _element_index64(rows, dim)
    lz = spec.log2_z
    if lz == 0:
        b_hi, b_lo = hi, lo
        off = jnp.zeros_like(lo)
    else:
        b_lo = (lo >> lz) | (hi << (32 - lz))
        b_hi = hi >> lz
        off = lo & jnp.uint32(spec.block_size - 1)
    t = jnp.broadcast_to(jnp.asarray(table_ids, dtype=jnp.uint32),
                         rows.shape)[..., None]
    t = jnp.broadcast_to(t, b_lo.shape)
    base = h(t, b_hi, b_lo)
    slot = base + off
    m = jnp.uint32(spec.size)
    return jnp.where(slot >= m, slot - m, slot)  # circular array wrap


def robe_signs(spec: RobeSpec, table_ids, rows: jnp.ndarray,
               dim: int) -> jnp.ndarray:
    """±1 signs g(e,x,i) (independent hash), float32 [..., dim]."""
    g = spec.sign_fn()
    hi, lo = _element_index64(rows, dim)
    t = jnp.broadcast_to(jnp.asarray(table_ids, dtype=jnp.uint32),
                         rows.shape)[..., None]
    t = jnp.broadcast_to(t, lo.shape)
    bit = g(t, hi, lo)
    return (1 - 2 * bit.astype(jnp.int32)).astype(jnp.float32)


def robe_lookup(memory: jnp.ndarray, spec: RobeSpec, table_ids,
                rows: jnp.ndarray, dim: int) -> jnp.ndarray:
    """Embedding lookup through the ROBE array (jnp reference path).

    memory: [|M|] learnable array.
    returns [..., dim] embeddings, dtype of ``memory``.
    """
    slots = robe_slots(spec, table_ids, rows, dim)
    emb = jnp.take(memory, slots.astype(jnp.int32), axis=0)
    if spec.use_sign:
        emb = emb * robe_signs(spec, table_ids, rows, dim).astype(emb.dtype)
    return emb


def robe_lookup_bag(memory: jnp.ndarray, spec: RobeSpec, table_ids,
                    rows: jnp.ndarray, dim: int,
                    weights: Optional[jnp.ndarray] = None,
                    combiner: str = "sum") -> jnp.ndarray:
    """EmbeddingBag through ROBE: multi-hot rows [..., bag] → pooled [..., dim].

    JAX has no native EmbeddingBag; this is gather + (weighted) reduce, the
    pattern called out in the assignment. ``rows[..., bag]`` may be padded
    with -1 (masked out).
    """
    mask = (rows >= 0)
    safe = jnp.where(mask, rows, 0)
    tids = jnp.asarray(table_ids, jnp.uint32)[..., None]      # per-field id
    emb = robe_lookup(memory, spec, tids, safe, dim)          # [..., bag, dim]
    w = mask.astype(emb.dtype)
    if weights is not None:
        w = w * weights.astype(emb.dtype)
    emb = emb * w[..., None]
    out = emb.sum(axis=-2)
    if combiner == "mean":
        # true weighted mean: fractional weight mass < 1 must not be
        # clamped away; empty bags (mass 0) pool to zero
        mass = w.sum(axis=-1, keepdims=True)
        out = jnp.where(mass > 0, out / jnp.where(mass > 0, mass, 1.0), 0.0)
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner}")
    return out


# ---------------------------------------------------------------------------
# Sketch interface used by the theory tests (paper §3): project an explicit
# parameter vector θ ∈ R^n into R^m with the ROBE-Z sketching matrix.
# ---------------------------------------------------------------------------

def sketch_vector(theta: np.ndarray, spec: RobeSpec) -> np.ndarray:
    """ROBE-Z sketch ˆθ ∈ R^m of θ ∈ R^n (numpy; test/analysis helper).

    Equivalent to multiplying by the sketching matrix of Fig. 3b: every
    element lands in its hashed slot (sign-weighted if use_sign).
    """
    n = theta.shape[0]
    slots = np.asarray(robe_slots(spec, 0, jnp.arange(n), 1))[:, 0]
    out = np.zeros(spec.size, dtype=np.float64)
    s = np.asarray(robe_signs(spec, 0, jnp.arange(n), 1))[:, 0] \
        if spec.use_sign else np.ones(n)
    np.add.at(out, slots, theta * s)
    return out


def unsketch_vector(mem: np.ndarray, n: int, spec: RobeSpec) -> np.ndarray:
    """Read every θ_i back out of the sketch (the lookup direction)."""
    slots = np.asarray(robe_slots(spec, 0, jnp.arange(n), 1))[:, 0]
    s = np.asarray(robe_signs(spec, 0, jnp.arange(n), 1))[:, 0] \
        if spec.use_sign else np.ones(n)
    return mem[slots] * s
