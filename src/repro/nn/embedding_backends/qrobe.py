"""``qrobe`` — the ROBE array stored as int8 with learned per-group scales.

The paper's 1000× compression keeps the array in f32; the model-size
trade-offs follow-up (PAPERS.md) shows the next regime comes from shrinking
bytes-per-weight.  This substrate stores the shared circular array as int8
codes plus one learned f32 scale per ``GROUP_SIZE``-slot group, ALPT-style:

* **forward** — ``repro.kernels.ops.qrobe_lookup`` gathers int8 codes
  through the unchanged ROBE hash and dequantizes them
  (``codes_f32 · scale_f32[slot >> GROUP_LOG2] · sign``, one rounding on
  delivery into ``scale.dtype``).  The jnp path gathers the int8 codes
  themselves, so its row reads are ~4× fewer bytes; the Pallas path
  dequantizes the whole array to f32 before its DMA gather and saves
  nothing yet.
* **scale training** — the scales are ordinary float leaves; the op's
  custom_vjp delivers their analytic gradient, so quantization is learned,
  not calibrated.
* **code training (straight-through)** — int8 leaves cannot carry float
  cotangents through ``jax.grad`` (their tangent type is float0).  The
  backend therefore adds a zero-valued f32 ``delta`` array to every lookup
  (outside the fused op, plain jnp — adding zeros changes nothing forward);
  autodiff routes exactly the memory cotangent of the dequantized array
  into ``delta``, the optimizer updates it like any dense leaf, and the
  post-step :meth:`project` hook folds ``codes·scale + delta`` back into
  fresh int8 codes under the (just-updated) scales and re-zeroes ``delta``
  — the dequantize → update → requantize cycle of ALPT, i.e. a
  straight-through estimator whose rounding happens once per step.

This is the first backend whose stored parameters are not what the math
sees, which is why the :class:`EmbeddingBackend` protocol grew the
``project`` hook — the groundwork for the DPQ / int4 entries of the same
ROADMAP item.  ``cacheable_rows`` is declined for now (the hot-row cache
speaks f32 memories).

Optimizer note: a scale's analytic gradient sums ``g · codes`` over its
group — code magnitudes reach ±127, so it runs ~two orders larger than
the underlying weight gradient.  Train with a per-coordinate adaptive
optimizer (adagrad / adam — what ALPT uses); plain SGD at an
embedding-tuned lr can blow the scales out in one step.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.robe import init_memory, robe_signs, robe_slots
from repro.nn.embedding_backends.base import EmbeddingBackend, \
    register_backend

#: slots per learned scale (power of two — the kernel indexes scales with a
#: shift, never a divide)
GROUP_SIZE = 256
GROUP_LOG2 = GROUP_SIZE.bit_length() - 1
#: scales below this are clamped during (re)quantization: a collapsed scale
#: would send every code to ±127 and freeze the group (scale-underflow
#: guard, exercised by tests/test_qrobe.py)
SCALE_FLOOR = 1e-8


def n_groups(size: int) -> int:
    return -(-size // GROUP_SIZE)


def _safe_scale(scale: jnp.ndarray) -> jnp.ndarray:
    """Sign-preserving divide-safe scales (|s| >= SCALE_FLOOR), f32."""
    s = scale.astype(jnp.float32)
    mag = jnp.maximum(jnp.abs(s), SCALE_FLOOR)
    return jnp.where(s < 0, -mag, mag)


def _expand(scale: jnp.ndarray, size: int) -> jnp.ndarray:
    """Per-group scales -> per-slot f32 scales of length ``size``."""
    gidx = jnp.arange(size, dtype=jnp.int32) >> GROUP_LOG2
    return jnp.take(scale.astype(jnp.float32), gidx, axis=0)


def quantize_array(w: jnp.ndarray, scale: jnp.ndarray):
    """f32 array -> (int8 codes, the scales used): saturating clip at ±127
    after rounding against the (floor-guarded) per-group scales."""
    s = _safe_scale(scale)
    q = jnp.round(w.astype(jnp.float32) / _expand(s, w.shape[0]))
    return jnp.clip(q, -127, 127).astype(jnp.int8), s


class QRobeBackend(EmbeddingBackend):
    name = "qrobe"
    local_batch = True           # replicated codes+scales, purely local
    cacheable_rows = None        # declined, as robe: the array IS the cache

    def validate(self, spec) -> None:
        if spec.robe is None:
            raise ValueError("robe spec required for kind='qrobe'")

    def init(self, key, spec, pad_rows_to: int = 1) -> dict:
        # same init distribution as robe, then max-abs per-group calibration
        # for the initial scales (they train from there)
        w = init_memory(key, spec.robe)
        size = spec.robe.size
        ng = n_groups(size)
        padded = jnp.zeros((ng * GROUP_SIZE,), jnp.float32).at[:size].set(w)
        gmax = jnp.abs(padded.reshape(ng, GROUP_SIZE)).max(axis=1)
        scale = jnp.maximum(gmax / 127.0, SCALE_FLOOR)
        codes, scale = quantize_array(w, scale)
        return {"codes": codes, "scale": scale,
                "delta": jnp.zeros((size,), jnp.float32)}

    # -- lookups -----------------------------------------------------------

    def lookup(self, params, spec, idx, fields=None):
        from repro.kernels.ops import qrobe_lookup
        fields = fields if fields is not None else tuple(range(spec.n_fields))
        out = qrobe_lookup(params["codes"], params["scale"], idx,
                           tuple(fields), spec.dim, spec.robe, GROUP_LOG2,
                           spec.use_kernel)
        # straight-through carrier: delta is zero by construction, so the
        # forward value is untouched — but this plain-jnp gather is what
        # hands autodiff a float path to the (dequantized) array, and the
        # post-step projection folds the optimizer's delta update back into
        # the int8 codes
        tids = jnp.asarray(fields, jnp.uint32)[None, :]
        slots = robe_slots(spec.robe, tids, idx, spec.dim).astype(jnp.int32)
        d = jnp.take(params["delta"], slots, axis=0)
        if spec.robe.use_sign:
            d = d * robe_signs(spec.robe, tids, idx, spec.dim)
        return out + d.astype(out.dtype)

    # -- the requantization step (ALPT fold) -------------------------------

    def project(self, params, spec) -> dict:
        """Post-optimizer projection: dequantize with the OLD codes, apply
        the optimizer's delta update, requantize under the (gradient-
        updated) scales, re-zero the carrier.  Saturates at ±127; the scale
        floor keeps collapsed groups recoverable."""
        size = spec.robe.size
        w = (params["codes"].astype(jnp.float32)
             * _expand(params["scale"], size)
             + params["delta"].astype(jnp.float32))
        codes, scale = quantize_array(w, params["scale"])
        return {"codes": codes, "scale": scale.astype(params["scale"].dtype),
                "delta": jnp.zeros_like(params["delta"])}

    # -- metadata ----------------------------------------------------------

    def param_specs(self, spec, rules, mesh=None) -> dict:
        # codes + scales are tiny (bytes of the f32 robe array / 4):
        # replicated everywhere, like the default robe placement
        return {"codes": P(), "scale": P(), "delta": P()}

    def param_count(self, spec) -> int:
        # the serving model: int8 codes + per-group scales.  delta is a
        # training-time carrier that is identically zero between steps and
        # never ships.
        return spec.robe.size + n_groups(spec.robe.size)


register_backend(QRobeBackend())
