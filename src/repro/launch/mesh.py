"""Device meshes: one helper builds every mesh in the repo.

``make_mesh`` gives every axis ``AxisType.Auto``: the sharding layer
(``repro.dist``) places arrays with PartitionSpecs and lets GSPMD pick the
layout of intermediates.  ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which contractions over a sharded dimension are rejected as
ambiguous — so nothing in the repo calls it directly.

Functions, not module-level constants — importing this module never
touches jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """A mesh of ``shape`` over ``axes``, every axis ``Auto``.

    ``devices=None`` takes the default backend's devices in jax's
    topology-aware order; otherwise ``devices`` are laid out row-major in
    the order given (a degraded mesh keeps its survivors where they were).
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    return jax.sharding.Mesh(np.asarray(devices).reshape(tuple(shape)),
                             tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_context(*, multi_pod: bool = False):
    from repro.dist.api import DistContext, default_rules
    mesh = make_production_mesh(multi_pod=multi_pod)
    return DistContext(mesh=mesh, rules=default_rules(multi_pod),
                       multi_pod=multi_pod)


def degrade_mesh(mesh, axis: str = "model", keep: Optional[int] = None):
    """The surviving sub-mesh after hardware drops out of ``axis``.

    Keeps the first ``keep`` slices (default: half) along ``axis`` and
    rebuilds a mesh of the same axis names from the remaining devices —
    dropping a slow pod is ``degrade_mesh(mesh, "pod", keep=1)``, shrinking
    the model axis is the default.  Axis names never change, so every
    PartitionSpec that was legal on the old mesh re-resolves against this
    one (``repro.dist.api.prune_specs`` handles divisibility fallbacks).
    """
    names = mesh.axis_names
    if axis not in names:
        raise ValueError(f"mesh has no axis {axis!r}: {names}")
    n = mesh.shape[axis]
    keep = n // 2 if keep is None else keep
    if not 1 <= keep < n:
        raise ValueError(f"keep={keep} must be in [1, {n}) for axis "
                         f"{axis!r} of size {n}")
    devs = np.asarray(mesh.devices)
    sl = [slice(None)] * devs.ndim
    sl[names.index(axis)] = slice(0, keep)
    kept = devs[tuple(sl)]
    return make_mesh(kept.shape, names, devices=kept)


def degrade_context(ctx, axis: str = "model", keep: Optional[int] = None):
    """A ``DistContext`` on the degraded mesh, same rules — the default
    ``degrade`` hook for ``repro.train.elastic.ResliceController``."""
    import dataclasses
    return dataclasses.replace(ctx, mesh=degrade_mesh(ctx.mesh, axis, keep))
