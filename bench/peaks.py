"""The peak table, ``peaks.json``, keyed by JAX's ``device_kind``.  A device
that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os


def peak(kind: str, what: str) -> float:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return float(table[kind][what])


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind
