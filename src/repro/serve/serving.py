"""Serving utilities: the sync micro-batching front-end.

The serving subsystem proper lives in the sibling modules — ``router``
(deadline-aware async batching), ``hot_cache`` (frequency-sketch hot-row
cache), ``server`` (multi-substrate ``EmbeddingServer``), ``replay``
(virtual-clock traffic replay).  This module
keeps the synchronous conveniences:

* ``MicroBatcher`` — a thin sync wrapper over the router's
  ``DeadlineBatcher`` policy: same admission checks, same close-out
  logic (``poll()`` dispatches only batches that are due; ``flush()``
  force-closes everything), one shared padding path
  (``router.stack_and_pad``), so sync and async serving can never drift.
* ``percentile`` — nearest-rank percentile of a sorted sample.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.serve.router import (DeadlineBatcher, RouterConfig,
                                accepts_n_valid, stack_and_pad)

__all__ = ["MicroBatcher", "percentile"]


def percentile(sorted_values, p: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sequence.

    Rank is ``ceil(p·n)`` (1-indexed), i.e. index ``ceil(p·n) − 1`` — the
    smallest value with at least a ``p`` fraction of the sample at or
    below it.  (The old ``int(n·p)`` *index* overshoots the rank by one
    wherever ``n·p`` is an integer: p50 of 4 samples read the 3rd.)
    """
    n = len(sorted_values)
    if n == 0:
        raise ValueError("percentile of an empty sample")
    if not 0.0 < p <= 1.0:
        raise ValueError(f"p must be in (0, 1], got {p}")
    return float(sorted_values[max(0, math.ceil(p * n) - 1)])


class MicroBatcher:
    """Collects requests into fixed-size batches (padding the tail) so the
    jitted scoring function compiles once; ``max_wait_ms`` bounds p99.

    Sync front-end over the router's ``DeadlineBatcher``: ``submit``
    admits (raising the policy's ``LoadShedError`` when the queue bound
    trips), ``poll()`` dispatches only the batches the close-out logic
    says are due, ``flush()`` force-closes everything.  The padded tail
    repeats the last real row to keep the compiled shape, and the real
    row count is threaded through: ``flush``/``poll`` slice the scores
    back to real requests before returning them, and a ``score_fn`` that
    accepts the ``n_valid`` keyword is told how many leading rows are
    real — so no consumer, stateless or stateful, can mistake padded
    scores for real ones.
    """

    def __init__(self, batch_size: int, score_fn: Callable[..., np.ndarray],
                 max_wait_ms: float = 2.0, max_queue: int = 4096,
                 clock: Callable[[], float] = time.monotonic):
        self.batch_size = batch_size
        self.score_fn = score_fn
        self._pass_valid = accepts_n_valid(score_fn)
        self._clock = clock
        self._batcher = DeadlineBatcher(RouterConfig(
            max_batch=batch_size, max_queue=max_queue,
            max_wait_s=max_wait_ms / 1e3))

    def __len__(self) -> int:
        return len(self._batcher)

    def submit(self, request: Dict[str, np.ndarray]) -> None:
        # reject at the door (a clear error naming the keys), not as a
        # KeyError deep in np.stack — and without poisoning the queue:
        # already-accepted requests stay servable
        if len(self._batcher):
            have = set(self._batcher._pending[0].features)
            if set(request) != have:
                raise ValueError(
                    f"MicroBatcher: request keys {sorted(request)} != the "
                    f"queued batch's keys {sorted(have)}; all requests in "
                    f"a batch must share the same feature keys")
        self._batcher.admit(request, self._clock())

    def _score(self, reqs) -> List[np.ndarray]:
        batch, n = stack_and_pad([r.features for r in reqs],
                                 self.batch_size)
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        if self._pass_valid:
            scores = np.asarray(self.score_fn(jb, n_valid=n))
        else:
            scores = np.asarray(self.score_fn(jb))
        return list(scores[:n])          # padded tail never escapes

    def poll(self, now: Optional[float] = None) -> List[np.ndarray]:
        """Score only the batches that are due (full, or past the
        close-out the deadline logic computed); [] when none is."""
        now = self._clock() if now is None else now
        out: List[np.ndarray] = []
        while True:
            reqs = self._batcher.poll(now)
            if reqs is None:
                return out
            out.extend(self._score(reqs))

    def flush(self) -> List[np.ndarray]:
        """Force-close everything queued; per-request scores in order."""
        out: List[np.ndarray] = []
        for reqs in self._batcher.drain():
            out.extend(self._score(reqs))
        return out
