"""The serving tier: async routing, hot-row caching, multi-substrate
scoring, and traffic replay.

* ``serving``   — sync ``MicroBatcher`` + ``percentile``
* ``router``    — ``DeadlineBatcher``/``FixedBatcher`` policies and the
  ``AsyncRouter`` front-end (admission, deadline close-out, load shedding)
* ``hot_cache`` — ``CountMinSketch`` + ``HotRowCache`` (fronts the
  fetch-bound substrates via the ``cacheable_rows`` backend hook)
* ``server``    — ``EmbeddingServer``: all four substrates resident, one
  jitted ``serve_scores`` each
* ``fleet``     — ``ReplicaFleet``: N replicas behind one admission path
  (shed → retry-on-replica) with staggered model rollouts
* ``replay``    — virtual-clock open-loop traffic replay (single server or
  fleet), the harness the serving and online tests drive

The light names are re-exported here; ``server``/``fleet``/``replay`` stay
submodule imports (they pull in the full model stack).
"""

from repro.serve.router import (AsyncRouter, DeadlineBatcher,   # noqa: F401
                                FixedBatcher, LoadShedError, RouterConfig,
                                stack_and_pad)
from repro.serve.hot_cache import CountMinSketch, HotRowCache   # noqa: F401
from repro.serve.serving import MicroBatcher, percentile  # noqa: F401
