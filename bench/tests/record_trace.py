#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reads, on the chip.

    python3 bench/tests/record_trace.py <out_dir>

Three runs of one jitted product (``bench_small``), each inside a
``score`` span and each followed by a 20 ms host sleep inside a
``batch_at`` span; the ``*.xplane.pb`` is copied to ``<out_dir>/small.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out_dir: str) -> int:
    def bench_small(x):
        return jnp.tanh(x @ x) @ x

    f = jax.jit(bench_small)
    x = jnp.ones((1024, 1024), jnp.float32) * 1e-3
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with TraceAnnotation("score"):
            f(x).block_until_ready()
        with TraceAnnotation("batch_at"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(d)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
