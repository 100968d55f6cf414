#!/usr/bin/env python3
"""Record the scoped trace that ``test_scopes.py`` reads, on the chip.

    python3 bench/tests/record_scoped_trace.py <out_dir>

Three runs of one jitted gradient step (``bench_scoped``):

* under ``jax.named_scope("embedding")`` a gather of 16,384 rows from a
  [65,536, 128] table, whose backward is a scatter-add under
  ``transpose(jvp(embedding))``;
* under ``interaction`` the first 351 columns of every row of the table
  scattered into a [65,536, 27, 27] triangle, as the DLRM interaction's
  backward does at a batch of 65,536 (the TPU compiler makes a ``while``
  loop of it, which carries no ``tf_op`` while the ops it holds do);
* under ``top_mlp`` a product with a [128, 128] weight;
* under ``bottom_mlp`` the mean of a 64 MB dense batch, copied from the
  host in every step;
* an unscoped update of the table and the weight.

Each run sits in a ``step`` span and holds, in turn, a ``train.h2d`` span
(20 ms asleep, then the ids and the dense batch handed to the device:
``jnp.asarray`` returns before the 64 MB copy ends), a ``train.run`` span
(the dispatch) and a ``train.sync`` span (the wait for the loss, in
which the device first waits for the copy).  The ``*.xplane.pb`` is
copied to ``<out_dir>/scoped.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

ROWS, DIM, N, FIELDS = 65536, 128, 16384, 27
DENSE = 16 * 2 ** 20                            # float32: 64 MB


def bench_scoped(table, w, ids, dense):
    rows, cols = np.tril_indices(FIELDS, k=-1)

    def loss(table, w):
        with jax.named_scope("embedding"):
            e = table[ids]
        with jax.named_scope("interaction"):
            v = jnp.concatenate([table] * 3, axis=1)[:, :len(rows)]
            tri = jnp.zeros((ROWS, FIELDS, FIELDS)).at[:, rows, cols].add(v)
        with jax.named_scope("bottom_mlp"):
            bias = jnp.mean(dense)
        with jax.named_scope("top_mlp"):
            return (jnp.sum(jnp.tanh(e @ w) ** 2) + 1e-3 * jnp.sum(tri ** 2)
                    + bias)

    value, (g_table, g_w) = jax.value_and_grad(loss, argnums=(0, 1))(table, w)
    return value, table - 1e-3 * g_table, w - 1e-3 * g_w


def main(out_dir: str) -> int:
    f = jax.jit(bench_scoped)
    table = jnp.ones((ROWS, DIM), jnp.float32) * 1e-2
    w = jnp.ones((DIM, DIM), jnp.float32) * 1e-2
    rng = np.random.default_rng(0)
    ids = rng.integers(0, ROWS, N).astype(np.int32)
    dense = rng.normal(size=DENSE).astype(np.float32)
    f(table, w, jnp.asarray(ids), jnp.asarray(dense))[0].block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with TraceAnnotation("step"):
            with TraceAnnotation("train.h2d"):
                time.sleep(0.02)
                ids_d, dense_d = jnp.asarray(ids), jnp.asarray(dense)
            with TraceAnnotation("train.run"):
                value, table, w = f(table, w, ids_d, dense_d)
            with TraceAnnotation("train.sync"):
                float(value)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "scoped.xplane.pb")
    shutil.copy(path, out)
    shutil.rmtree(d)
    print(os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
