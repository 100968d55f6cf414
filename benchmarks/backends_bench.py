"""Per-backend embedding microbenchmark -> ``BENCH_backends.json``.

One row per registered ``EmbeddingBackend`` at smoke scale: trained
parameter count, the backend's own cost model (bytes fetched / flops per
batch), and measured CPU lookup throughput.  Substrates with a fused
Pallas lookup (robe / hashed / tt / qrobe) get a second row with the kernel path
forced on, so the fused-vs-jnp trajectory is recorded per commit — every
row carries a ``kernel`` flag and a ``mode`` field ("jnp", "interpret",
or "pallas" on a real TPU).  Off-TPU the kernel rows measure interpret
mode (a correctness proxy, not kernel speed), so they run at a reduced
batch to keep CI wall-clock sane.  Every row is stamped with its
measurement provenance (platform / interpret flag / jax version) by
``benchmarks.common.stamp_row``, and two end-to-end serve rows record the
full-table baseline vs robe through its Pallas kernels
(``table4_inference_throughput.serve_rows``).  The JSON lands at the repo
root.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.robe import RobeSpec
from repro.nn.embeddings import (EmbeddingSpec, backend_names,
                                 embedding_init, embedding_lookup,
                                 get_backend)

BENCH_VOCABS = (50_000, 20_000, 80_000, 5_000, 30_000, 1_000, 15_000, 400)
DIM = 16
#: substrates whose lookup has a fused Pallas kernel behind use_kernel
KERNEL_KINDS = ("robe", "hashed", "tt", "qrobe")
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_backends.json")


def _spec(kind: str, use_kernel: bool = False) -> EmbeddingSpec:
    n_logical = sum(BENCH_VOCABS) * DIM
    return EmbeddingSpec(
        vocab_sizes=BENCH_VOCABS, dim=DIM, kind=kind, use_kernel=use_kernel,
        robe=RobeSpec(size=max(512, n_logical // 1000), block_size=32,
                      seed=11))


def _row(kind: str, batch: int, iters: int, idx_np: np.ndarray,
         use_kernel: bool) -> dict:
    spec = _spec(kind, use_kernel=use_kernel)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = jnp.asarray(idx_np[:batch])
    fn = jax.jit(lambda p, i, s=spec: embedding_lookup(p, s, i))
    fn(params, idx).block_until_ready()            # compile
    t0 = time.monotonic()
    for _ in range(iters):
        fn(params, idx).block_until_ready()
    dt = (time.monotonic() - t0) / iters
    from benchmarks.common import stamp_row
    cost = get_backend(kind).cost(spec, batch)
    mode = "jnp" if not use_kernel else (
        "pallas" if jax.default_backend() == "tpu" else "interpret")
    return stamp_row({
        "name": f"backends/{kind}" + ("+kernel" if use_kernel else ""),
        "kernel": use_kernel,
        "mode": mode,
        "batch": batch,
        "params": int(spec.param_count),
        "compression": round(float(spec.compression), 1),
        "lookups_per_s": int(batch * spec.n_fields / dt),
        "us_per_batch": round(dt * 1e6),
        "cost_bytes_fetched": int(cost["bytes_fetched"]),
        "cost_flops": int(cost["flops"]),
    })


def run(batch: int = 8192, iters: int = 16):
    rows = []
    rs = np.random.RandomState(0)
    idx_np = rs.randint(0, min(BENCH_VOCABS),
                        (batch, len(BENCH_VOCABS))).astype(np.int32)
    for kind in backend_names():
        rows.append(_row(kind, batch, iters, idx_np, use_kernel=False))
    # fused rows: full batch on a real TPU; interpret mode off-TPU is a
    # conformance datapoint, so a slice of the batch + 2 iters suffices
    on_tpu = jax.default_backend() == "tpu"
    k_batch = batch if on_tpu else max(256, batch // 16)
    k_iters = iters if on_tpu else 2
    for kind in KERNEL_KINDS:
        rows.append(_row(kind, k_batch, k_iters, idx_np, use_kernel=True))
    # end-to-end serve rows: the paper's 3.1×-vs-full inference comparison
    # as recorded data — full-table serve baseline vs the robe
    # serve op (lazy import: table4 pulls in the model stack)
    from benchmarks.table4_inference_throughput import serve_rows
    rows.extend(serve_rows(batch=k_batch, iters=k_iters))
    return rows


def write_json(rows, path: str = OUT_PATH) -> str:
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
        f.write("\n")
    return os.path.abspath(path)


if __name__ == "__main__":
    rows = run()
    for r in rows:
        print(r)
    print("wrote", write_json(rows))
