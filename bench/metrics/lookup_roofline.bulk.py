"""The embedding lookup's share of its roofline, in %: the bytes any lookup
of the batch has to move (``flops.lookup_bytes``) at the chip's HBM peak,
over the device time of the lookup alone.  The harness jits
``repro.nn.embeddings.embedding_lookup`` with the cell's spec by itself
(``bench_lookup``) and runs it on the window's batches, under its own
trace; the device time is the median run of that program.  Bytes bound
it: its hashing is a few integer operations per element."""

import numpy as np

from bench.flops import lookup_bytes
from bench.peaks import device_kind, peak


def warm(run):
    import jax
    import jax.numpy as jnp
    from repro.nn.embeddings import embedding_lookup

    server = run.state["server"]
    backend = run.config["embedding"]
    spec = server.recsys_config(backend).embedding_spec()

    def bench_lookup(params, ids):
        return embedding_lookup(params, spec, ids)

    params = server.params(backend)["embedding"]
    ids = jax.ShapeDtypeStruct(run.state["pool"][0]["sparse"].shape,
                               jnp.int32)
    compiled = jax.jit(bench_lookup).lower(params, ids).compile()
    run.state["lookup_probe"] = (compiled, params)


def probe(run):
    import jax.numpy as jnp
    compiled, params = run.state.pop("lookup_probe")
    for b in run.state["pool"]:
        compiled(params, jnp.asarray(b["sparse"])).block_until_ready()


def read(run):
    tr = run.traces.get("probe")
    runs = tr.module_runs("bench_lookup") if tr is not None else []
    if not runs:
        return None
    t = float(np.median(runs))
    need = lookup_bytes(run.config, run.traffic["batch"])
    return need / peak(device_kind(), "hbm_bytes_per_s") / t * 100
