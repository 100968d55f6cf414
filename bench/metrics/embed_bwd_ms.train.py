"""Device time of the embedding lookup's backward alone at the train
batch, in ms: the VJP of ``repro.nn.embeddings.embedding_lookup`` with the
cell's spec (``bench_embed_bwd``: hash the slots, scatter-add the
cotangent into the array), jitted by the harness and run on the pool's
batches under its own trace; the median run."""

import numpy as np


def warm(run):
    import jax
    import jax.numpy as jnp
    from repro.nn.embeddings import embedding_lookup

    spec = run.state["spec"]

    def bench_embed_bwd(memory, ids):
        _, vjp = jax.vjp(
            lambda m: embedding_lookup({"memory": m}, spec, ids), memory)
        ct = jnp.broadcast_to(
            (ids % 7).astype(jnp.float32)[..., None] * 1e-3,
            ids.shape + (spec.dim,))
        return vjp(ct)[0]

    mem = jax.ShapeDtypeStruct((run.config["robe_size"],), jnp.float32)
    ids = jax.ShapeDtypeStruct(run.state["pool"][0]["sparse"].shape,
                               jnp.int32)
    run.state["bwd_probe"] = jax.jit(bench_embed_bwd).lower(mem, ids).compile()


def probe(run):
    import jax.numpy as jnp
    compiled = run.state.pop("bwd_probe")
    memory = run.state["state"]["params"]["embedding"]["memory"]
    for b in run.state["pool"][:4]:
        compiled(memory, jnp.asarray(b["sparse"])).block_until_ready()


def read(run):
    tr = run.traces.get("probe")
    runs = tr.module_runs("bench_embed_bwd") if tr is not None else []
    if not runs:
        return None
    return float(np.median(runs)) * 1e3
