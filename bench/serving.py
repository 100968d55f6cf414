"""What the serving drivers share: the server under test, built as the
configuration states, and the reference's scores for the rows it served."""

from __future__ import annotations

import numpy as np

#: rows the reference scores at a time (one compiled shape for any count)
REF_BLOCK = 16384


def check_program(rc, cfg: dict) -> None:
    """Raise where the program's model departs from the configuration."""
    spec = rc.embedding_spec()
    want = {"embedding": cfg["embedding"], "robe_size": cfg["robe_size"],
            "robe_block": cfg["robe_block"],
            "hash seed": cfg["robe_hash"]["seed"],
            "use_sign": cfg["robe_use_sign"], "use_kernel": False,
            "embed_dim": cfg["embed_dim"],
            "bot_mlp": tuple(cfg["bot_mlp"]), "top_mlp": tuple(cfg["top_mlp"]),
            "compute_dtype": "float32"}
    got = {"embedding": rc.embedding, "robe_size": spec.robe.size,
           "robe_block": spec.robe.block_size, "hash seed": spec.robe.seed,
           "use_sign": spec.robe.use_sign, "use_kernel": spec.use_kernel,
           "embed_dim": rc.embed_dim, "bot_mlp": tuple(rc.bot_mlp),
           "top_mlp": tuple(rc.top_mlp),
           "compute_dtype": np.dtype(rc.compute_dtype).name}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise ValueError(f"the program departs from the configuration "
                         f"(got, stated): {bad}")


def make_server(run):
    """An ``EmbeddingServer`` holding the configuration's one substrate,
    with weights made from the seed; the program's defaults otherwise."""
    from repro.serve.server import EmbeddingServer, ServerConfig
    cfg = run.config
    params = run.reference.init_params(cfg, run.seed)
    sc = ServerConfig(vocab_sizes=tuple(cfg["vocab_sizes"]),
                      embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
                      bot_mlp=tuple(cfg["bot_mlp"]),
                      top_mlp=tuple(cfg["top_mlp"]),
                      backends=(cfg["embedding"],),
                      robe_block=cfg["robe_block"])
    server = EmbeddingServer(sc, params={cfg["embedding"]: params})
    check_program(server.recsys_config(cfg["embedding"]), cfg)
    return server


def reference_scores(run, dense: np.ndarray, sparse: np.ndarray,
                     numerics=None) -> np.ndarray:
    """The reference's scores of ``len(dense)`` rows, REF_BLOCK at a time,
    with weights remade from the seed."""
    import jax.numpy as jnp
    ref, cfg = run.reference, run.config
    num = numerics or run.numerics()
    params = ref.cast(ref.init_params(cfg, run.seed), num)
    score = ref.make_score(cfg, num)
    n = len(dense)
    out = np.empty(n, np.float64)
    for lo in range(0, n, REF_BLOCK):
        hi = min(n, lo + REF_BLOCK)
        pad = REF_BLOCK - (hi - lo)
        d = np.concatenate([dense[lo:hi], np.repeat(dense[hi - 1:hi], pad, 0)])
        s = np.concatenate([sparse[lo:hi],
                            np.repeat(sparse[hi - 1:hi], pad, 0)])
        got = score(params, jnp.asarray(d), jnp.asarray(ref.block_bases(cfg, s)))
        out[lo:hi] = np.asarray(got, np.float64)[:hi - lo]
    return out


def score_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |served − reference| over the rows, relative to the largest
    |reference| score."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or got.size == 0:
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(1e-30, np.max(np.abs(want))))
