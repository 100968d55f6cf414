"""Offline scoring of a multi-hot model: back-to-back batches through
``EmbeddingServer.score``, as ``bulk`` runs them.

Traffic keys: ``batch`` (rows per call), ``pool`` (distinct batches made
from the seed in set-up; the window cycles through them), ``zipf``.  A row
holds ``multi_hot_sizes[f]`` ids of each field ``f`` of the configuration,
side by side in field order, each drawn on its own by ``CtrStream``'s rule
from its field's vocabulary, and ``n_dense`` standard-normal features.

The server is built as the configuration states (``arch``, the cross
layers, the multi-hot sizes); the window, the release and the check
(``score_gap`` of every score the window returned against the reference's
score of its row) are ``bulk``'s own.
"""

from __future__ import annotations

import numpy as np

from bench.data import CtrStream
from bench.harness import load_module
from bench.serving import check_program

bulk = load_module("drivers", "bulk.py")
window, release, check = bulk.window, bulk.release, bulk.check


def check_multihot(rc, cfg: dict) -> None:
    """Raise where the program's model departs from the configuration."""
    check_program(rc, cfg)
    want = {"arch": cfg["arch"], "cross_layers": cfg["cross_layers"],
            "cross_rank": cfg["cross_rank"],
            "bag_sizes": tuple(cfg["multi_hot_sizes"])}
    got = {"arch": rc.arch, "cross_layers": rc.cross_layers,
           "cross_rank": rc.cross_rank, "bag_sizes": tuple(rc.bag_sizes)}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise ValueError(f"the program departs from the configuration "
                         f"(got, stated): {bad}")


def make_server(run):
    """An ``EmbeddingServer`` holding the configuration's one substrate and
    model, with weights made from the seed."""
    from repro.serve.server import EmbeddingServer, ServerConfig
    cfg = run.config
    params = run.reference.init_params(cfg, run.seed)
    sc = ServerConfig(vocab_sizes=tuple(cfg["vocab_sizes"]),
                      embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
                      bot_mlp=tuple(cfg["bot_mlp"]),
                      top_mlp=tuple(cfg["top_mlp"]), arch=cfg["arch"],
                      cross_layers=cfg["cross_layers"],
                      cross_rank=cfg["cross_rank"],
                      bag_sizes=tuple(cfg["multi_hot_sizes"]),
                      backends=(cfg["embedding"],),
                      robe_block=cfg["robe_block"])
    server = EmbeddingServer(sc, params={cfg["embedding"]: params})
    check_multihot(server.recsys_config(cfg["embedding"]), cfg)
    return server


def stream(cfg: dict, batch: int, zipf: float, seed: int) -> CtrStream:
    """Rows of ``multi_hot_sizes[f]`` ids of each field ``f``: one
    ``CtrStream`` column per id, over its field's vocabulary."""
    vocab = np.repeat(cfg["vocab_sizes"], cfg["multi_hot_sizes"])
    return CtrStream(vocab, cfg["n_dense"], batch, zipf, seed)


def setup(run) -> None:
    # the end-to-end readers know a closed loop of scoring calls by the
    # name of the driver whose window this is
    run.traffic = dict(run.traffic, driver="bulk")
    cfg, tr = run.config, run.traffic
    server = make_server(run)
    run.phase("server")
    rows = stream(cfg, tr["batch"], tr["zipf"], run.seed)
    pool = [rows.batch_at(i, labels=False) for i in range(tr["pool"])]
    run.phase("pool")
    server.score(cfg["embedding"], pool[0])     # compiles the one shape
    run.phase("compile")
    run.state = {"server": server, "pool": pool}


def control_gap(run, numerics: str, seed: int) -> float:
    """``score_gap`` of the reference at ``numerics`` (in the program's
    place) against the reference at the configuration's numerics, over the
    rows of ``seed``'s pool: the reading a limit is set against."""
    from bench.serving import reference_scores, score_gap
    cfg, tr = run.config, run.traffic
    rows = stream(cfg, tr["batch"], tr["zipf"], seed)
    low = run.numerics(numerics)
    gaps = []
    for i in range(tr["pool"]):
        b = rows.batch_at(i, labels=False)
        gaps.append(score_gap(
            reference_scores(run, b["dense"], b["sparse"], low),
            reference_scores(run, b["dense"], b["sparse"])))
    return max(gaps)
