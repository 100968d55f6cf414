"""DLRM-DCNv2 through the normal path against the benchmark's plain
reference (``bench/reference/dlrm_dcnv2_robe.py``), on the CPU at a small
size with seeded random weights, both in float32: the served scores
(``EmbeddingServer.score``), and one ``build_train_step`` step with adagrad
(the loss, the gradients through adagrad's accumulator, the new weights).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.recsys import RecsysConfig, loss_fn
from repro.serve.server import EmbeddingServer, ServerConfig
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import TrainConfig, build_train_step, init_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 33 + 17
CFG = {"vocab_sizes": [1000, 500, 2000, 100, 50, 300],
       "multi_hot_sizes": [3, 1, 2, 1, 5, 2], "embed_dim": 16, "n_dense": 13,
       "bot_mlp": [32, 16], "top_mlp": [24, 8, 1], "cross_layers": 3,
       "cross_rank": 8, "robe_size": 632, "robe_block": 8,
       "robe_init_scale": 0.01, "robe_hash": {"seed": 11, "salt": 1}}
B = 64


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, ROOT)
    try:
        from bench.harness import load_module
        return load_module("reference", "dlrm_dcnv2_robe.py")
    finally:
        sys.path.remove(ROOT)


def _program_config() -> RecsysConfig:
    return RecsysConfig(name="dcnv2", arch="dlrm_dcnv2",
                        vocab_sizes=tuple(CFG["vocab_sizes"]),
                        embed_dim=CFG["embed_dim"], n_dense=CFG["n_dense"],
                        bot_mlp=tuple(CFG["bot_mlp"]),
                        top_mlp=tuple(CFG["top_mlp"]),
                        cross_layers=CFG["cross_layers"],
                        cross_rank=CFG["cross_rank"],
                        bag_sizes=tuple(CFG["multi_hot_sizes"]),
                        embedding="robe", robe_size=CFG["robe_size"],
                        robe_block=CFG["robe_block"])


def _batch(seed: int) -> dict:
    rs = np.random.RandomState(seed)
    vocab = np.repeat(CFG["vocab_sizes"], CFG["multi_hot_sizes"])
    return {"dense": rs.randn(B, CFG["n_dense"]).astype(np.float32),
            "sparse": (rs.random_sample((B, len(vocab))) ** 2
                       * vocab).astype(np.int32),
            "label": rs.randint(0, 2, B).astype(np.int32)}


def test_served_scores_match_the_reference(ref):
    num = ref.Numerics.named("f32")
    params = ref.init_params(CFG, SEED)
    rc = _program_config()
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=rc.vocab_sizes, embed_dim=rc.embed_dim,
        n_dense=rc.n_dense, bot_mlp=rc.bot_mlp, top_mlp=rc.top_mlp,
        arch=rc.arch, cross_layers=rc.cross_layers, cross_rank=rc.cross_rank,
        bag_sizes=rc.bag_sizes, backends=("robe",),
        robe_block=rc.robe_block, robe_compression=100),
        params={"robe": params})
    b = _batch(1)
    want = np.asarray(ref.make_score(CFG, num)(
        params, jnp.asarray(b["dense"]),
        jnp.asarray(ref.block_bases(CFG, b["sparse"]))))
    got = server.score("robe", {"dense": b["dense"], "sparse": b["sparse"]})
    assert got.shape == (B,)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


def test_train_step_matches_the_reference(ref):
    num = ref.Numerics.named("f32")
    lr, eps = 0.01, 1e-8
    rc = _program_config()
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=lr, eps=eps))
    tc = TrainConfig(max_restarts=0)
    step = build_train_step(lambda p, b: loss_fn(p, rc, b), opt, tc)
    state = init_state(ref.init_params(CFG, SEED), opt, tc)
    b = _batch(2)
    state, metrics = step(state, {k: jnp.asarray(v) for k, v in b.items()})

    params = ref.init_params(CFG, SEED)
    v = jax.tree.map(jnp.zeros_like, params)
    new, _, loss, grads = ref.make_train_step(CFG, num, lr, eps)(
        params, v, jnp.asarray(b["dense"]),
        jnp.asarray(ref.block_bases(CFG, b["sparse"])),
        jnp.asarray(b["label"]))
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(grads)[0]]
    assert any("cross" in n for n in names)
    # adagrad's accumulator after one step is g²: the gradients, leaf by
    # leaf, to a share of the leaf's largest
    for name, g2, g in zip(names, jax.tree.leaves(state["opt"]["v"]),
                           jax.tree.leaves(grads)):
        g_prog = np.sqrt(np.asarray(g2))
        g_ref = np.abs(np.asarray(g))
        np.testing.assert_allclose(g_prog, g_ref, rtol=1e-4,
                                   atol=1e-5 * g_ref.max() + 1e-12,
                                   err_msg=name)
    for got, want in zip(jax.tree.leaves(state["params"]),
                         jax.tree.leaves(new)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
