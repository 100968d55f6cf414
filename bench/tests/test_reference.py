"""The plain reference against the program, at a small size on the CPU,
where both compute in float32 (the reference's ``f32`` numerics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.data import CtrStream
from bench.tests.conftest import small_cell

ref = harness.load_module("reference", "dlrm_robe.py")


def _program_config(cfg):
    from repro.models.recsys import RecsysConfig
    return RecsysConfig(name="t", arch="dlrm",
                        vocab_sizes=tuple(cfg["vocab_sizes"]),
                        embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
                        bot_mlp=tuple(cfg["bot_mlp"]),
                        top_mlp=tuple(cfg["top_mlp"]), embedding="robe",
                        robe_size=cfg["robe_size"],
                        robe_block=cfg["robe_block"])


@pytest.fixture(scope="module")
def setting():
    cfg = small_cell("tb-train")["config"]
    batch = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], 256, 1.05,
                      7).batch_at(0)
    return cfg, batch


@pytest.mark.parametrize("block", [8, 16])
def test_slots_match_the_program_hash(setting, block):
    from repro.core.robe import robe_slots
    cfg, batch = setting
    cfg = dict(cfg, robe_block=block)
    rc = _program_config(cfg)
    spec = rc.embedding_spec().robe
    assert spec.seed == cfg["robe_hash"]["seed"]
    ids = batch["sparse"]
    want = np.asarray(robe_slots(spec, jnp.arange(ids.shape[1],
                                                  dtype=jnp.uint32)[None, :],
                                 jnp.asarray(ids), cfg["embed_dim"]))
    mem = jnp.arange(cfg["robe_size"], dtype=jnp.float32)
    got = np.asarray(ref.embed(cfg, mem, jnp.asarray(ref.block_bases(cfg,
                                                                     ids))))
    np.testing.assert_array_equal(got, want)


def test_large_row_ids_hash_alike():
    """Element indices past 2^32 (rows near the 40M cap at d=128)."""
    from repro.core.robe import RobeSpec, robe_slots
    cfg = {"embed_dim": 128, "robe_block": 32, "robe_size": 26_135_627,
           "robe_hash": {"seed": 11, "salt": 1}}
    ids = np.array([[39_999_999, 0, 33_554_432, 12_345_678]], np.int32)
    spec = RobeSpec(size=26_135_627, block_size=32, seed=11)
    want = np.asarray(robe_slots(spec, jnp.arange(4, dtype=jnp.uint32)[None],
                                 jnp.asarray(ids), 128))
    mem = jnp.arange(cfg["robe_size"], dtype=jnp.int32)
    got = np.asarray(ref.embed(cfg, mem, jnp.asarray(ref.block_bases(cfg,
                                                                     ids))))
    np.testing.assert_array_equal(got, want)


def test_forward_matches_the_program(setting):
    from repro.models.recsys import forward
    cfg, batch = setting
    params = ref.init_params(cfg, 3)
    want = np.asarray(forward(params, _program_config(cfg),
                              {"dense": jnp.asarray(batch["dense"]),
                               "sparse": jnp.asarray(batch["sparse"])}))
    got = np.asarray(ref.make_score(cfg, ref.Numerics.named("f32"))(
        params, jnp.asarray(batch["dense"]),
        jnp.asarray(ref.block_bases(cfg, batch["sparse"]))))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_train_step_matches_the_program(setting):
    from repro.models.recsys import loss_fn
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_loop import (TrainConfig, build_train_step,
                                        init_state)
    cfg, batch = setting
    o = cfg["optimizer"]
    rc = _program_config(cfg)
    opt = make_optimizer(OptimizerConfig(kind=o["kind"], lr=o["lr"],
                                         eps=o["eps"]))
    tc = TrainConfig(max_restarts=0)
    step = build_train_step(lambda p, b: loss_fn(p, rc, b), opt, tc)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    state, metrics = step(init_state(ref.init_params(cfg, 3), opt, tc), jb)

    params = ref.init_params(cfg, 3)
    v = jax.tree.map(jnp.zeros_like, params)
    rstep = ref.make_train_step(cfg, ref.Numerics.named("f32"), o["lr"],
                                o["eps"])
    params, v, loss, grads = rstep(
        params, v, jb["dense"],
        jnp.asarray(ref.block_bases(cfg, batch["sparse"])), jb["label"])
    np.testing.assert_allclose(float(metrics["loss"]), float(loss),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(state["opt"]["v"]), jax.tree.leaves(v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-12)
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-7)


def test_numerics_are_ordered(setting):
    """The stated TPU numerics sit between float32 and the bf16 control."""
    cfg, batch = setting
    params = ref.init_params(cfg, 5)
    d = jnp.asarray(batch["dense"])
    b = jnp.asarray(ref.block_bases(cfg, batch["sparse"]))
    out = {n: np.asarray(ref.make_score(cfg, ref.Numerics.named(n))(
        ref.cast(params, ref.Numerics.named(n)), d, b), np.float64)
        for n in ("f32", "tpu_default", "bf16")}
    scale = np.max(np.abs(out["f32"]))
    assert 0 < np.max(np.abs(out["tpu_default"] - out["f32"])) / scale < 0.05
    assert np.max(np.abs(out["bf16"] - out["tpu_default"])) / scale > 1e-4
