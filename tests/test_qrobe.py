"""qrobe int8 substrate: quantization edge cases and training drift.

The shared parity / conformance suites prove qrobe agrees with its jnp
reference; this file covers what only an int8 substrate can get wrong —
collapsed (underflow) scales, saturating clips, mixed bf16×int8 dtype —
plus the end-to-end claim: QAT training tracks the float robe substrate
on the same synthetic CTR stream.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.robe import RobeSpec
from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
from repro.models.recsys import (RecsysConfig, init_params, loss_fn,
                                 make_project_fn)
from repro.nn.embedding_backends import get_backend
from repro.nn.embedding_backends.qrobe import (GROUP_SIZE, SCALE_FLOOR,
                                               n_groups, quantize_array)
from repro.nn.embeddings import EmbeddingSpec
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import TrainConfig, build_train_step, init_state

VOCABS = (400, 240, 640)


def _spec(**kw) -> EmbeddingSpec:
    kw.setdefault("robe", RobeSpec(size=2048, block_size=8, seed=3))
    return EmbeddingSpec(vocab_sizes=VOCABS, dim=8, kind="qrobe", **kw)


# ---------------------------------------------------------------------------
# quantize_array: the single entry point init and project share
# ---------------------------------------------------------------------------

def test_saturating_clip_at_127():
    """Values beyond ±127·scale must clip, not wrap — int8 overflow would
    flip signs."""
    scale = jnp.full((1,), 0.01, jnp.float32)
    w = jnp.asarray([10.0, -10.0, 1.27, -1.27, 0.0], jnp.float32)
    codes, _ = quantize_array(w, scale)
    np.testing.assert_array_equal(np.asarray(codes),
                                  [127, -127, 127, -127, 0])


def test_scale_underflow_floor_keeps_group_finite():
    """A collapsed (≈0) scale would send every ratio to ±inf; the floor
    guard pins it at SCALE_FLOOR, the codes stay finite (saturated), and
    the returned scale is the guarded one — so a later dequantize
    reconstructs finite values."""
    scale = jnp.asarray([0.0, 1e-30, -1e-30], jnp.float32)
    w = jnp.ones((3,), jnp.float32)
    codes, safe = quantize_array(w, jnp.repeat(scale, 1))
    # one slot per group here (size 3 < GROUP_SIZE ⇒ one group): exercise
    # per-group with an explicit expanded call instead
    assert np.all(np.isfinite(np.asarray(codes, np.float32)))
    assert np.all(np.abs(np.asarray(safe)) >= SCALE_FLOOR)
    # sign is preserved through the floor — a learned negative scale must
    # not silently flip the whole group
    assert float(safe[2]) < 0


def test_project_recovers_from_collapsed_scale():
    """Zero out one group's scale: project must saturate that group (not
    NaN it) and leave every other group untouched."""
    bk = get_backend("qrobe")
    spec = _spec()
    params = bk.init(jax.random.PRNGKey(0), spec)
    ng = n_groups(spec.robe.size)
    assert ng >= 2
    crushed = dict(params, scale=params["scale"].at[0].set(0.0))
    out = bk.project(crushed, spec)
    assert np.all(np.isfinite(np.asarray(out["scale"])))
    assert np.abs(np.asarray(out["scale"])).min() >= SCALE_FLOOR
    # untouched groups requantize to exactly the same codes
    np.testing.assert_array_equal(
        np.asarray(out["codes"][GROUP_SIZE:]),
        np.asarray(params["codes"][GROUP_SIZE:]))
    # the crushed group saturates instead of exploding
    g0 = np.asarray(out["codes"][:GROUP_SIZE])
    assert np.abs(g0).max() <= 127


def test_underflow_scale_trains_without_nan():
    """One training step from a collapsed-scale state stays finite: the
    grads, the update, and the post-step projection all survive."""
    cfg = RecsysConfig(name="t", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                       top_mlp=(8, 1), embed_dim=8, vocab_sizes=VOCABS,
                       embedding="qrobe", robe_size=2048, robe_block=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    emb = params["embedding"]
    params["embedding"] = dict(emb, scale=emb["scale"].at[0].set(0.0))
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.05))
    tc = TrainConfig(checkpoint_every=10 ** 9)
    step = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc,
                            project=make_project_fn(cfg))
    state = init_state(params, opt, tc)
    stream = CtrStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=4,
                                     batch_size=64))
    for s in range(3):
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(s).items()}
        state, m = step(state, batch)
        assert bool(m["finite"] == 1.0)
        assert np.isfinite(float(m["loss"]))
    p = state["params"]["embedding"]
    assert np.all(np.isfinite(np.asarray(p["scale"])))
    assert bool(jnp.all(p["delta"] == 0))


# ---------------------------------------------------------------------------
# mixed dtype: bf16 activations over int8 params
# ---------------------------------------------------------------------------

def test_bf16_scale_bf16_out_int8_codes():
    """The op's output dtype follows the scale: bf16 scales give bf16
    activations straight off the int8 gather (no f32 materialization in
    the signature), within bf16 tolerance of the f32 dequant."""
    bk = get_backend("qrobe")
    spec = _spec()
    params = bk.init(jax.random.PRNGKey(0), spec)
    rs = np.random.RandomState(1)
    idx = jnp.asarray(rs.randint(0, min(VOCABS), (16, 3)), jnp.int32)
    want = bk.lookup(params, spec, idx)                      # f32
    p16 = dict(params, scale=params["scale"].astype(jnp.bfloat16),
               delta=params["delta"].astype(jnp.bfloat16))
    got = bk.lookup(p16, spec, idx)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)
    # and through the kernel path
    got_k = bk.lookup(p16, dataclasses.replace(spec, use_kernel=True), idx)
    assert got_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got_k, np.float32),
                               np.asarray(got, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_bf16_compute_model_forward_finite():
    """End-to-end: a bf16-compute DLRM over int8 embedding params runs and
    stays finite (the mixed-dtype path the serving tier would take)."""
    cfg = RecsysConfig(name="t", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                       top_mlp=(8, 1), embed_dim=8, vocab_sizes=VOCABS,
                       embedding="qrobe", robe_size=2048, robe_block=8,
                       compute_dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(2)
    batch = {"sparse": jnp.asarray(rs.randint(0, min(VOCABS),
                                              (8, cfg.n_fields)), jnp.int32),
             "dense": jnp.asarray(rs.randn(8, cfg.n_dense), jnp.float32)}
    from repro.models.recsys import forward
    out = forward(params, cfg, batch)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


# ---------------------------------------------------------------------------
# serve-bytes claim + training drift vs the float substrate
# ---------------------------------------------------------------------------

def test_served_bytes_about_4x_under_robe():
    """At the same slot count, qrobe serves its int8 ``codes`` and one f32
    ``scale`` per group: 3.5× to 4× fewer bytes than robe's f32 array."""
    spec_q, spec_r = _spec(), dataclasses.replace(_spec(), kind="robe")
    key = jax.random.PRNGKey(0)
    pq = get_backend("qrobe").init(key, spec_q)
    pr = get_backend("robe").init(key, spec_r)
    served_q = pq["codes"].nbytes + pq["scale"].nbytes
    ratio = pr["memory"].nbytes / served_q
    assert 3.5 <= ratio <= 4.0, ratio
    # compressed footprint: int8 codes + one f32 scale per group
    assert get_backend("qrobe").param_count(spec_q) == \
        spec_q.robe.size + n_groups(spec_q.robe.size)


def test_qrobe_training_tracks_robe():
    """The QAT drift gate: same arch, stream, optimizer, steps — the int8
    substrate's final loss must track the float robe substrate within
    tolerance (quantization noise, not divergence)."""
    losses = {}
    for kind in ("robe", "qrobe"):
        cfg = RecsysConfig(name="t", arch="dlrm", n_dense=4,
                           bot_mlp=(16, 8), top_mlp=(8, 1), embed_dim=8,
                           vocab_sizes=VOCABS, embedding=kind,
                           robe_size=2048, robe_block=8)
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.05))
        tc = TrainConfig(checkpoint_every=10 ** 9)
        step = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc,
                                project=make_project_fn(cfg))
        state = init_state(params, opt, tc)
        stream = CtrStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=4,
                                         batch_size=128))
        tail = []
        for s in range(30):
            batch = {k: jnp.asarray(v)
                     for k, v in stream.batch_at(s).items()}
            state, m = step(state, batch)
            if s >= 25:
                tail.append(float(m["loss"]))
        losses[kind] = float(np.mean(tail))
    assert np.isfinite(losses["qrobe"])
    # both must actually learn (start ≈ 0.87 on this stream)...
    assert losses["qrobe"] < 0.8 and losses["robe"] < 0.8
    # ...and the int8 run may trail the float run only by quantization
    # noise, not by a divergence
    assert losses["qrobe"] <= losses["robe"] + 0.05, losses


@pytest.mark.parametrize("z,dim", [(8, 8), (16, 24)],
                         ids=("aligned", "general"))
def test_both_kernel_layouts_match_jnp(z, dim):
    """z % dim == 0 routes the aligned single-gather kernel, otherwise the
    general limb-wise kernel — both must match the jnp path on the same
    params (the circular-wrap + scale-group indexing subtlety)."""
    bk = get_backend("qrobe")
    spec = EmbeddingSpec(vocab_sizes=VOCABS, dim=dim, kind="qrobe",
                         robe=RobeSpec(size=2048, block_size=z, seed=3))
    spec_k = dataclasses.replace(spec, use_kernel=True)
    params = bk.init(jax.random.PRNGKey(0), spec)
    idx = jnp.asarray(np.random.RandomState(3).randint(
        0, min(VOCABS), (16, 3)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(bk.lookup(params, spec_k, idx)),
        np.asarray(bk.lookup(params, spec, idx)), rtol=1e-6, atol=1e-7)
