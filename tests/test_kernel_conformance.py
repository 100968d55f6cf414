"""Kernel-conformance harness: the shared gate every fused Pallas op must
pass before it may ship (ROADMAP §Kernel conformance).

One parametrized suite over the five fused ops — ``robe_lookup``,
``qrobe_lookup``, ``dot_interaction``, ``qr_lookup``, ``tt_lookup`` —
asserting

  (a) Pallas-interpret forward == the jnp reference to 1e-5 (f32) /
      1e-2 (bf16),
  (b) the ops' ``custom_vjp`` grads == ``jax.grad`` of the reference path,
  (c) awkward shapes — prime batch sizes (pad-and-slice), ``bag > 1``
      (folded through the backends), and dim not a multiple of 128 — all
      agree with the reference,

plus hypothesis property tests for the index math the QR / TT kernels
compute in-kernel (round-trip + in-bounds coverage) and a check of the
fused lookups against the *materialized* whole-table oracles in
``kernels/ref.py``.

Each case is a (fused, reference, params) triple over the same inputs:
``fused(params, use_kernel)`` runs the op with the kernel forced on/off,
``reference(params)`` is the independent jnp path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.robe import RobeSpec
from repro.kernels import ref
from repro.kernels.ops import (dot_interaction, qr_lookup, qrobe_lookup,
                               robe_lookup, tt_lookup)
from repro.nn.embedding_backends.hashed import qr_layout
from repro.nn.embedding_backends.qrobe import GROUP_LOG2
from repro.nn.embedding_backends.tt import factor_dim, factor_rows

VOCABS = (40, 24, 64)
QR_M = 8
TT_RANK = 4


def _tt_meta(vocabs, dim):
    factors = tuple(int(n) for n in factor_rows(int(sum(vocabs))))
    offsets = tuple(int(o) for o in
                    np.concatenate([[0], np.cumsum(vocabs)[:-1]]))
    return factors, offsets, factor_dim(dim)


def _case(name, dtype=jnp.float32, b=16, dim=24, vocabs=VOCABS, seed=0):
    """(fused, reference, params): same inputs, kernel-switchable fused op
    vs the independent jnp reference path."""
    f = len(vocabs)
    rs = np.random.RandomState(seed)
    idx = jnp.asarray(rs.randint(0, min(vocabs), (b, f)), jnp.int32)

    if name == "robe":
        spec = RobeSpec(size=4096, block_size=16, seed=7, use_sign=True)
        params = (jnp.asarray(rs.randn(4096), dtype),)
        tids = tuple(range(f))
        fused = lambda p, uk: robe_lookup(p[0], idx, tids, dim, spec, uk)
        reference = lambda p: ref.robe_lookup_ref(
            p[0], idx, jnp.arange(f, dtype=jnp.uint32), dim, spec)
    elif name == "dot":
        params = (jnp.asarray(rs.randn(b, f, dim), dtype),)
        fused = lambda p, uk: dot_interaction(p[0], False, uk)
        reference = lambda p: ref.dot_interaction_ref(p[0], False)
    elif name == "qr":
        q_rows, q_off, r_off = qr_layout(vocabs, QR_M)
        qo, ro = tuple(map(int, q_off)), tuple(map(int, r_off))
        params = (jnp.asarray(rs.randn(sum(q_rows), dim), dtype),
                  jnp.asarray(rs.randn(QR_M * f, dim), dtype))
        fused = lambda p, uk: qr_lookup(p[0], p[1], idx, qo, ro, QR_M, uk)
        reference = lambda p: ref.qr_lookup_ref(p[0], p[1], idx, qo, ro,
                                                QR_M)
    elif name == "tt":
        factors, offsets, (d1, d2, d3) = _tt_meta(vocabs, dim)
        n1, n2, n3 = factors
        params = (jnp.asarray(rs.randn(n1, d1, TT_RANK), dtype),
                  jnp.asarray(rs.randn(n2, TT_RANK, d2, TT_RANK), dtype),
                  jnp.asarray(rs.randn(n3, TT_RANK, d3), dtype))
        fused = lambda p, uk: tt_lookup(p[0], p[1], p[2], idx, offsets,
                                        factors, dim, uk)
        reference = lambda p: ref.tt_lookup_ref(p[0], p[1], p[2], idx,
                                                offsets, factors, dim)
    elif name == "qrobe":
        # int8 codes + learned per-group scales, dequantized in-kernel.
        # ``dtype`` parametrizes the SCALE (= activation) dtype; the codes
        # are int8 in every case — the mixed-dtype contract.
        spec = RobeSpec(size=4096, block_size=16, seed=7, use_sign=True)
        params = (jnp.asarray(rs.randint(-127, 128, (4096,)), jnp.int8),
                  jnp.asarray(np.abs(rs.randn(4096 >> GROUP_LOG2)) * 0.05
                              + 0.01, dtype))
        tids = tuple(range(f))
        fused = lambda p, uk: qrobe_lookup(p[0], p[1], idx, tids, dim,
                                           spec, GROUP_LOG2, uk)
        reference = lambda p: ref.qrobe_lookup_ref(
            p[0], p[1], idx, jnp.arange(f, dtype=jnp.uint32), dim, spec,
            GROUP_LOG2)
    else:
        raise AssertionError(name)
    return fused, reference, params


CASES = ("robe", "dot", "qr", "tt", "qrobe")
#: every fused op carries a custom_vjp (explicit scatter-add / symmetric
#: gram contraction) — the Pallas forwards have no autodiff rule
VJP_CASES = CASES
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-6),
       jnp.bfloat16: dict(rtol=1e-2, atol=1e-2)}


def _assert_close(got, want, dtype, **kw):
    tol = dict(TOL[dtype])
    tol.update(kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


# ---------------------------------------------------------------------------
# (a) forward: Pallas interpret == jnp reference, f32 and bf16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16),
                         ids=("f32", "bf16"))
def test_forward_interpret_matches_ref(name, dtype):
    fused, reference, params = _case(name, dtype=dtype)
    got = fused(params, True)
    want = reference(params)
    assert got.shape == want.shape and got.dtype == want.dtype
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("name", CASES)
def test_jnp_path_matches_ref_exactly(name):
    """use_kernel=False must BE the reference path (no drift allowed)."""
    fused, reference, params = _case(name)
    np.testing.assert_array_equal(np.asarray(fused(params, False)),
                                  np.asarray(reference(params)))


# ---------------------------------------------------------------------------
# (b) backward: custom_vjp grads == jax.grad of the reference path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", VJP_CASES)
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16),
                         ids=("f32", "bf16"))
@pytest.mark.parametrize("use_kernel", (False, True),
                         ids=("jnp", "kernel"))
def test_custom_vjp_grad_matches_ref_grad(name, dtype, use_kernel):
    fused, reference, params = _case(name, dtype=dtype)
    rs = np.random.RandomState(10)
    ct = jnp.asarray(rs.randn(*reference(params).shape), jnp.float32)

    def loss_fused(p):
        return (fused(p, use_kernel).astype(jnp.float32) * ct).sum()

    def loss_ref(p):
        return (reference(p).astype(jnp.float32) * ct).sum()

    # allow_int: qrobe's int8 codes flow through grad with float0
    # cotangents (a no-op for the all-float cases)
    g_fused = jax.grad(loss_fused, allow_int=True)(params)
    g_ref = jax.grad(loss_ref, allow_int=True)(params)
    for gf, gr in zip(g_fused, g_ref):
        if gf.dtype == jax.dtypes.float0:
            # integer leaf: both paths must agree there is NO gradient
            assert gr.dtype == jax.dtypes.float0
            continue
        # custom_vjp contract: cotangents carry the parameter dtype.
        # bf16 tolerance is looser than forward: the ref path's scatter-add
        # accumulates in bf16 while the custom bwd accumulates in f32, and
        # with ~B·F colliding rows per core slot the bf16 rounding noise is
        # O(eps · n_collisions · |grad|) ≈ 0.2 at these magnitudes.
        if dtype == jnp.bfloat16:
            _assert_close(gf, gr, dtype, rtol=5e-2, atol=0.25)
        else:
            _assert_close(gf, gr, dtype, atol=1e-6)
        assert gf.dtype == gr.dtype == dtype


# ---------------------------------------------------------------------------
# (c) awkward shapes: prime batches pad-and-slice, dim % 128 != 0, bag > 1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CASES)
def test_prime_batch_pads_and_slices(name):
    """b=13 with f·dim sized so the VMEM tile is SMALLER than the batch:
    the pad branch really runs, and the output slices back to b rows."""
    from repro.kernels.tiling import pick_batch_tile
    b, f, dim = 13, 8, 6000                       # tile 10 < 13 → pads to 20
    assert pick_batch_tile(b, f, dim) < b
    vocabs = tuple(range(30, 30 + 8))
    fused, reference, params = _case(name, b=b, dim=dim, vocabs=vocabs)
    got = fused(params, True)
    want = reference(params)
    assert got.shape == want.shape and got.shape[0] == b
    _assert_close(got, want, jnp.float32, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("name", CASES)
def test_dim_not_multiple_of_128(name):
    fused, reference, params = _case(name, b=7, dim=40)
    _assert_close(fused(params, True), reference(params), jnp.float32)


@pytest.mark.parametrize("kind", ("robe", "hashed", "tt"))
def test_bag_lookup_flows_through_kernel(kind):
    """lookup_bag folds the bag into the batch before the fused lookup:
    kernel-on must equal kernel-off for weighted-mean pooling with −1
    padding and an empty bag."""
    from repro.nn.embeddings import (EmbeddingSpec, embedding_init,
                                     embedding_lookup_bag)
    kw = dict(vocab_sizes=VOCABS, dim=8, kind=kind,
              robe=RobeSpec(size=512, block_size=8, seed=3),
              hashed_buckets=16, tt_rank=4)
    spec_jnp = EmbeddingSpec(**kw)
    spec_ker = EmbeddingSpec(use_kernel=True, **kw)
    params = embedding_init(jax.random.PRNGKey(0), spec_jnp)
    rs = np.random.RandomState(6)
    idx = rs.randint(0, min(VOCABS), (5, 3, 4))
    idx[0, 0, 2:] = -1
    idx[2, 1, :] = -1
    w = jnp.asarray((rs.rand(5, 3, 4) * 0.3).astype(np.float32))
    idx = jnp.asarray(idx, jnp.int32)
    want = embedding_lookup_bag(params, spec_jnp, idx, combiner="mean",
                                weights=w)
    got = embedding_lookup_bag(params, spec_ker, idx, combiner="mean",
                               weights=w)
    _assert_close(got, want, jnp.float32)


@pytest.mark.parametrize("kind", ("robe", "qrobe"))
def test_dma_gather_bags_over_several_grid_steps(kind):
    """Multi-hot bags with −1 padding (one bag fully empty) pooled through
    the DMA gather, with enough (sample, bag slot, field) lookups that the
    gather runs several grid steps over the array, the last one padded:
    kernel-on equals kernel-off."""
    from repro.kernels.tiling import PAIRS_PER_STEP
    from repro.nn.embeddings import (EmbeddingSpec, embedding_init,
                                     embedding_lookup_bag)
    b, f, bag = 100, 4, 3
    assert PAIRS_PER_STEP < b * f * bag < 2 * PAIRS_PER_STEP
    kw = dict(vocab_sizes=(37,) * f, dim=24, kind=kind,
              robe=RobeSpec(size=4096, block_size=16, seed=7, use_sign=True))
    spec_jnp = EmbeddingSpec(**kw)
    spec_ker = EmbeddingSpec(use_kernel=True, **kw)
    params = embedding_init(jax.random.PRNGKey(3), spec_jnp)
    rs = np.random.RandomState(3)
    idx = rs.randint(0, 37, (b, f, bag)).astype(np.int32)
    idx[0, 0, 1:] = -1
    idx[3, 2, :] = -1                             # empty bag pools to zero
    idx = jnp.asarray(idx)
    want = embedding_lookup_bag(params, spec_jnp, idx)
    got = embedding_lookup_bag(params, spec_ker, idx)
    assert not np.any(np.asarray(got)[3, 2])
    _assert_close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# fused lookups vs the MATERIALIZED whole-table oracles
# ---------------------------------------------------------------------------

def test_qr_kernel_matches_materialized_table():
    fused, _, params = _case("qr")
    table = ref.qr_materialize_ref(params[0], params[1], VOCABS, QR_M)
    idx = jnp.asarray(np.random.RandomState(0).randint(
        0, min(VOCABS), (16, 3)), jnp.int32)
    off = jnp.asarray(np.concatenate([[0], np.cumsum(VOCABS)[:-1]]),
                      jnp.int32)
    want = jnp.take(table, idx + off[None, :], axis=0)
    _assert_close(fused(params, True), want, jnp.float32)


def test_tt_kernel_matches_materialized_table():
    fused, _, params = _case("tt")
    table = ref.tt_materialize_ref(*params)
    idx = jnp.asarray(np.random.RandomState(0).randint(
        0, min(VOCABS), (16, 3)), jnp.int32)
    off = jnp.asarray(np.concatenate([[0], np.cumsum(VOCABS)[:-1]]),
                      jnp.int32)
    want = jnp.take(table, idx + off[None, :], axis=0)
    _assert_close(fused(params, True), want, jnp.float32)


# ---------------------------------------------------------------------------
# hypothesis property tests for the kernels' index math
# ---------------------------------------------------------------------------

from hypothesis import given, settings, strategies as st


@settings(max_examples=40, deadline=None)
@given(vocab=st.integers(min_value=1, max_value=50_000_000),
       log_m=st.integers(min_value=1, max_value=14),
       frac=st.integers(min_value=0, max_value=10**6))
def test_qr_decomposition_round_trips(vocab, log_m, frac):
    """q·m + r == id, with q/r in-bounds for ragged vocab sizes — the
    contract the fused kernel's in-kernel index math must keep."""
    m = 2 ** log_m
    x = (vocab - 1) * frac // 10**6          # spans [0, vocab)
    q, r = x // m, x % m
    assert q * m + r == x
    assert 0 <= r < m
    assert 0 <= q < -(-vocab // m)           # quotient-table rows


@settings(max_examples=20, deadline=None)
@given(vs=st.lists(st.integers(min_value=1, max_value=100_000),
                   min_size=1, max_size=8),
       log_m=st.integers(min_value=1, max_value=10))
def test_qr_layout_offsets_stay_disjoint(vs, log_m):
    """Per-field table segments never overlap: field f's max quotient /
    remainder index stays below field f+1's offset."""
    vs, m = tuple(vs), 2 ** log_m
    q_rows, q_off, r_off = qr_layout(vs, m)
    for f, v in enumerate(vs):
        top_q = q_off[f] + (v - 1) // m
        end_q = q_off[f + 1] if f + 1 < len(vs) else sum(q_rows)
        assert top_q < end_q
        if f + 1 < len(vs):                   # r segments: m rows per field
            assert r_off[f] + m - 1 < r_off[f + 1]
    assert sum(q_rows) == sum(-(-v // m) for v in vs)


@settings(max_examples=40, deadline=None)
@given(n=st.integers(min_value=1, max_value=300_000_000),
       frac=st.integers(min_value=0, max_value=10**6))
def test_tt_factorization_covers_vocab(n, frac):
    """factor_rows covers every row id with in-range core indices, and the
    mixed-radix decomposition (i3 fastest) round-trips."""
    n1, n2, n3 = (int(x) for x in factor_rows(n))
    assert n1 * n2 * n3 >= n
    g = (n - 1) * frac // 10**6              # spans [0, n)
    i3 = g % n3
    rest = g // n3
    i1, i2 = rest // n2, rest % n2
    assert 0 <= i1 < n1 and 0 <= i2 < n2 and 0 <= i3 < n3
    assert (i1 * n2 + i2) * n3 + i3 == g


@settings(max_examples=20, deadline=None)
@given(log_d=st.integers(min_value=0, max_value=10))
def test_tt_dim_factorization_exact(log_d):
    d = 2 ** log_d
    d1, d2, d3 = factor_dim(d)
    assert d1 * d2 * d3 == d
