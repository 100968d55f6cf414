"""EmbeddingBackend protocol: registry, per-backend forward + gradient
parity against independent jnp references, bag pooling with per-sample
weights, spec validation/caching, and PartitionSpec ownership."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.robe import RobeSpec, robe_lookup as robe_lookup_core
from repro.kernels.ref import qr_materialize_ref, tt_materialize_ref
from repro.nn.embedding_backends.qrobe import _expand
from repro.nn.embeddings import (EmbeddingSpec, backend_names,
                                 embedding_init, embedding_lookup,
                                 embedding_lookup_bag, embedding_lookup_dist,
                                 get_backend)

VOCABS = (40, 24, 64)
DIM = 8
BACKENDS = ("full", "robe", "hashed", "tt", "qrobe")
#: substrates with a fused Pallas lookup kernel — their parity/gradient
#: cases run twice, kernel off (jnp path) and on (interpret mode)
KERNEL_BACKENDS = ("robe", "hashed", "tt", "qrobe")
KIND_KERNEL = [(k, False) for k in BACKENDS] + \
    [(k, True) for k in KERNEL_BACKENDS]


def _spec(kind: str, **kw) -> EmbeddingSpec:
    kw.setdefault("robe", RobeSpec(size=512, block_size=8, seed=3))
    kw.setdefault("hashed_buckets", 16)
    kw.setdefault("tt_rank", 4)
    return EmbeddingSpec(vocab_sizes=VOCABS, dim=DIM, kind=kind, **kw)


def _reference_table(params: dict, spec: EmbeddingSpec) -> jnp.ndarray:
    """The full [total_rows, dim] logical table each substrate represents,
    materialized through an INDEPENDENT jnp path (whole-table einsums /
    core-module lookups, not the backend's per-row code)."""
    if spec.kind == "full":
        return params["table"][:spec.total_rows]
    if spec.kind == "robe":
        rows = jnp.arange(spec.total_rows, dtype=jnp.int32)
        tids = np.repeat(np.arange(spec.n_fields, dtype=np.uint32),
                         np.asarray(spec.vocab_sizes))
        local = rows - jnp.asarray(spec.offsets, jnp.int32)[tids]
        return robe_lookup_core(params["memory"], spec.robe,
                                jnp.asarray(tids), local, spec.dim)
    if spec.kind == "hashed":
        return qr_materialize_ref(params["q_table"], params["r_table"],
                                  spec.vocab_sizes, spec.hashed_buckets)
    if spec.kind == "tt":
        return tt_materialize_ref(params["core0"], params["core1"],
                                  params["core2"])[:spec.total_rows]
    if spec.kind == "qrobe":
        # dequantize the whole array (codes·scale + the straight-through
        # delta carrier), then read it through the core ROBE lookup — the
        # same independent path the float robe case uses
        memory = (params["codes"].astype(jnp.float32)
                  * _expand(params["scale"], params["codes"].shape[0])
                  + params["delta"].astype(jnp.float32))
        rows = jnp.arange(spec.total_rows, dtype=jnp.int32)
        tids = np.repeat(np.arange(spec.n_fields, dtype=np.uint32),
                         np.asarray(spec.vocab_sizes))
        local = rows - jnp.asarray(spec.offsets, jnp.int32)[tids]
        return robe_lookup_core(memory, spec.robe, jnp.asarray(tids),
                                local, spec.dim)
    raise AssertionError(spec.kind)


def _max_grad_err(ga, gb):
    """Max abs difference across grad trees, skipping float0 leaves (the
    int8 code cotangents — both paths must agree those are gradient-free,
    which the zip-dtype check below enforces)."""
    errs = [0.0]
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        if a.dtype == jax.dtypes.float0 or b.dtype == jax.dtypes.float0:
            assert a.dtype == b.dtype
            continue
        errs.append(float(jnp.max(jnp.abs(a - b))))
    return max(errs)


def test_registry_returns_all_registered():
    for name in BACKENDS:
        assert get_backend(name).name == name
    assert set(BACKENDS) <= set(backend_names())


def test_unknown_backend_raises_with_names():
    with pytest.raises(KeyError, match="robe"):
        get_backend("no-such-substrate")


@pytest.mark.parametrize("kind,use_kernel", KIND_KERNEL)
def test_lookup_matches_reference(kind, use_kernel):
    spec = _spec(kind, use_kernel=use_kernel)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    rs = np.random.RandomState(1)
    idx = jnp.asarray(rs.randint(0, min(VOCABS), (16, 3)), jnp.int32)
    got = embedding_lookup(params, spec, idx)
    table = _reference_table(params, spec)
    g = jnp.asarray(spec.offsets, jnp.int32)[None, :] + idx
    want = jnp.take(table, g, axis=0)
    assert got.shape == (16, 3, DIM)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind,use_kernel", KIND_KERNEL)
def test_grad_matches_reference(kind, use_kernel):
    spec = _spec(kind, use_kernel=use_kernel)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    rs = np.random.RandomState(2)
    idx = jnp.asarray(rs.randint(0, min(VOCABS), (8, 3)), jnp.int32)
    ct = jnp.asarray(rs.randn(8, 3, DIM), jnp.float32)
    g = jnp.asarray(spec.offsets, jnp.int32)[None, :] + idx

    def loss_backend(p):
        return (embedding_lookup(p, spec, idx) * ct).sum()

    def loss_reference(p):
        return (jnp.take(_reference_table(p, spec), g, axis=0) * ct).sum()

    gb = jax.grad(loss_backend, allow_int=True)(params)
    gr = jax.grad(loss_reference, allow_int=True)(params)
    assert _max_grad_err(gb, gr) < 1e-4


@pytest.mark.parametrize("kind", KERNEL_BACKENDS)
def test_kernel_path_tracks_jnp_path(kind):
    """Fused (interpret) and jnp lookups must agree bit-for-bit-close in
    forward AND gradient — the regression gate against drift between the
    two paths."""
    spec_j = _spec(kind)
    spec_k = _spec(kind, use_kernel=True)
    params = embedding_init(jax.random.PRNGKey(0), spec_j)
    rs = np.random.RandomState(7)
    idx = jnp.asarray(rs.randint(0, min(VOCABS), (16, 3)), jnp.int32)
    ct = jnp.asarray(rs.randn(16, 3, DIM), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(embedding_lookup(params, spec_k, idx)),
        np.asarray(embedding_lookup(params, spec_j, idx)),
        rtol=1e-6, atol=1e-7)
    gk = jax.grad(lambda p: (embedding_lookup(p, spec_k, idx) * ct).sum(),
                  allow_int=True)(params)
    gj = jax.grad(lambda p: (embedding_lookup(p, spec_j, idx) * ct).sum(),
                  allow_int=True)(params)
    assert _max_grad_err(gk, gj) < 1e-5


@pytest.mark.parametrize("kind", BACKENDS)
def test_field_subset_lookup(kind):
    spec = _spec(kind)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    rs = np.random.RandomState(3)
    idx_all = jnp.asarray(rs.randint(0, min(VOCABS), (6, 3)), jnp.int32)
    want = embedding_lookup(params, spec, idx_all)[:, 1:]
    got = embedding_lookup(params, spec, idx_all[:, 1:], fields=(1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


@pytest.mark.parametrize("kind", BACKENDS)
def test_lookup_bag_mean_with_weights(kind):
    """EmbeddingBag parity: weighted mean over a −1-padded bag equals the
    explicit per-slot weighted average of single lookups."""
    spec = _spec(kind)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    rs = np.random.RandomState(4)
    b, f, bag = 5, 3, 4
    idx = rs.randint(0, min(VOCABS), (b, f, bag))
    idx[0, 0, 2:] = -1                     # padded tail
    idx[2, 1, :] = -1                      # fully-empty bag
    # fractional masses (< 1) must divide by the true weight mass, not a
    # clamped max(mass, 1)
    w = (rs.rand(b, f, bag) * 0.3).astype(np.float32)
    idx_j, w_j = jnp.asarray(idx, jnp.int32), jnp.asarray(w)

    got = embedding_lookup_bag(params, spec, idx_j, combiner="mean",
                               weights=w_j)
    acc = np.zeros((b, f, DIM), np.float32)
    wm = np.zeros((b, f), np.float32)
    for j in range(bag):
        ej = np.asarray(embedding_lookup(
            params, spec, jnp.asarray(np.maximum(idx[:, :, j], 0),
                                      jnp.int32)))
        wj = w[:, :, j] * (idx[:, :, j] >= 0)
        acc += ej * wj[..., None]
        wm += wj
    want = np.where(wm[..., None] > 0,
                    acc / np.where(wm > 0, wm, 1.0)[..., None], 0.0)
    assert got.shape == (b, f, DIM)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_lookup_bag_sum_unweighted_masks_padding():
    spec = _spec("full")
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = jnp.asarray([[[2, 5, -1]]], jnp.int32)
    got = embedding_lookup_bag(params, spec,
                               jnp.tile(idx, (1, 3, 1)), combiner="sum")
    e = embedding_lookup(params, spec, jnp.asarray([[2, 2, 2], [5, 5, 5]],
                                                   jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(e[0] + e[1]), rtol=1e-6)


# ---------------------------------------------------------------------------
# multi-hot fields (EmbeddingSpec.bag_sizes): [B, ΣL] id columns, pooled
# ---------------------------------------------------------------------------

BAGS = (3, 1, 4)


def _multi_hot(spec: EmbeddingSpec, b: int, seed: int) -> np.ndarray:
    rs = np.random.RandomState(seed)
    return np.stack([rs.randint(0, spec.vocab_sizes[f], b)
                     for f in spec.columns], 1).astype(np.int32)


def test_multi_hot_columns():
    spec = _spec("robe", bag_sizes=BAGS)
    assert spec.columns == (0, 0, 0, 1, 2, 2, 2, 2)
    np.testing.assert_array_equal(spec.column_offsets,
                                  spec.offsets[[0, 0, 0, 1, 2, 2, 2, 2]])
    assert _spec("robe").columns == (0, 1, 2)


@pytest.mark.parametrize("bad", [(1, 2), (1, 0, 2), (2, 2, 2, 2)])
def test_bag_sizes_validated(bad):
    with pytest.raises(ValueError, match="bag_sizes"):
        _spec("robe", bag_sizes=bad)


@pytest.mark.parametrize("kind", BACKENDS)
def test_multi_hot_pooling_matches_reference(kind):
    """The pooled lookup equals, per field, the sum of the reference
    table's rows of the field's ids (so each column is read as a row of
    its own field)."""
    spec = _spec(kind, bag_sizes=BAGS)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = _multi_hot(spec, 16, 5)
    got = embedding_lookup_dist(params, spec, jnp.asarray(idx))
    rows = np.asarray(jnp.take(_reference_table(params, spec),
                               jnp.asarray(idx + spec.column_offsets[None]),
                               axis=0))
    want = np.stack([rows[:, np.asarray(spec.columns) == f].sum(1)
                     for f in range(spec.n_fields)], 1)
    assert got.shape == (16, 3, DIM)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", BACKENDS)
def test_multi_hot_pooling_matches_lookup_bag(kind):
    """The ragged pooling equals ``lookup_bag`` on the same ids, each
    field padded with −1 to the widest bag."""
    spec = _spec(kind, bag_sizes=BAGS)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = _multi_hot(spec, 8, 6)
    padded = np.full((8, spec.n_fields, max(BAGS)), -1, np.int32)
    cols = np.asarray(spec.columns)
    for f, n in enumerate(BAGS):
        padded[:, f, :n] = idx[:, cols == f]
    got = embedding_lookup_dist(params, spec, jnp.asarray(idx))
    want = embedding_lookup_bag(params, spec, jnp.asarray(padded))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_multi_hot_columns_hash_under_their_field():
    """Every ROBE id is hashed under its column's field: the lookup before
    pooling equals the element-wise ROBE lookup with table ids
    ``spec.columns``, and not with one table id per column."""
    spec = _spec("robe", bag_sizes=BAGS)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = jnp.asarray(_multi_hot(spec, 8, 7))
    got = np.asarray(get_backend("robe").lookup_dist(params, spec, idx))

    def core(tids):
        return np.asarray(robe_lookup_core(
            params["memory"], spec.robe,
            jnp.asarray(tids, jnp.uint32)[None, :], idx, DIM))

    np.testing.assert_array_equal(got, core(spec.columns))
    wrong = core(np.arange(len(spec.columns)))
    assert not np.allclose(got[:, 1:3], wrong[:, 1:3])


@pytest.mark.parametrize("kind", BACKENDS)
def test_multi_hot_grad_matches_reference(kind):
    spec = _spec(kind, bag_sizes=BAGS)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = _multi_hot(spec, 8, 8)
    ct = jnp.asarray(np.random.RandomState(9).randn(8, 3, DIM), jnp.float32)
    rows = jnp.asarray(idx + spec.column_offsets[None])
    onehot = jnp.asarray(np.asarray(spec.columns)[:, None]
                         == np.arange(spec.n_fields)[None], jnp.float32)

    def loss_backend(p):
        return (embedding_lookup_dist(p, spec, jnp.asarray(idx)) * ct).sum()

    def loss_reference(p):
        per_col = jnp.take(_reference_table(p, spec), rows, axis=0)
        return jnp.einsum("bcd,cf,bfd->", per_col, onehot, ct,
                          precision="highest")

    gb = jax.grad(loss_backend, allow_int=True)(params)
    gr = jax.grad(loss_reference, allow_int=True)(params)
    for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(gr)):
        assert a.dtype == b.dtype
        if a.dtype != jax.dtypes.float0:        # qrobe's int8 codes
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", BACKENDS)
def test_one_hot_lookup_dist_adds_nothing(kind):
    """With one id per field (``bag_sizes == ()``) the layer's lookup
    lowers to exactly the backend's plain lookup: no pooling, the default
    fields."""
    spec = _spec(kind)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    idx = jnp.zeros((4, 3), jnp.int32)
    dist_jaxpr = jax.make_jaxpr(
        lambda p, i: embedding_lookup_dist(p, spec, i))(params, idx)
    plain = jax.make_jaxpr(
        lambda p, i: get_backend(kind).lookup(p, spec, i))(params, idx)
    assert str(dist_jaxpr) == str(plain)


# ---------------------------------------------------------------------------
# spec hygiene (construction-time validation + cached offsets)
# ---------------------------------------------------------------------------

def test_offsets_cached_and_correct():
    spec = _spec("full")
    assert spec.offsets is spec.offsets          # cached, not recomputed
    np.testing.assert_array_equal(spec.offsets, np.asarray([0, 40, 64]))


@pytest.mark.parametrize("bad", [(), (100, 0), (100, -3), (0,)])
def test_vocab_sizes_validated(bad):
    with pytest.raises(ValueError):
        EmbeddingSpec(vocab_sizes=bad, dim=8, kind="full")


def test_robe_requires_robe_spec():
    with pytest.raises(ValueError, match="robe spec"):
        EmbeddingSpec(vocab_sizes=VOCABS, dim=8, kind="robe", robe=None)


# ---------------------------------------------------------------------------
# layout + config sweep
# ---------------------------------------------------------------------------

def test_param_specs_owned_by_backend():
    rules = {"batch": "data", "table_rows": "model"}
    assert get_backend("full").param_specs(_spec("full"), rules) \
        == {"table": P("model", None)}
    assert get_backend("full").param_specs(
        _spec("full", placement="2d"), rules) \
        == {"table": P(("data", "model"), None)}
    assert get_backend("robe").param_specs(_spec("robe"), rules) \
        == {"memory": P()}
    assert get_backend("robe").param_specs(
        _spec("robe", placement="model"), rules) \
        == {"memory": P("model")}
    for kind in ("hashed", "tt", "qrobe"):
        tree = get_backend(kind).param_specs(_spec(kind), rules)
        assert all(s == P() for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, P)))


def test_recsys_specs_delegate_embedding_subtree():
    from repro.dist.param_specs import recsys_specs
    spec = _spec("full")
    pshapes = {"embedding": {"table": jax.ShapeDtypeStruct(
        (128, DIM), jnp.float32)},
        "top": [jax.ShapeDtypeStruct((4, 4), jnp.float32)]}
    rules = {"batch": "data", "table_rows": "model"}
    out = recsys_specs(pshapes, rules, embedding_spec=spec)
    assert out["embedding"]["table"] == P("model", None)
    assert out["top"][0] == P()


@pytest.mark.parametrize("kind", BACKENDS)
def test_dlrm_config_sweeps_backend(kind):
    from repro.configs import get_arch
    from repro.models import recsys as R
    cfg = get_arch("dlrm-rm2").make_config("smoke", embedding=kind)
    rs = np.random.RandomState(0)
    batch = {"sparse": jnp.asarray(rs.randint(0, 40, (8, cfg.n_fields)),
                                   jnp.int32),
             "dense": jnp.asarray(rs.randn(8, cfg.n_dense), jnp.float32),
             "label": jnp.asarray(rs.randint(0, 2, (8,)), jnp.int32)}
    loss, grads = jax.value_and_grad(
        lambda p: R.loss_fn(p, cfg, batch)[0], allow_int=True
    )(R.init_params(jax.random.PRNGKey(0), cfg))
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(grads)
               if l.dtype != jax.dtypes.float0)


# ---------------------------------------------------------------------------
# the serve path with the kernels on
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KERNEL_BACKENDS)
def test_dlrm_serve_kernel_path_matches_jnp(kind):
    """End-to-end parity: dlrm-rm2 smoke scoring with use_kernel=True (the
    substrate's lookup kernel, then the dot-interaction kernel) equals the
    jnp path to 1e-5."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import recsys as R
    cfg = get_arch("dlrm-rm2").make_config("smoke", embedding=kind)
    cfg_k = dataclasses.replace(cfg, use_kernel=True)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(5)
    batch = {"sparse": jnp.asarray(rs.randint(0, 40, (16, cfg.n_fields)),
                                   jnp.int32),
             "dense": jnp.asarray(rs.randn(16, cfg.n_dense), jnp.float32)}
    want = R.serve_scores(params, cfg, batch)
    got = R.serve_scores(params, cfg_k, batch)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", BACKENDS)
def test_param_count_matches_init(kind):
    """``param_count`` (what ``EmbeddingSpec.param_count`` and
    ``.compression`` read) equals the elements of the parameter leaves
    ``init`` makes.  qrobe counts what it serves, ``codes`` and ``scale``:
    its f32 ``delta`` is zero by construction and never ships."""
    spec = _spec(kind)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    if kind == "qrobe":
        assert not np.any(np.asarray(params.pop("delta")))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert get_backend(kind).param_count(spec) == spec.param_count == n > 0
