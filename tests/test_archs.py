"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and no NaNs (assignment requirement f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_arch_ids, get_arch

LM_ARCHS = [a for a in all_arch_ids() if get_arch(a).kind == "lm"]
RS_ARCHS = [a for a in all_arch_ids() if get_arch(a).kind == "recsys"]


def _tree_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(tree)
               if jnp.issubdtype(l.dtype, jnp.floating))


@pytest.mark.parametrize("arch_id", LM_ARCHS)
@pytest.mark.parametrize("embedding", ["full", "robe"])
def test_lm_smoke(arch_id, embedding):
    from repro.models import transformer as T
    cfg = get_arch(arch_id).make_config("smoke", embedding=embedding)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    logits, aux = T.forward(params, cfg, toks)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # one train step
    loss, grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, cfg, {"tokens": toks, "labels": toks})[0]
    )(params)
    assert bool(jnp.isfinite(loss)) and _tree_finite(grads)
    if embedding == "robe":
        assert float(jnp.abs(grads["embed"]["memory"]).sum()) > 0


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_decode_smoke(arch_id):
    from repro.models import transformer as T
    cfg = get_arch(arch_id).make_config("smoke")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    caches = T.init_cache(cfg, 2, 8)
    logits, caches = T.decode_step(
        params, cfg, caches, jnp.zeros((2, 1), jnp.int32), 0)
    assert logits.shape == (2, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch_id", RS_ARCHS)
@pytest.mark.parametrize("embedding", ["full", "robe"])
def test_recsys_smoke(arch_id, embedding):
    from repro.models import recsys as R
    cfg = get_arch(arch_id).make_config("smoke", embedding=embedding)
    rs = np.random.RandomState(0)
    batch = {"sparse": jnp.asarray(
        rs.randint(0, 40, (8, cfg.n_fields)), jnp.int32),
        "label": jnp.asarray(rs.randint(0, 2, (8,)), jnp.int32)}
    if cfg.n_dense:
        batch["dense"] = jnp.asarray(rs.randn(8, cfg.n_dense), jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda p: R.loss_fn(p, cfg, batch)[0]
    )(R.init_params(jax.random.PRNGKey(0), cfg))
    assert bool(jnp.isfinite(loss)) and _tree_finite(grads)
    if cfg.arch != "two_tower":
        out = R.forward(R.init_params(jax.random.PRNGKey(0), cfg), cfg, batch)
        assert out.shape == (8,)
        assert bool(jnp.all(jnp.isfinite(out)))


def test_two_tower_retrieval_smoke():
    from repro.models import recsys as R
    cfg = get_arch("two-tower-retrieval").make_config("smoke")
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(1)
    n_item = cfg.n_fields - cfg.n_user_fields
    scores = R.serve_scores(params, cfg, {
        "sparse": jnp.asarray(rs.randint(0, 40, (2, cfg.n_fields)),
                              jnp.int32),
        "cand_sparse": jnp.asarray(rs.randint(0, 40, (64, n_item)),
                                   jnp.int32)})
    assert scores.shape == (2, 64)
    assert bool(jnp.all(jnp.isfinite(scores)))


@pytest.mark.parametrize("shape", ["full_graph_sm", "molecule"])
def test_gnn_smoke(shape):
    from repro.models import gatedgcn as G
    cfg = get_arch("gatedgcn").make_config("smoke", shape=shape)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(2)
    if shape == "molecule":
        batch = {"nodes": jnp.zeros((4, 10, cfg.d_feat)),
                 "atom_types": jnp.asarray(rs.randint(0, cfg.atom_vocab,
                                                      (4, 10)), jnp.int32),
                 "edges": jnp.asarray(rs.randint(0, 10, (4, 20, 2)),
                                      jnp.int32),
                 "labels": jnp.asarray(rs.randint(0, 2, (4,)), jnp.int32),
                 "node_mask": jnp.ones((4, 10), jnp.int32)}
    else:
        edges = rs.randint(0, 20, (1, 60, 2))
        edges[0, -5:] = -1
        batch = {"nodes": jnp.asarray(rs.randn(1, 20, cfg.d_feat),
                                      jnp.float32),
                 "edges": jnp.asarray(edges, jnp.int32),
                 "labels": jnp.asarray(rs.randint(0, cfg.n_classes, (1, 20)),
                                       jnp.int32)}
    loss, grads = jax.value_and_grad(
        lambda p: G.loss_fn(p, cfg, batch)[0]
    )(params)
    assert bool(jnp.isfinite(loss)) and _tree_finite(grads)


def test_full_configs_construct():
    """The exact assigned full-scale configs must all build (no allocation)."""
    for a in all_arch_ids():
        b = get_arch(a)
        cfg = b.make_config("full")
        if b.kind == "lm":
            assert cfg.n_layers >= 16
            # eval_shape proves init is well-formed without allocating
            from repro.models.transformer import init_params
            import functools
            shapes = jax.eval_shape(
                functools.partial(init_params, cfg=cfg),
                jax.random.PRNGKey(0))
            assert len(jax.tree.leaves(shapes)) > 10


@pytest.mark.parametrize("arch", ["dcn", "deepfm", "fibinet", "dlrm_dcnv2"])
def test_paper_extra_families_smoke(arch):
    """The paper's Table-3 families beyond the assigned four (DCN, DeepFM,
    FiBiNET), and the MLPerf DLRM-DCNv2 with multi-hot fields,
    smoke-tested here."""
    from repro.models import recsys as R
    kw = dict(name=arch, vocab_sizes=(500, 300, 800, 100), embed_dim=8,
              embedding="robe", robe_size=2048, robe_block=8)
    if arch == "dcn":
        cfg = R.RecsysConfig(arch="dcn", cross_layers=2, dnn=(16,), **kw)
    elif arch == "deepfm":
        cfg = R.RecsysConfig(arch="deepfm", dnn=(16,), **kw)
    elif arch == "dlrm_dcnv2":
        cfg = R.RecsysConfig(arch="dlrm_dcnv2", n_dense=5, bot_mlp=(16, 8),
                             top_mlp=(16, 1), cross_layers=2, cross_rank=4,
                             bag_sizes=(2, 1, 3, 1), **kw)
    else:
        cfg = R.RecsysConfig(arch="fibinet", dnn=(16,), **kw)
    rs = np.random.RandomState(0)
    batch = {"sparse": jnp.asarray(rs.randint(0, 90, (8, cfg.n_columns)),
                                   jnp.int32),
             "label": jnp.asarray(rs.randint(0, 2, (8,)), jnp.int32)}
    if cfg.n_dense:
        batch["dense"] = jnp.asarray(rs.randn(8, cfg.n_dense), jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda p: R.loss_fn(p, cfg, batch)[0]
    )(R.init_params(jax.random.PRNGKey(0), cfg))
    assert bool(jnp.isfinite(loss)) and _tree_finite(grads)


#: the tables' size at published widths, in GB (f32): CriteoTB's ~204M
#: rows at d=64, and at the MLPerf models' d=128 (the paper's "100 GB")
PAPER_TABLE_GB = {"dlrm-rm2": (47, 58), "dlrm-criteo-tb": (95, 115),
                  "dlrm-dcnv2": (95, 115)}


@pytest.mark.parametrize("arch", sorted(PAPER_TABLE_GB))
def test_paper_model_config_exists(arch):
    """The paper's models (CriteoTB DLRM at d=64, the MLPerf DLRM and
    DLRM-DCNv2 at d=128) are first-class configs at published widths:
    their tables (~52 GB, ~100 GB) become a ROBE array 1000× smaller, and
    the full table the ``full`` substrate would store holds more than 50×
    the array's parameters."""
    cfg = get_arch(arch).make_config("full")
    spec = cfg.embedding_spec()
    lo, hi = PAPER_TABLE_GB[arch]
    full_gb = spec.total_rows * spec.dim * 4 / 1e9
    robe_mb = spec.param_count * 4 / 1e6
    assert lo < full_gb < hi, full_gb
    assert lo < robe_mb < hi, robe_mb             # GB of tables → MB of array
    assert spec.compression >= 999
    full = get_arch(arch).make_config("full", embedding="full")
    assert full.embedding_spec().param_count > 50 * spec.param_count


def test_dlrm_dcnv2_config_is_the_mlperf_model():
    """DLRM-DCNv2 at its published widths: 214 multi-hot ids over the 26
    CriteoTB fields, x0 = 128 + 26·128 = 3,456 through 3 cross layers of
    rank 512, top MLP 3456-1024-1024-512-256-1, and the 1000× ROBE array
    of dlrm-criteo-tb."""
    from repro.models import recsys as R
    cfg = get_arch("dlrm-dcnv2").make_config("full")
    assert cfg.n_fields == 26 and cfg.n_columns == 214
    assert cfg.robe_size == 26_135_627
    assert cfg.robe_size == get_arch("dlrm-criteo-tb").make_config(
        "full").robe_size
    shapes = jax.eval_shape(lambda k: R.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert [layer["v"].shape for layer in shapes["cross"]] == \
        [(3456, 512)] * 3
    assert [layer["w"].shape for layer in shapes["top"]] == [
        (3456, 1024), (1024, 1024), (1024, 512), (512, 256), (256, 1)]
    assert [layer["w"].shape for layer in shapes["bot"]] == [
        (13, 512), (512, 256), (256, 128)]
