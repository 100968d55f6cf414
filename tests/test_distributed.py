"""Multi-device semantics tests.

XLA device count must be forced before jax initializes, so these run in
subprocesses with ``--xla_force_host_platform_device_count=8``; the main
pytest process keeps its single CPU device (per the assignment).
"""

from conftest import run_forced_subprocess


def _run(body: str):
    return run_forced_subprocess(body, n_devices=8)


def test_moe_ep_matches_dense_oracle():
    _run("""
        from repro.dist import api as dist
        from repro.nn.moe import (MoeConfig, moe_init, moe_apply_dense,
                                  moe_apply_ep, moe_param_specs)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = dist.default_rules()
        cfg = MoeConfig(d_model=16, d_ff=32, n_experts=8, top_k=2,
                        n_shared=1, capacity_factor=8.0, dispatch="ep")
        p = moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        y_dense, _ = moe_apply_dense(p, cfg, x)
        f = jax.shard_map(
            lambda pp, xx: moe_apply_ep(pp, cfg, xx,
                                        aux_axes=("data", "model")),
            mesh=mesh,
            in_specs=(moe_param_specs(cfg, rules),
                      P(("data", "model"), None)),
            out_specs=(P(("data", "model"), None), P()))
        y_ep, _ = jax.jit(f)(p, x)
        assert float(jnp.max(jnp.abs(y_dense - y_ep))) < 1e-5
        gd = jax.grad(lambda pp: (moe_apply_dense(pp, cfg, x)[0]**2).sum())(p)
        ge = jax.jit(jax.grad(lambda pp: (f(pp, x)[0]**2).sum()))(p)
        err = jax.tree.reduce(max, jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), gd, ge))
        assert err < 1e-5, err
    """)


def test_full_embedding_sharded_lookup_matches_local():
    _run("""
        from repro.nn.embeddings import (EmbeddingSpec, embedding_init,
                                         embedding_lookup,
                                         full_lookup_sharded_body)
        mesh = make_mesh((2, 4), ("data", "model"))
        spec = EmbeddingSpec(vocab_sizes=(40, 24, 64), dim=8, kind="full")
        params = embedding_init(jax.random.PRNGKey(0), spec, pad_rows_to=8)
        idx = jax.random.randint(jax.random.PRNGKey(1), (16, 3), 0, 24)
        want = embedding_lookup(params, spec, idx)
        table = params["table"]
        rows = table.shape[0] // 4
        f = jax.shard_map(
            lambda tb, ix: full_lookup_sharded_body(tb, ix, spec.offsets,
                                                    "model", rows),
            mesh=mesh, in_specs=(P("model", None), P("data", None)),
            out_specs=P(("data", "model"), None, None))
        got = jax.jit(f)(table, idx)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-6
        # gradient: scatter back into the sharded table
        gw = jax.grad(lambda t: (embedding_lookup({"table": t}, spec, idx)
                                 ** 2).sum())(table)
        gs = jax.jit(jax.grad(lambda t: (f(t, idx) ** 2).sum()))(table)
        assert float(jnp.max(jnp.abs(gw - gs))) < 1e-6
    """)


def test_grad_compression_error_feedback():
    _run("""
        from repro.train.compression import compressed_psum
        mesh = make_mesh((8,), ("data",))
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 1e-3}
        res = {"w": jnp.zeros((8, 64))}

        def body(gg, rr):
            gg = jax.tree.map(lambda x: x[0], gg)
            rr = jax.tree.map(lambda x: x[0], rr)
            out, nr = compressed_psum(gg, rr, ("data",), "int8")
            return (jax.tree.map(lambda x: x[None], out),
                    jax.tree.map(lambda x: x[None], nr))

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P("data", None), P("data", None)),
                          out_specs=(P("data", None), P("data", None)),
                          check_vma=False)
        out, new_res = jax.jit(f)(g, res)
        exact = g["w"].mean(0)
        got = out["w"][0]
        # int8 quantized mean within quantization error; EF captures the rest
        q_err = float(jnp.max(jnp.abs(got - exact)))
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127
        assert q_err <= scale * 1.01, (q_err, scale)
        # residual + dequantized == original (per shard, exact bookkeeping)
        recon = new_res["w"] + jnp.round(
            (g["w"] + 0) / scale).clip(-127, 127) * scale
        # bf16 path: lossless-ish roundtrip of EF
        out2, nr2 = jax.jit(f)(g, res)
        assert float(jnp.max(jnp.abs(out2["w"] - out["w"]))) == 0.0
    """)


def test_recsys_dlrm_distributed_matches_single_device():
    _run("""
        from repro.dist import api as dist
        from repro.launch.mesh import make_production_mesh
        from repro.models.recsys import RecsysConfig, init_params, loss_fn
        cfg = RecsysConfig(
            name="d", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
            top_mlp=(16, 1), embed_dim=8,
            vocab_sizes=(64, 96, 32), embedding="full",
            compute_dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(0), cfg)
        rs = np.random.RandomState(0)
        batch = {"dense": jnp.asarray(rs.randn(16, 4), jnp.float32),
                 "sparse": jnp.asarray(rs.randint(0, 30, (16, 3)), jnp.int32),
                 "label": jnp.asarray(rs.randint(0, 2, (16,)), jnp.int32)}
        l_local, _ = loss_fn(params, cfg, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        with dist.use(ctx):
            l_dist, _ = jax.jit(lambda p, b: loss_fn(p, cfg, b))(params,
                                                                 batch)
        assert abs(float(l_local) - float(l_dist)) < 1e-5, \
            (float(l_local), float(l_dist))
    """)


def test_lm_distributed_matches_single_device():
    _run("""
        from repro.dist import api as dist
        from repro.models.transformer import (TransformerConfig, init_params,
                                              loss_fn)
        cfg = TransformerConfig(
            name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=64, vocab=64, q_chunk=8,
            compute_dtype=jnp.float32, remat=False)
        p = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        batch = {"tokens": toks, "labels": toks}
        l_local, _ = loss_fn(p, cfg, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        with dist.use(ctx):
            l_dist, _ = jax.jit(lambda pp, b: loss_fn(pp, cfg, b))(p, batch)
        assert abs(float(l_local) - float(l_dist)) < 2e-4, \
            (float(l_local), float(l_dist))
    """)


def test_lm_decode_seq_sharded_cache_matches():
    _run("""
        from repro.dist import api as dist
        from repro.models.transformer import (TransformerConfig, decode_step,
                                              forward, init_cache,
                                              init_params)
        cfg = TransformerConfig(
            name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=64, vocab=64, q_chunk=0,
            compute_dtype=jnp.float32, cache_dtype=jnp.float32, remat=False)
        p = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        full, _ = forward(p, cfg, toks)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        from jax.sharding import NamedSharding
        cache = init_cache(cfg, 2, 8)
        cspec = jax.tree.map(
            lambda x: NamedSharding(mesh, P(None, "data", "model",
                                            *([None] * (x.ndim - 3)))),
            cache)
        cache = jax.tree.map(jax.device_put, cache, cspec)
        with dist.use(ctx):
            step = jax.jit(lambda pp, c, t, pos:
                           decode_step(pp, cfg, c, t, pos),
                           static_argnums=())
            outs = []
            for t in range(8):
                lg, cache = step(p, cache, toks[:, t:t + 1], t)
                outs.append(lg)
        dec = jnp.stack(outs, 1)
        err = float(jnp.max(jnp.abs(dec - full)))
        assert err < 2e-4, err
    """)


def test_gnn_edge_parallel_matches_single_device():
    _run("""
        from repro.dist import api as dist
        from repro.models.gatedgcn import GatedGCNConfig, forward, \\
            init_params
        cfg = GatedGCNConfig(name="g", n_layers=2, d_hidden=8, d_feat=4,
                             n_classes=3)
        params = init_params(jax.random.PRNGKey(0), cfg)
        rs = np.random.RandomState(0)
        n, e = 50, 8192     # ≥4096 edges triggers the edge-parallel path
        edges = rs.randint(0, n, (1, e, 2))
        edges[0, -100:] = -1
        batch = {"nodes": jnp.asarray(rs.randn(1, n, 4), jnp.float32),
                 "edges": jnp.asarray(edges, jnp.int32),
                 "labels": jnp.zeros((1, n), jnp.int32)}
        o_local = forward(params, cfg, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        with dist.use(ctx):
            o_dist = jax.jit(lambda p, b: forward(p, cfg, b))(params, batch)
        err = float(jnp.max(jnp.abs(o_local - o_dist)))
        assert err < 2e-3, err
    """)


def test_recsys_2d_table_sharding_matches_local():
    _run("""
        from repro.dist import api as dist
        from repro.models.recsys import RecsysConfig, init_params, loss_fn
        kw = dict(name="d", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                  top_mlp=(16, 1), embed_dim=8, vocab_sizes=(64, 96, 32),
                  compute_dtype=jnp.float32)
        cfg1 = RecsysConfig(embedding="full", **kw)
        cfg2 = RecsysConfig(embedding="full", full_table_shard="2d", **kw)
        params = init_params(jax.random.PRNGKey(0), cfg1)
        rs = np.random.RandomState(0)
        batch = {"dense": jnp.asarray(rs.randn(16, 4), jnp.float32),
                 "sparse": jnp.asarray(rs.randint(0, 30, (16, 3)), jnp.int32),
                 "label": jnp.asarray(rs.randint(0, 2, (16,)), jnp.int32)}
        l_local, _ = loss_fn(params, cfg1, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        with dist.use(ctx):
            l2d, _ = jax.jit(lambda p, b: loss_fn(p, cfg2, b))(params, batch)
            g_local = jax.grad(lambda p: loss_fn(p, cfg1, batch)[0])(params)
            g2d = jax.jit(jax.grad(
                lambda p: loss_fn(p, cfg2, batch)[0]))(params)
        assert abs(float(l_local) - float(l2d)) < 1e-5
        err = jax.tree.reduce(max, jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), g_local, g2d))
        assert err < 1e-5, err
    """)


def test_robe_model_sharded_matches_replicated():
    """ZeRO-3 ROBE (`robe_shard_model=True`): the array shards over `model`
    and is all-gathered per step; loss and slot gradients must match the
    replicated placement exactly, and the compiled step must actually carry
    the gather."""
    _run("""
        from repro.dist import api as dist
        from repro.dist.param_specs import recsys_specs
        from repro.models.recsys import RecsysConfig, init_params, loss_fn
        import functools
        from jax.sharding import NamedSharding
        kw = dict(name="d", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                  top_mlp=(16, 1), embed_dim=8, vocab_sizes=(64, 96, 32),
                  robe_size=512, robe_block=8, compute_dtype=jnp.float32)
        cfg_rep = RecsysConfig(embedding="robe", **kw)
        cfg_z3 = RecsysConfig(embedding="robe", robe_shard_model=True, **kw)
        params = init_params(jax.random.PRNGKey(0), cfg_rep)
        rs = np.random.RandomState(0)
        batch = {"dense": jnp.asarray(rs.randn(16, 4), jnp.float32),
                 "sparse": jnp.asarray(rs.randint(0, 30, (16, 3)), jnp.int32),
                 "label": jnp.asarray(rs.randint(0, 2, (16,)), jnp.int32)}
        l_rep, _ = loss_fn(params, cfg_rep, batch)
        g_rep = jax.grad(lambda p: loss_fn(p, cfg_rep, batch)[0])(params)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        spec = cfg_z3.embedding_spec()
        pshapes = jax.eval_shape(
            functools.partial(init_params, cfg=cfg_z3),
            jax.random.PRNGKey(0))
        pspecs = recsys_specs(pshapes, ctx.rules, embedding_spec=spec)
        assert pspecs["embedding"]["memory"] == P("model"), pspecs
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
        with dist.use(ctx):
            step = jax.jit(lambda p, b: loss_fn(p, cfg_z3, b),
                           in_shardings=(shardings, None))
            l_z3, _ = step(params, batch)
            g_z3 = jax.jit(jax.grad(
                lambda p: loss_fn(p, cfg_z3, batch)[0]),
                in_shardings=(shardings,))(params)
            hlo = step.lower(params, batch).compile().as_text()
        assert "all-gather" in hlo       # the ZeRO-3 gather is real
        assert abs(float(l_rep) - float(l_z3)) < 1e-5
        err = jax.tree.reduce(max, jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), g_rep, g_z3))
        assert err < 1e-5, err
    """)


def test_robe_replicated_lookup_is_local():
    """The replicated ROBE array under a mesh: each device looks up its
    own rows of a multi-hot DLRM-DCNv2 batch; loss and gradients match one
    device, and the compiled gradient step exchanges nothing but the
    all-reduce of the gradients."""
    _run("""
        from repro.dist import api as dist
        from repro.models.recsys import RecsysConfig, init_params, loss_fn
        cfg = RecsysConfig(name="d", arch="dlrm_dcnv2", n_dense=4,
                           bot_mlp=(16, 8), top_mlp=(16, 1), embed_dim=8,
                           vocab_sizes=(64, 96, 32), bag_sizes=(2, 1, 3),
                           cross_layers=2, cross_rank=4, embedding="robe",
                           robe_size=512, robe_block=8,
                           compute_dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(0), cfg)
        rs = np.random.RandomState(0)
        batch = {"dense": jnp.asarray(rs.randn(16, 4), jnp.float32),
                 "sparse": jnp.asarray(rs.randint(0, 30, (16, 6)), jnp.int32),
                 "label": jnp.asarray(rs.randint(0, 2, (16,)), jnp.int32)}
        grad = jax.value_and_grad(lambda p, b: loss_fn(p, cfg, b)[0])
        l_one, g_one = grad(params, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        with dist.use(ctx):
            step = jax.jit(grad)
            l_mesh, g_mesh = step(params, batch)
            hlo = step.lower(params, batch).compile().as_text()
        assert "all-reduce" in hlo
        for other in ("all-gather", "all-to-all", "collective-permute"):
            assert other not in hlo, other
        assert abs(float(l_one) - float(l_mesh)) < 1e-5
        err = jax.tree.reduce(max, jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), g_one, g_mesh))
        assert err < 1e-5, err
    """)


def test_recsys_cells_compile_every_backend():
    """The dlrm-rm2 serve cell compiles for all four substrates with each
    backend's own param_specs (mesh scaled to the CI host's 8 devices;
    the 16x16 production run is the same code path)."""
    _run("""
        from repro.dist import api as dist
        from repro.launch.cells import build_recsys_cell
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        for emb in ("full", "robe", "hashed", "tt"):
            with dist.use(ctx):
                cell = build_recsys_cell("dlrm-rm2", "serve_p99", ctx, emb)
                compiled = jax.jit(
                    cell.fn, in_shardings=cell.in_shardings
                ).lower(*cell.arg_shapes).compile()
            assert compiled is not None, emb
            print(emb, "ok")
        # fused-kernel path (Pallas interpret off-TPU): the same cells must
        # compile with every kernel-backed substrate's lookup fused
        for emb in ("robe", "hashed", "tt"):
            with dist.use(ctx):
                cell = build_recsys_cell("dlrm-rm2", "serve_p99", ctx, emb,
                                         use_kernel=True)
                compiled = jax.jit(
                    cell.fn, in_shardings=cell.in_shardings
                ).lower(*cell.arg_shapes).compile()
            assert compiled is not None, emb
            print(emb, "kernel ok")
    """)


def test_lm_embed_shard_map_lookup_matches_local():
    _run("""
        from repro.dist import api as dist
        from repro.models.transformer import (TransformerConfig, forward,
                                              init_params)
        cfg = TransformerConfig(
            name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=64, vocab=4096, q_chunk=0,
            compute_dtype=jnp.float32, remat=False)
        p = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 4096)
        l_local, _ = forward(p, cfg, toks)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        with dist.use(ctx):
            l_dist, _ = jax.jit(lambda pp, t: forward(pp, cfg, t))(p, toks)
        err = float(jnp.max(jnp.abs(l_local - l_dist)))
        assert err < 2e-4, err
    """)
