"""Cell builders for the multi-pod dry-run.

A *cell* = (architecture × input shape [× embedding variant]).  ``build``
returns the jit-able step function, ShapeDtypeStruct stand-ins for every
input (never allocating), and the input shardings for the production mesh.

Shape kinds:
  LM      train   -> train_step (fwd + bwd + optimizer update)
          prefill -> forward(logits_mode="last", collect_cache=True)
          decode  -> decode_step against a seq-sharded KV cache
  RecSys  train   -> train_step; serve -> forward; retrieval -> serve_scores
  GNN     train / train_sampled -> train_step (edge-parallel for big graphs)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.dist import api as dist
from repro.dist.param_specs import (recsys_specs, replicated_specs,
                                    state_specs, transformer_specs)
from repro.nn.embedding_backends import get_backend
from repro.train.optimizer import OptimizerConfig, make_optimizer

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class BuiltCell:
    cell_id: str
    fn: Callable
    arg_shapes: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    model_flops_per_step: float        # 6·N·D (dense) / 6·N_active·D (MoE)
    note: str = ""
    skip: Optional[str] = None


def _shardify(ctx, spec_tree):
    return dist.named_shardings(ctx, spec_tree)


def _dp(ctx):
    return ctx.rules.get("batch")


def _pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

_LM_OPT = {
    # the 1T cell: bf16 moments (memory — see DESIGN.md §8)
    "kimi-k2-1t-a32b": OptimizerConfig(kind="adam", lr=2e-4,
                                       moment_dtype=jnp.bfloat16),
}


def _lm_cfg(arch_id: str, shape: dict, embedding: str):
    bundle = get_arch(arch_id)
    over = {}
    if arch_id == "kimi-k2-1t-a32b":
        over["param_dtype"] = jnp.bfloat16   # 1T params: bf16 + FSDP
    if shape["kind"] != "train":
        over["remat"] = False
    return bundle.make_config("full", embedding=embedding, **over)


def _lm_state_shapes(cfg, opt):
    params = jax.eval_shape(
        functools.partial(__import__("repro.models.transformer",
                                     fromlist=["init_params"]).init_params,
                          cfg=cfg), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    return {"params": params, "opt": opt_state,
            "step": SDS((), jnp.int32)}


def build_lm_cell(arch_id: str, shape_name: str, ctx,
                  embedding: str = "full") -> BuiltCell:
    from repro.models import transformer as T
    bundle = get_arch(arch_id)
    shape = bundle.shapes[shape_name]
    cell_id = f"{arch_id}/{shape_name}[{embedding}]"
    if shape.get("skip"):
        return BuiltCell(cell_id, None, (), (), 0.0, skip=shape["skip"])
    cfg = _lm_cfg(arch_id, shape, embedding)
    fsdp = arch_id == "kimi-k2-1t-a32b"
    dp = _dp(ctx)
    b, t = shape["global_batch"], shape["seq_len"]
    n_active = cfg.active_param_count()

    pshapes = jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                             jax.random.PRNGKey(0))
    pspecs = transformer_specs(pshapes, ctx.rules, fsdp=fsdp)

    if shape["kind"] == "train":
        opt = make_optimizer(_LM_OPT.get(
            arch_id, OptimizerConfig(kind="adam", lr=3e-4)))
        state_shape = {"params": pshapes,
                       "opt": jax.eval_shape(opt.init, pshapes),
                       "step": SDS((), jnp.int32)}
        state_spec = {"params": pspecs,
                      "opt": state_specs(pspecs, state_shape["opt"]),
                      "step": P()}
        batch_shape = {"tokens": SDS((b, t), jnp.int32),
                       "labels": SDS((b, t), jnp.int32)}
        batch_spec = {"tokens": P(dp, None), "labels": P(dp, None)}

        def step(state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: T.loss_fn(p, cfg, batch)[0])(state["params"])
            new_p, new_o = opt.update(state["params"], grads, state["opt"],
                                      state["step"])
            return {"params": new_p, "opt": new_o,
                    "step": state["step"] + 1}, loss

        flops = 6.0 * n_active * b * t
        return BuiltCell(cell_id, step, (state_shape, batch_shape),
                         _shardify(ctx, (state_spec, batch_spec)), flops)

    if shape["kind"] == "prefill":
        def prefill(params, tokens):
            logits, _, cache = T.forward(params, cfg, tokens,
                                         collect_cache=True,
                                         logits_mode="last")
            return logits, cache

        tok_shape = SDS((b, t), jnp.int32)
        flops = 2.0 * n_active * b * t
        return BuiltCell(cell_id, prefill, (pshapes, tok_shape),
                         _shardify(ctx, (pspecs, P(dp, None))), flops)

    # decode: one token against a seq-len KV cache
    cache_shape = jax.eval_shape(
        lambda: T.init_cache(cfg, b, t))
    # caches: batch over dp, SEQUENCE over model (divides for every head
    # count; attention over the sharded S reduces via GSPMD).  Layer-stacked
    # entries carry a leading L dim; unrolled dense layers do not.
    def cache_spec(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        stacked = "layers" in keys and "dense_layers" not in keys
        pre = (None,) if stacked else ()
        tail = (None,) * (leaf.ndim - len(pre) - 2)
        return P(*(pre + (dp, "model") + tail))
    cspec = jax.tree_util.tree_map_with_path(cache_spec, cache_shape)

    def decode(params, caches, tokens, pos):
        return T.decode_step(params, cfg, caches, tokens, pos)

    flops = 2.0 * n_active * b * 1
    return BuiltCell(
        cell_id, decode,
        (pshapes, cache_shape, SDS((b, 1), jnp.int32), SDS((), jnp.int32)),
        _shardify(ctx, (pspecs, cspec, P(dp, None), P())), flops,
        note=f"serve_step: 1 new token, KV len {t}")


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

_RS_OPT = {
    "dlrm-rm2": OptimizerConfig(kind="sgd", lr=1.0),        # paper: SGD
    "dlrm-criteo-tb": OptimizerConfig(kind="sgd", lr=1.0),
}


def _recsys_batch(cfg, batch: int, ctx, spec_axes):
    shapes = {"sparse": SDS((batch, cfg.n_fields), jnp.int32)}
    specs = {"sparse": P(spec_axes, None)}
    if cfg.n_dense:
        shapes["dense"] = SDS((batch, cfg.n_dense), jnp.float32)
        specs["dense"] = P(spec_axes, None)
    shapes["label"] = SDS((batch,), jnp.int32)
    specs["label"] = P(spec_axes)
    return shapes, specs


def build_recsys_cell(arch_id: str, shape_name: str, ctx,
                      embedding: str = "robe",
                      use_kernel: bool = False) -> BuiltCell:
    from repro.models import recsys as R
    bundle = get_arch(arch_id)
    shape = bundle.shapes[shape_name]
    cell_id = f"{arch_id}/{shape_name}[{embedding}]" + \
        ("[kernel]" if use_kernel else "")
    table_2d = embedding == "full2d"
    emb_kind = "full" if table_2d else embedding
    cfg = bundle.make_config("full", embedding=emb_kind,
                             full_table_shard="2d" if table_2d else "model",
                             compute_dtype=jnp.bfloat16,
                             use_kernel=use_kernel)
    embedding = emb_kind
    emb_spec = cfg.embedding_spec()
    backend = get_backend(emb_spec.kind)
    dp = _dp(ctx)
    dp_t = (dp,) if isinstance(dp, str) else tuple(dp)
    # local-lookup substrates (robe/hashed/tt) → batch shards over the
    # WHOLE mesh; the full-table baseline exchanges over model → dp only
    flat_axes = dp_t + ("model",) if backend.local_batch else dp

    pshapes = jax.eval_shape(functools.partial(R.init_params, cfg=cfg),
                             jax.random.PRNGKey(0))
    pspecs = recsys_specs(pshapes, ctx.rules, embedding_spec=emb_spec)

    # model flops ≈ 2·(dense params)·batch + interaction; embedding is
    # memory-bound: report the dense-compute figure
    dense_params = sum(int(np.prod(l.shape)) for path, l in
                       jax.tree_util.tree_flatten_with_path(pshapes)[0]
                       if "embedding" not in str(path))

    if shape["kind"] == "train":
        b = shape["batch"]
        opt = make_optimizer(_RS_OPT.get(
            arch_id, OptimizerConfig(kind="adam", lr=1e-3)))
        state_shape = {"params": pshapes,
                       "opt": jax.eval_shape(opt.init, pshapes),
                       "step": SDS((), jnp.int32)}
        state_spec = {"params": pspecs,
                      "opt": state_specs(pspecs, state_shape["opt"]),
                      "step": P()}
        bshape, bspec = _recsys_batch(cfg, b, ctx, flat_axes)

        # quantized substrates (qrobe): int8 leaves pass through grad with
        # float0 cotangents (allow_int; the optimizer's frozen-leaf wrapper
        # skips them) and the backend's post-step projection folds the
        # float update back into the stored codes
        proj = R.make_project_fn(cfg)

        def step(state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: R.loss_fn(p, cfg, batch)[0],
                allow_int=True)(state["params"])
            new_p, new_o = opt.update(state["params"], grads, state["opt"],
                                      state["step"])
            if proj is not None:
                new_p = proj(new_p)
            return {"params": new_p, "opt": new_o,
                    "step": state["step"] + 1}, loss

        flops = 6.0 * dense_params * b
        return BuiltCell(cell_id, step, (state_shape, bshape),
                         _shardify(ctx, (state_spec, bspec)), flops)

    if shape["kind"] == "serve":
        b = shape["batch"]
        bshape, bspec = _recsys_batch(cfg, b, ctx, flat_axes)
        bshape.pop("label"), bspec.pop("label")
        if cfg.arch == "two_tower":
            fn = lambda params, batch: R.tower_vectors(params, cfg, batch)
        else:
            fn = lambda params, batch: R.serve_scores(params, cfg, batch)
        flops = 2.0 * dense_params * b
        return BuiltCell(cell_id, fn, (pshapes, bshape),
                         _shardify(ctx, (pspecs, bspec)), flops)

    # retrieval: 1 query × n candidates
    n_cand = shape["n_candidates"]
    if cfg.arch == "two_tower":
        n_item = cfg.n_fields - cfg.n_user_fields
        bshape = {"sparse": SDS((1, cfg.n_fields), jnp.int32),
                  "cand_sparse": SDS((n_cand, n_item), jnp.int32)}
        bspec = {"sparse": P(None, None),
                 "cand_sparse": P("model", None)}   # 1M % 256 ≠ 0; model=16 ✓
        fn = lambda params, batch: R.serve_scores(params, cfg, batch)
        flops = 2.0 * dense_params * n_cand
        note = "1 query vs 1e6 candidates (batched dot; candidates " \
               "sharded over model)"
    else:
        # CTR archs: score 1M candidate-augmented rows for one user
        bshape, bspec = _recsys_batch(cfg, n_cand, ctx, flat_axes)
        bshape.pop("label"), bspec.pop("label")
        # 1e6 % 256 != 0 → shard the bulk-scoring batch over model only
        if backend.local_batch:
            bspec = {k: P("model", *([None] * (len(v.shape) - 1)))
                     for k, v in bshape.items()}
        fn = lambda params, batch: R.serve_scores(params, cfg, batch)
        flops = 2.0 * dense_params * n_cand
        note = "retrieval-scoring as bulk forward over 1e6 rows"
    return BuiltCell(cell_id, fn, (pshapes, bshape),
                     _shardify(ctx, (pspecs, bspec)), flops, note=note)


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def build_gnn_cell(arch_id: str, shape_name: str, ctx,
                   embedding: str = "n/a") -> BuiltCell:
    from repro.models import gatedgcn as G
    bundle = get_arch(arch_id)
    shape = bundle.shapes[shape_name]
    cell_id = f"{arch_id}/{shape_name}"
    cfg = bundle.make_config("full", shape=shape_name)
    dp = _dp(ctx)
    opt = make_optimizer(OptimizerConfig(kind="adam", lr=1e-3))

    pshapes = jax.eval_shape(functools.partial(G.init_params, cfg=cfg),
                             jax.random.PRNGKey(0))
    pspecs = replicated_specs(pshapes)
    all_axes = tuple(ctx.mesh.axis_names)

    if shape_name == "molecule":
        b, n, e = shape["batch"], shape["n_nodes"], shape["n_edges"]
        bshape = {"nodes": SDS((b, n, 1), jnp.float32),
                  "atom_types": SDS((b, n), jnp.int32),
                  "edges": SDS((b, e, 2), jnp.int32),
                  "labels": SDS((b,), jnp.int32),
                  "node_mask": SDS((b, n), jnp.int32)}
        bspec = {k: P(dp, *([None] * (len(v.shape) - 1)))
                 for k, v in bshape.items()}
        n_edges_eff = b * e
    else:
        if shape["kind"] == "train_sampled":
            bn = shape["batch_nodes"]
            f1, f2 = shape["fanouts"]
            n = bn * (1 + f1 + f1 * f2)
            e = bn * f1 + bn * f1 * f2
        else:
            n, e = shape["n_nodes"], shape["n_edges"]
        e_pad = _pad_to(e, 512)
        bshape = {"nodes": SDS((1, n, cfg.d_feat), jnp.float32),
                  "edges": SDS((1, e_pad, 2), jnp.int32),
                  "labels": SDS((1, n), jnp.int32)}
        bspec = {"nodes": P(None, None, None),
                 "edges": P(None, all_axes, None),
                 "labels": P(None, None)}
        if shape["kind"] == "train_sampled":
            bshape["label_mask"] = SDS((1, n), jnp.int32)
            bspec["label_mask"] = P(None, None)
        n_edges_eff = e

    state_shape = {"params": pshapes,
                   "opt": jax.eval_shape(opt.init, pshapes),
                   "step": SDS((), jnp.int32)}
    state_spec = {"params": pspecs,
                  "opt": state_specs(pspecs, state_shape["opt"]),
                  "step": P()}

    def step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: G.loss_fn(p, cfg, batch)[0])(state["params"])
        new_p, new_o = opt.update(state["params"], grads, state["opt"],
                                  state["step"])
        return {"params": new_p, "opt": new_o,
                "step": state["step"] + 1}, loss

    h = cfg.d_hidden
    # per layer: 5 dense [E|N,h]x[h,h] + gather/scatter; fwd+bwd ≈ ×3
    flops = 3.0 * cfg.n_layers * (2.0 * (3 * n_edges_eff) * h * h
                                  + 2.0 * 2 * n_edges_eff * h)
    return BuiltCell(cell_id, step, (state_shape, bshape),
                     _shardify(ctx, (state_spec, bspec)), flops,
                     note="edge-parallel message passing"
                     if shape_name != "molecule" else "batch-parallel")


def build_cell(arch_id: str, shape_name: str, ctx,
               embedding: str = "default") -> BuiltCell:
    kind = get_arch(arch_id).kind
    if kind == "lm":
        emb = "full" if embedding == "default" else embedding
        return build_lm_cell(arch_id, shape_name, ctx, emb)
    if kind == "recsys":
        emb = "robe" if embedding == "default" else embedding
        return build_recsys_cell(arch_id, shape_name, ctx, emb)
    return build_gnn_cell(arch_id, shape_name, ctx)
