"""The recsys cell builder: one (architecture × shape × embedding) step.

A *cell* = (recsys architecture × input shape [× embedding substrate]).
``build_recsys_cell`` returns the jit-able step function (``train`` ->
train_step; ``serve`` -> forward; ``retrieval`` -> serve_scores),
ShapeDtypeStruct stand-ins for every input (never allocating), and the
input shardings for the given mesh.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.dist import api as dist
from repro.dist.param_specs import recsys_specs, state_specs
from repro.nn.embedding_backends import get_backend
from repro.train.optimizer import OptimizerConfig, make_optimizer

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class BuiltCell:
    cell_id: str
    fn: Callable
    arg_shapes: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]


def _shardify(ctx, spec_tree):
    return dist.named_shardings(ctx, spec_tree)


def _dp(ctx):
    return ctx.rules.get("batch")


_RS_OPT = {
    "dlrm-rm2": OptimizerConfig(kind="sgd", lr=1.0),        # paper: SGD
    "dlrm-criteo-tb": OptimizerConfig(kind="sgd", lr=1.0),
}


def _recsys_batch(cfg, batch: int, ctx, spec_axes):
    shapes = {"sparse": SDS((batch, cfg.n_columns), jnp.int32)}
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

        return BuiltCell(cell_id, step, (state_shape, bshape),
                         _shardify(ctx, (state_spec, bspec)))

    if shape["kind"] == "serve":
        b = shape["batch"]
        bshape, bspec = _recsys_batch(cfg, b, ctx, flat_axes)
        bshape.pop("label"), bspec.pop("label")
        if cfg.arch == "two_tower":
            fn = lambda params, batch: R.tower_vectors(params, cfg, batch)
        else:
            fn = lambda params, batch: R.serve_scores(params, cfg, batch)
        return BuiltCell(cell_id, fn, (pshapes, bshape),
                         _shardify(ctx, (pspecs, bspec)))

    # retrieval: 1 query × n candidates
    n_cand = shape["n_candidates"]
    if cfg.arch == "two_tower":
        n_item = cfg.n_fields - cfg.n_user_fields
        bshape = {"sparse": SDS((1, cfg.n_fields), jnp.int32),
                  "cand_sparse": SDS((n_cand, n_item), jnp.int32)}
        bspec = {"sparse": P(None, None),
                 "cand_sparse": P("model", None)}   # 1M % 256 ≠ 0; model=16 ✓
        fn = lambda params, batch: R.serve_scores(params, cfg, batch)
    else:
        # CTR archs: score 1M candidate-augmented rows for one user
        bshape, bspec = _recsys_batch(cfg, n_cand, ctx, flat_axes)
        bshape.pop("label"), bspec.pop("label")
        # 1e6 % 256 != 0 → shard the bulk-scoring batch over model only
        if backend.local_batch:
            bspec = {k: P("model", *([None] * (len(v.shape) - 1)))
                     for k, v in bshape.items()}
        fn = lambda params, batch: R.serve_scores(params, cfg, batch)
    return BuiltCell(cell_id, fn, (pshapes, bshape),
                     _shardify(ctx, (pspecs, bspec)))
