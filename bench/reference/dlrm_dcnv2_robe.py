"""Plain reference for DLRM-DCNv2 with a ROBE embedding array, in
jax.numpy.

Written from the published descriptions, not from the program, and
imports nothing of it; the ROBE hash, the numerics, the MLPs, the loss
and adagrad are those of ``dlrm_robe.py`` beside it:

* DLRM-DCNv2 (mlcommons/training ``recommendation_v2/torchrec_dlrm``, the
  MLPerf recommendation model; Wang et al. 2020, "DCN V2",
  arXiv:2008.13535): field ``f`` of a sample carries
  ``multi_hot_sizes[f]`` ids, whose embeddings are summed; the bottom MLP
  over the dense features, ReLU after every layer; ``x0`` = [bottom
  output, the F pooled embeddings flattened in field order]; then each
  low-rank cross layer ``x_{l+1} = x0 ⊙ (W_l (V_l x_l) + b_l) + x_l`` with
  ``V_l`` (no bias) and ``W_l`` (bias ``b_l``); the top MLP over the last
  ``x``, ReLU between layers and none after the last; the score is its
  single output (a logit).
* ROBE-Z (Desai et al. 2022): as in ``dlrm_robe.py``; an id of field
  ``f`` is hashed under ``f`` whichever of the field's ids it is, and its
  slots are read one element at a time.

Departures, each one stated by the configuration file: no sign hash; one
ROBE array for all 26 fields in place of the 26 tables; the ids are the
traffic's (each drawn on its own, not MLPerf's synthetic multi-hot set).

Batches hold ``[B, ΣL]`` ids, field ``f``'s ``multi_hot_sizes[f]``
columns side by side, in field order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import load_module

_base = load_module("reference", "dlrm_robe.py")

Numerics = _base.Numerics
cast = _base.cast
matmul = _base.matmul
key_from_seed = _base.key_from_seed


def columns(cfg: dict) -> np.ndarray:
    """The field of each id column: field ``f`` ``multi_hot_sizes[f]``
    times, in field order."""
    sizes = cfg["multi_hot_sizes"]
    return np.repeat(np.arange(len(sizes)), sizes)


def block_bases(cfg: dict, ids: np.ndarray) -> np.ndarray:
    """int32 [B, ΣL, d/Z]: h(f, x·d/Z + j), the slot that block j of id x
    starts at, hashed under the id's field f."""
    d, z, m = cfg["embed_dim"], cfg["robe_block"], cfg["robe_size"]
    nb = d // z
    coeffs = _base.hash_coefficients(cfg["robe_hash"]["seed"],
                                     cfg["robe_hash"]["salt"])
    key = (ids.astype(np.uint64)[:, :, None] * np.uint64(nb)
           + np.arange(nb, dtype=np.uint64)[None, None, :])
    table = np.broadcast_to(
        columns(cfg).astype(np.uint64)[None, :, None], key.shape)
    return _base.block_hash(coeffs, table, key, m).astype(np.int32)


def embed(cfg: dict, memory, bases):
    """[B, ΣL, d/Z] block starts -> [B, ΣL, d] rows: slot ``(start + j)
    mod |M|`` for ``j < Z`` of each block, one element at a time."""
    z = cfg["robe_block"]
    slots = (bases[..., None] + jnp.arange(z, dtype=bases.dtype)) \
        % memory.shape[0]
    return memory[slots].reshape(bases.shape[0], bases.shape[1], -1)


def pooled(cfg: dict, memory, bases):
    """[B, F, d]: each field's embeddings summed over its ids."""
    emb = embed(cfg, memory, bases)                       # [B, ΣL, d]
    seg = jax.ops.segment_sum(jnp.swapaxes(emb, 0, 1), columns(cfg),
                              len(cfg["multi_hot_sizes"]),
                              indices_are_sorted=True)    # [F, B, d]
    return jnp.swapaxes(seg, 0, 1)


def logits(cfg: dict, params: dict, dense, bases, num):
    """DLRM-DCNv2 scores [B] from dense [B, n_dense] and the ids' ROBE
    block starts [B, ΣL, d/Z]."""
    dense = dense.astype(num.dtype)
    emb = pooled(cfg, params["embedding"]["memory"], bases)
    bot = _base._mlp(params["bot"], dense, num, relu_last=True)
    x0 = jnp.concatenate([bot, emb.reshape(emb.shape[0], -1)], axis=-1)
    x = x0
    for layer in params["cross"]:
        low = matmul(x, layer["v"], num.round_operands)
        x = x0 * (matmul(low, layer["w"], num.round_operands)
                  + layer["b"]) + x
    return _base._mlp(params["top"], x, num, relu_last=False)[:, 0]


def loss(cfg: dict, params, dense, bases, labels, num):
    return _base.bce(logits(cfg, params, dense, bases, num), labels)


def make_score(cfg: dict, num):
    """(params, dense, bases) -> scores [B], jitted, at ``num``."""
    return _base._highest(jax.jit(
        lambda p, d, b: logits(cfg, p, d, b, num)))


def make_train_step(cfg: dict, num, lr: float, eps: float):
    """(params, v, dense, bases, labels) -> (params, v, loss, grads),
    jitted, at ``num``: adagrad on the gradients of ``loss``."""

    def step(params, v, dense, bases, labels):
        value, grads = jax.value_and_grad(
            lambda p: loss(cfg, p, dense, bases, labels, num))(params)
        params, v = _base.adagrad(params, v, grads, lr, eps)
        return params, v, value, grads

    return _base._highest(jax.jit(step))


def init_params(cfg: dict, seed: int) -> dict:
    """Float32 weights in the program's layout, made on the device in one
    jitted call: the ROBE array ~ N(0, robe_init_scale²); each linear map
    (MLP layers, each cross layer's V and W) He-uniform over its fan-in;
    biases zero."""
    n_x0 = cfg["bot_mlp"][-1] + len(cfg["vocab_sizes"]) * cfg["embed_dim"]
    bot = [cfg["n_dense"]] + list(cfg["bot_mlp"])
    top = [n_x0] + list(cfg["top_mlp"])

    def he(key, fan_in, fan_out):
        lim = float(np.sqrt(6.0 / fan_in))
        return jax.random.uniform(key, (fan_in, fan_out), jnp.float32,
                                  -lim, lim)

    def mlp(key, dims):
        return [{"w": he(k, dims[i], dims[i + 1]),
                 "b": jnp.zeros((dims[i + 1],), jnp.float32)}
                for i, k in enumerate(jax.random.split(key, len(dims) - 1))]

    def cross(key):
        out = []
        for k in jax.random.split(key, cfg["cross_layers"]):
            kv, kw = jax.random.split(k)
            out.append({"v": he(kv, n_x0, cfg["cross_rank"]),
                        "w": he(kw, cfg["cross_rank"], n_x0),
                        "b": jnp.zeros((n_x0,), jnp.float32)})
        return out

    def make(key):
        km, kb, kc, kt = jax.random.split(key, 4)
        mem = (jax.random.normal(km, (cfg["robe_size"],), jnp.float32)
               * cfg["robe_init_scale"])
        return {"embedding": {"memory": mem}, "bot": mlp(kb, bot),
                "cross": cross(kc), "top": mlp(kt, top)}

    return jax.jit(make)(key_from_seed(seed))
