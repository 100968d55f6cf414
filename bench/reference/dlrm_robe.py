"""Plain reference for DLRM with a ROBE embedding array, in jax.numpy.

Written from the published descriptions, not from the program, and
imports nothing of it:

* DLRM (Naumov et al. 2019; facebookresearch/dlrm ``dlrm_s_pytorch.py``):
  bottom MLP over the dense features with ReLU after every layer; the
  dot interaction of the bottom output and the F field embeddings, kept as
  the strictly lower triangle of their gram matrix in row-major order
  (``for i: for j < i``); the top MLP over [bottom output, triangle], ReLU
  between layers and none after the last; the score is the last layer's
  single output (a logit).  Loss: binary cross-entropy with logits, the
  mean over the batch.
* ROBE-Z (Desai et al. 2022, Eq. 1-3): element ``i`` of row ``x`` of
  field ``e`` lives at ``(h(e, (x·d + i) >> log2 Z) + ((x·d + i) & (Z-1)))
  mod |M|`` of one circular array ``M``; ``h`` is drawn from a 2-universal
  family ``((a_e·e + Σ a_k·digit_k(key) + b) mod P) mod |M|`` with
  P = 2^31 - 1 over the key's three 31-bit digits.  The coefficients are
  drawn as the configuration's ``robe_hash`` states.  Gradients of every
  element that shares a slot add up in that slot (the paper's Fig. 2).
* Adagrad (Duchi et al. 2011): ``v += g²; p -= lr·g / (sqrt(v) + eps)``.

Departures from the paper, each one stated by the configuration file:
no sign hash (``robe_use_sign`` false); one array for all 26 fields; the
optimizer is adagrad with a zero accumulator at the start.

The hash is evaluated on the host in numpy uint64, once per (field,
block); the gather, the model and the gradient run in jax on the device.

Numerics (``Numerics``):

* ``f32`` — float32 throughout, every matmul at ``Precision.HIGHEST``.
* ``tpu_default`` — what float32 at JAX's default matmul precision means
  on a TPU: the operands of each matrix product are rounded to bfloat16 and
  the products summed in float32, forward and backward.  A product with
  one side of width 1 (a matrix-vector or outer product) is computed by
  the TPU compiler on the vector unit in float32 and is left unrounded.
  This is the precision the configurations state.
* ``bf16`` — parameters, activations, gradients and optimizer state in
  bfloat16: the control, one precision below.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

P31 = (1 << 31) - 1
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# ROBE hashing (host)
# ---------------------------------------------------------------------------

def hash_coefficients(seed: int, salt: int) -> tuple:
    """(a_table, a2, a1, a0, b) of one member of the hash family, drawn as
    the configuration's ``robe_hash`` states."""
    rs = np.random.RandomState((seed * 0x9E3779B1 + salt * 0x85EBCA77)
                               % (2 ** 31))
    a = [int(rs.randint(1, P31, dtype=np.int64)) for _ in range(4)]
    b = int(rs.randint(0, P31, dtype=np.int64))
    return (*a, b)


def block_hash(coeffs: tuple, table: np.ndarray, key: np.ndarray,
               m: int) -> np.ndarray:
    """h(table, key) in [0, m) for uint64 ``key`` (any shape)."""
    a_t, a2, a1, a0, b = (np.uint64(c) for c in coeffs)
    key = key.astype(np.uint64)
    m31 = np.uint64(P31)
    d0 = key & m31
    d1 = (key >> np.uint64(31)) & m31
    d2 = key >> np.uint64(62)
    # four products below 2^62 each plus b: the sum stays below 2^64
    acc = (b + a_t * table.astype(np.uint64) + a2 * d2 + a1 * d1
           + a0 * d0)
    return (acc % m31) % np.uint64(m)


def block_bases(cfg: dict, ids: np.ndarray) -> np.ndarray:
    """int32 [B, F, d/Z]: h(e, x·d/Z + j), the slot that block j of row x
    of field e starts at (rows cover whole blocks: d is a multiple of Z)."""
    d, z, m = cfg["embed_dim"], cfg["robe_block"], cfg["robe_size"]
    if d % z:
        raise ValueError(f"embed_dim {d} must be a multiple of robe_block {z}")
    nb = d // z
    f = ids.shape[1]
    coeffs = hash_coefficients(cfg["robe_hash"]["seed"],
                               cfg["robe_hash"]["salt"])
    key = (ids.astype(np.uint64)[:, :, None] * np.uint64(nb)
           + np.arange(nb, dtype=np.uint64)[None, None, :])
    table = np.broadcast_to(np.arange(f, dtype=np.uint64)[None, :, None],
                            key.shape)
    return block_hash(coeffs, table, key, m).astype(np.int32)


def embed(cfg: dict, memory, bases):
    """[B, F, d/Z] block starts -> [B, F, d] rows (on the device): each
    block is the Z slots from its start on, round the circular array."""
    z = cfg["robe_block"]
    ring = jnp.concatenate([memory, memory[:z - 1]])    # the wrap, unrolled
    blocks = jax.vmap(lambda s: jax.lax.dynamic_slice(ring, (s,), (z,)))(
        bases.reshape(-1))
    return blocks.reshape(bases.shape[0], bases.shape[1], -1)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Numerics:
    dtype: object            # dtype of parameters and activations
    round_operands: bool     # round matmul operands to bfloat16

    @staticmethod
    def named(name: str) -> "Numerics":
        return {"f32": Numerics(jnp.float32, False),
                "tpu_default": Numerics(jnp.float32, True),
                "bf16": Numerics(jnp.bfloat16, False)}[name]


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _mm(a, b, rnd: bool):
    """a [..., K] @ b [K, N] (or batched), operands rounded when ``rnd``
    and neither side is one wide."""
    if rnd and a.shape[-1] > 1 and b.shape[-1] > 1 and a.shape[-2] > 1:
        a, b = _bf16(a), _bf16(b)
    return jnp.matmul(a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul(x, w, rnd: bool):
    """x [M, K] @ w [K, N]; each product of the backward pass rounds its
    operands as the forward does."""
    return _mm(x, w, rnd)


def _matmul_fwd(x, w, rnd):
    return _mm(x, w, rnd), (x, w)


def _matmul_bwd(rnd, res, g):
    x, w = res
    return _mm(g, w.T, rnd), _mm(x.T, g, rnd)


matmul.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def gram(feats, rnd: bool):
    """[B, F, D] -> [B, F, F] pairwise dot products."""
    return _mm(feats, jnp.swapaxes(feats, 1, 2), rnd)


def _gram_fwd(feats, rnd):
    return gram(feats, rnd), feats


def _gram_bwd(rnd, feats, g):
    sym = g + jnp.swapaxes(g, 1, 2)
    return (_mm(sym, feats, rnd),)


gram.defvjp(_gram_fwd, _gram_bwd)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def cast(params, num: Numerics):
    return jax.tree.map(lambda p: p.astype(num.dtype), params)


def _mlp(layers: Sequence[dict], x, num: Numerics, relu_last: bool):
    for i, layer in enumerate(layers):
        x = matmul(x, layer["w"], num.round_operands) + layer["b"]
        if i < len(layers) - 1 or relu_last:
            x = jnp.where(x > 0, x, 0)        # ReLU, derivative 0 at 0
    return x


def logits(cfg: dict, params: dict, dense, bases, num: Numerics):
    """DLRM scores [B] from dense [B, n_dense] and the rows' ROBE block
    starts [B, F, d/Z]."""
    dense = dense.astype(num.dtype)
    emb = embed(cfg, params["embedding"]["memory"], bases)
    bot = _mlp(params["bot"], dense, num, relu_last=True)
    feats = jnp.concatenate([bot[:, None, :], emb], axis=1)
    n = feats.shape[1]
    rows, cols = np.tril_indices(n, k=-1)
    inter = gram(feats, num.round_operands)[:, rows, cols]
    top_in = jnp.concatenate([bot, inter], axis=-1)
    return _mlp(params["top"], top_in, num, relu_last=False)[:, 0]


def bce(z, y):
    """Mean binary cross-entropy of logits ``z`` against labels ``y``."""
    y = y.astype(z.dtype)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def loss(cfg: dict, params, dense, bases, labels, num: Numerics):
    return bce(logits(cfg, params, dense, bases, num), labels)


def adagrad(params, v, grads, lr: float, eps: float):
    v = jax.tree.map(lambda vv, g: vv + g * g, v, grads)
    params = jax.tree.map(
        lambda p, g, vv: p - lr * g / (jnp.sqrt(vv) + eps), params, grads, v)
    return params, v


def _highest(fn):
    """``fn`` called under ``default_matmul_precision("highest")``, which
    holds where a product is traced at the call."""

    def call(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return call


def make_score(cfg: dict, num: Numerics):
    """(params, dense, bases) -> scores [B], jitted, at ``num``."""
    return _highest(jax.jit(lambda p, d, b: logits(cfg, p, d, b, num)))


def make_train_step(cfg: dict, num: Numerics, lr: float, eps: float):
    """(params, v, dense, bases, labels) -> (params, v, loss, grads),
    jitted, at ``num``."""

    def step(params, v, dense, bases, labels):
        value, grads = jax.value_and_grad(
            lambda p: loss(cfg, p, dense, bases, labels, num))(params)
        params, v = adagrad(params, v, grads, lr, eps)
        return params, v, value, grads

    return _highest(jax.jit(step))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def key_from_seed(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(cfg: dict, seed: int) -> dict:
    """Float32 weights in the program's layout, made on the device in one
    jitted call: the ROBE array ~ N(0, robe_init_scale²); each linear
    layer's weight He-uniform over its fan-in, its bias zero."""
    d = cfg["embed_dim"]
    n_pairs = (len(cfg["vocab_sizes"]) + 1) * len(cfg["vocab_sizes"]) // 2
    bot = [cfg["n_dense"]] + list(cfg["bot_mlp"])
    top = [cfg["bot_mlp"][-1] + n_pairs] + list(cfg["top_mlp"])
    if cfg["bot_mlp"][-1] != d:
        raise ValueError("the bottom MLP must end at embed_dim")

    def mlp(key, dims):
        out = []
        for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
            lim = float(np.sqrt(6.0 / dims[i]))
            out.append({"w": jax.random.uniform(k, (dims[i], dims[i + 1]),
                                                jnp.float32, -lim, lim),
                        "b": jnp.zeros((dims[i + 1],), jnp.float32)})
        return out

    def make(key):
        km, kb, kt = jax.random.split(key, 3)
        mem = (jax.random.normal(km, (cfg["robe_size"],), jnp.float32)
               * cfg["robe_init_scale"])
        return {"embedding": {"memory": mem}, "bot": mlp(kb, bot),
                "top": mlp(kt, top)}

    return jax.jit(make)(key_from_seed(seed))
