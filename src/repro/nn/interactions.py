"""Feature-interaction operators for the recsys family.

dot (DLRM), FM (DeepFM), CIN (xDeepFM), cross network (DCN), low-rank
cross network (DCN-V2, in DLRM-DCNv2), SENET + bilinear (FiBiNET),
multi-head self-attention over fields (AutoInt).  All take field
embeddings [B, F, D], but the cross networks, which take the flat [B, N].
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.ops import dot_interaction
from repro.nn.core import dense_apply, dense_init, he_uniform


# ---------------------------------------------------------------------------
# FM second-order term (DeepFM): ½((Σv)² − Σv²) summed over dim
# ---------------------------------------------------------------------------

def fm_interaction(feats: jnp.ndarray) -> jnp.ndarray:
    s = feats.sum(axis=1)                    # [B, D]
    s2 = (feats * feats).sum(axis=1)         # [B, D]
    return 0.5 * (s * s - s2).sum(axis=-1, keepdims=True)   # [B, 1]


# ---------------------------------------------------------------------------
# DCN cross network: x_{l+1} = x0 * (W x_l + b) + x_l
# ---------------------------------------------------------------------------

def cross_net_init(key, dim: int, n_layers: int) -> list:
    keys = jax.random.split(key, n_layers)
    return [dense_init(k, dim, dim, bias=True, scale=0.01) for k in keys]


def cross_net_apply(layers: list, x0: jnp.ndarray) -> jnp.ndarray:
    x = x0
    for p in layers:
        x = x0 * dense_apply(p, x) + x
    return x


# ---------------------------------------------------------------------------
# DCN-V2 low-rank cross network (Wang et al. 2020, arXiv:2008.13535, Eq. 2
# with W = U·V): x_{l+1} = x0 ⊙ (W_l (V_l x_l) + b_l) + x_l, V_l: N -> r
# without bias, W_l: r -> N with bias
# ---------------------------------------------------------------------------

def low_rank_cross_init(key, dim: int, rank: int, n_layers: int) -> list:
    """Per layer {"v": [dim, rank], "w": [rank, dim]} He-uniform over
    their fan-in, {"b": [dim]} zero."""
    out = []
    for k in jax.random.split(key, n_layers):
        kv, kw = jax.random.split(k)
        out.append({"v": he_uniform(kv, (dim, rank)),
                    "w": he_uniform(kw, (rank, dim)),
                    "b": jnp.zeros((dim,), jnp.float32)})
    return out


def low_rank_cross_apply(layers: list, x0: jnp.ndarray) -> jnp.ndarray:
    """x0 [B, N] -> [B, N] through every layer."""
    x = x0
    for p in layers:
        low = x @ p["v"].astype(x.dtype)
        x = x0 * (low @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)) + x
    return x


# ---------------------------------------------------------------------------
# xDeepFM CIN: x^k[b,h,d] = Σ_ij W^k[h,i,j] x0[b,i,d] x^{k-1}[b,j,d]
# ---------------------------------------------------------------------------

def cin_init(key, n_fields: int, layer_sizes: Sequence[int]) -> list:
    params = []
    prev = n_fields
    for i, h in enumerate(layer_sizes):
        k = jax.random.fold_in(key, i)
        params.append({"w": jax.random.normal(k, (h, n_fields, prev),
                                              jnp.float32) * 0.01})
        prev = h
    return params


def cin_apply(params: list, x0: jnp.ndarray) -> jnp.ndarray:
    """x0 [B, F, D] -> [B, Σ_k H_k] (sum-pooled feature maps)."""
    xk = x0
    pooled = []
    for p in params:
        # z[b,i,j,d] contracted immediately — never materialize B,F,Fk,D
        xk = jnp.einsum("bid,bjd,hij->bhd", x0, xk, p["w"].astype(x0.dtype))
        pooled.append(xk.sum(axis=-1))       # [B, H]
    return jnp.concatenate(pooled, axis=-1)


# ---------------------------------------------------------------------------
# FiBiNET: SENET field re-weighting + bilinear interaction
# ---------------------------------------------------------------------------

def senet_init(key, n_fields: int, reduction: int = 3) -> dict:
    mid = max(1, n_fields // reduction)
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, n_fields, mid, bias=False),
            "w2": dense_init(k2, mid, n_fields, bias=False)}


def senet_apply(p: dict, feats: jnp.ndarray) -> jnp.ndarray:
    z = feats.mean(axis=-1)                            # [B, F]
    a = jax.nn.relu(dense_apply(p["w1"], z))
    a = jax.nn.relu(dense_apply(p["w2"], a))           # [B, F]
    return feats * a[..., None]


def bilinear_init(key, n_fields: int, dim: int) -> dict:
    # "field-all" bilinear: one shared [D, D]
    return {"w": jax.random.normal(key, (dim, dim), jnp.float32) * 0.01}


def bilinear_apply(p: dict, feats: jnp.ndarray) -> jnp.ndarray:
    b, f, d = feats.shape
    left = feats @ p["w"].astype(feats.dtype)          # [B, F, D]
    i, j = jnp.tril_indices(f, k=-1)
    return (left[:, i, :] * feats[:, j, :]).reshape(b, -1)


# ---------------------------------------------------------------------------
# AutoInt interacting layer: MHSA over fields with residual
# ---------------------------------------------------------------------------

def autoint_layer_init(key, d_in: int, d_attn: int, n_heads: int) -> dict:
    kq, kk, kv, kr = jax.random.split(key, 4)
    d_h = d_attn * n_heads
    return {"wq": dense_init(kq, d_in, d_h, bias=False),
            "wk": dense_init(kk, d_in, d_h, bias=False),
            "wv": dense_init(kv, d_in, d_h, bias=False),
            "wr": dense_init(kr, d_in, d_h, bias=False)}  # residual proj


def autoint_layer_apply(p: dict, x: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    b, f, _ = x.shape
    def split(t):
        return t.reshape(b, f, n_heads, -1).transpose(0, 2, 1, 3)
    q, k, v = split(dense_apply(p["wq"], x)), split(dense_apply(p["wk"], x)), \
        split(dense_apply(p["wv"], x))
    att = jax.nn.softmax(jnp.einsum("bhfd,bhgd->bhfg", q, k), axis=-1)
    o = jnp.einsum("bhfg,bhgd->bhfd", att, v).transpose(0, 2, 1, 3
                                                        ).reshape(b, f, -1)
    return jax.nn.relu(o + dense_apply(p["wr"], x))


def dot_interaction_op(feats: jnp.ndarray, self_interaction: bool = False,
                       use_kernel: bool = False) -> jnp.ndarray:
    return dot_interaction(feats, self_interaction, use_kernel)
