"""RecSys family: DLRM, DLRM-DCNv2, AutoInt, xDeepFM, DeepFM, DCN,
FiBiNET, Two-Tower.

All share the embedding front-end (``EmbeddingSpec`` + a registered
``EmbeddingBackend``: full / robe / hashed / tt — the paper's comparison
axis as a pluggable substrate) and differ in the interaction op.
Batch layout: dense features [B, n_dense] float, sparse ids [B, F] int32
— or, for multi-hot fields (``RecsysConfig.bag_sizes``), [B, ΣL] id
columns, field ``f``'s ``bag_sizes[f]`` ids side by side, pooled by sum.

DLRM-DCNv2 (the MLPerf recommendation model; Wang et al., arXiv:2008.13535)
keeps DLRM's bottom and top MLPs and replaces the dot interaction with
``cross_layers`` low-rank cross layers over ``x0 = [bottom output,
flattened pooled embeddings]``.

Outputs are logits [B] (CTR models) or (user_vec, item_vec) (two-tower).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.robe import RobeSpec
from repro.dist import api as dist
from repro.nn.core import dense_apply, dense_init, mlp_apply, mlp_init
from repro.nn.embeddings import EmbeddingSpec, embedding_init, \
    embedding_lookup, embedding_lookup_dist, get_backend, pool_bags
from repro.nn.interactions import (autoint_layer_apply, autoint_layer_init,
                                   bilinear_apply, bilinear_init, cin_apply,
                                   cin_init, cross_net_apply, cross_net_init,
                                   dot_interaction_op, fm_interaction,
                                   low_rank_cross_apply, low_rank_cross_init,
                                   senet_apply, senet_init)


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    arch: str                        # dlrm|dlrm_dcnv2|autoint|xdeepfm|deepfm|
    #   dcn|fibinet|two_tower
    vocab_sizes: Tuple[int, ...]
    embed_dim: int
    n_dense: int = 0
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    dnn: Tuple[int, ...] = ()        # deep branch (deepfm/xdeepfm/dcn/…)
    cin_layers: Tuple[int, ...] = ()
    cross_layers: int = 0
    cross_rank: int = 0              # dlrm_dcnv2: rank of each cross layer
    attn_layers: int = 0
    attn_dim: int = 0
    attn_heads: int = 0
    tower_mlp: Tuple[int, ...] = ()  # two-tower
    n_user_fields: int = 0           # two-tower: first k fields are user side
    # embedding substrate — any registered EmbeddingBackend name
    embedding: str = "robe"          # "full" | "robe" | "hashed" | "tt"
    robe_size: int = 0
    robe_block: int = 32
    robe_shard_model: bool = False   # ZeRO-3 ROBE: array sharded over model,
    # all-gathered per step (arrays beyond a replica's HBM)
    hashed_buckets: int = 0          # QR remainder buckets (0 = auto)
    tt_rank: int = 0                 # tensor-train core rank (0 = default)
    use_kernel: bool = False
    full_table_shard: str = "model"  # "model" | "2d" (rows over ALL devices;
    # kills the data-axis dense table-grad all-reduce — §Perf iteration)
    compute_dtype: object = jnp.float32
    bag_sizes: Tuple[int, ...] = ()  # multi-hot ids per field, summed
    #   within the field; () = one id per field

    def embedding_spec(self) -> EmbeddingSpec:
        robe = None
        if self.robe_size > 0:
            robe = RobeSpec(size=self.robe_size, block_size=self.robe_block,
                            seed=11)
        placement = "default"
        if self.robe_shard_model:
            placement = "model"
        elif self.full_table_shard == "2d":
            placement = "2d"
        return EmbeddingSpec(vocab_sizes=self.vocab_sizes,
                             dim=self.embed_dim, kind=self.embedding,
                             robe=robe, use_kernel=self.use_kernel,
                             placement=placement,
                             hashed_buckets=self.hashed_buckets,
                             tt_rank=self.tt_rank, bag_sizes=self.bag_sizes)

    @property
    def n_fields(self) -> int:
        return len(self.vocab_sizes)

    @property
    def n_columns(self) -> int:
        """Id columns of a batch's ``sparse``: ΣL, or one per field."""
        return sum(self.bag_sizes) or self.n_fields


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg: RecsysConfig) -> dict:
    ks = jax.random.split(key, 10)
    spec = cfg.embedding_spec()
    # pad the concatenated table so it row-shards evenly on any mesh ≤ 512
    p: dict = {"embedding": embedding_init(ks[0], spec, pad_rows_to=512)}
    f, d = cfg.n_fields, cfg.embed_dim
    a = cfg.arch
    if a == "dlrm":
        p["bot"] = mlp_init(ks[1], (cfg.n_dense,) + cfg.bot_mlp)
        n_pairs = (f + 1) * f // 2          # F embeddings + bottom output
        p["top"] = mlp_init(ks[2], (cfg.bot_mlp[-1] + n_pairs,) + cfg.top_mlp)
    elif a == "dlrm_dcnv2":
        p["bot"] = mlp_init(ks[1], (cfg.n_dense,) + cfg.bot_mlp)
        x0 = cfg.bot_mlp[-1] + f * d
        p["cross"] = low_rank_cross_init(ks[2], x0, cfg.cross_rank,
                                         cfg.cross_layers)
        p["top"] = mlp_init(ks[3], (x0,) + cfg.top_mlp)
    elif a == "autoint":
        p["attn"] = [autoint_layer_init(
            jax.random.fold_in(ks[1], i),
            d if i == 0 else cfg.attn_dim * cfg.attn_heads,
            cfg.attn_dim, cfg.attn_heads) for i in range(cfg.attn_layers)]
        p["out"] = dense_init(ks[2], f * cfg.attn_dim * cfg.attn_heads, 1)
    elif a == "xdeepfm":
        p["cin"] = cin_init(ks[1], f, cfg.cin_layers)
        p["dnn"] = mlp_init(ks[2], (f * d,) + cfg.dnn + (1,))
        p["cin_out"] = dense_init(ks[3], sum(cfg.cin_layers), 1)
        p["linear"] = dense_init(ks[4], f * d, 1)
    elif a == "deepfm":
        p["dnn"] = mlp_init(ks[1], (f * d,) + cfg.dnn + (1,))
        p["linear"] = dense_init(ks[2], f * d, 1)
    elif a == "dcn":
        p["cross"] = cross_net_init(ks[1], f * d, cfg.cross_layers)
        p["dnn"] = mlp_init(ks[2], (f * d,) + cfg.dnn)
        p["out"] = dense_init(ks[3], f * d + cfg.dnn[-1], 1)
    elif a == "fibinet":
        p["senet"] = senet_init(ks[1], f)
        p["bilinear"] = bilinear_init(ks[2], f, d)
        p["bilinear2"] = bilinear_init(ks[3], f, d)
        n_bi = f * (f - 1) // 2 * d
        p["dnn"] = mlp_init(ks[4], (2 * n_bi,) + cfg.dnn + (1,))
    elif a == "two_tower":
        in_u = cfg.n_user_fields * d
        in_i = (f - cfg.n_user_fields) * d
        p["user"] = mlp_init(ks[1], (in_u,) + cfg.tower_mlp)
        p["item"] = mlp_init(ks[2], (in_i,) + cfg.tower_mlp)
    else:
        raise ValueError(f"unknown recsys arch {a}")
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed(params, cfg: RecsysConfig, sparse_ids: jnp.ndarray) -> jnp.ndarray:
    # the substrate owns its distributed lookup (shard_map bodies, batch
    # layout, collectives) — see repro.nn.embedding_backends
    spec = cfg.embedding_spec()
    with jax.named_scope("embedding"):
        emb = embedding_lookup_dist(params["embedding"], spec, sparse_ids,
                                    compute_dtype=cfg.compute_dtype)
        return emb.astype(cfg.compute_dtype)


def _batch_emb(params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """[B, F, dim] field embeddings for ``batch`` — precomputed or looked
    up.

    A batch carrying ``"emb"`` bypasses the substrate lookup entirely:
    the serving tier's hot-row cache (``serve/hot_cache.py``) gathers the
    backend's own rows on the host (``cacheable_rows`` contract, bit-
    identical to the device gather) and injects them here, so cached and
    uncached scores agree to the bit.
    """
    emb = batch.get("emb")
    if emb is not None:
        with jax.named_scope("embedding"):
            emb = pool_bags(jnp.asarray(emb), cfg.embedding_spec())
            return emb.astype(cfg.compute_dtype)
    return _embed(params, cfg, batch["sparse"])


def _bottom(params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    with jax.named_scope("bottom_mlp"):
        dense = batch["dense"].astype(cfg.compute_dtype)
        return mlp_apply(params["bot"], dense, final_act=jax.nn.relu)


def forward(params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """batch: {"dense": [B,n_dense], "sparse": [B,F]} -> logits [B].

    A batch may carry precomputed ``"emb"`` [B, F, dim] instead of (or
    alongside) ``"sparse"`` — the serving tier's hot-row cache path
    (``serve/hot_cache.py``); it takes precedence over the substrate
    lookup.

    Each DLRM layer runs under its own ``jax.named_scope`` (``embedding``,
    ``bottom_mlp``, ``interaction``, ``top_mlp``), so a profile names the
    layer of every device op, forward and backward; DLRM-DCNv2's cross
    layers run under ``interaction/cross``, its pooling under
    ``embedding/bag_pool``.
    """
    a = cfg.arch
    if a == "dlrm_dcnv2":
        bot = _bottom(params, cfg, batch)
        emb = _batch_emb(params, cfg, batch)
        with jax.named_scope("interaction"):
            x0 = jnp.concatenate([bot, emb.reshape(emb.shape[0], -1)], -1)
            with jax.named_scope("cross"):
                x = low_rank_cross_apply(params["cross"], x0)
        with jax.named_scope("top_mlp"):
            return mlp_apply(params["top"], x)[:, 0]
    if a == "dlrm":
        bot = _bottom(params, cfg, batch)
        emb = _batch_emb(params, cfg, batch)
        with jax.named_scope("interaction"):
            # [B, (F+1)·F/2] triangle of the gram of [bot; embeddings]
            feats = jnp.concatenate([bot[:, None, :], emb], axis=1)
            inter = dot_interaction_op(feats, use_kernel=cfg.use_kernel)
        with jax.named_scope("top_mlp"):
            top_in = jnp.concatenate([bot, inter], axis=-1)
            return mlp_apply(params["top"], top_in)[:, 0]
    emb = _batch_emb(params, cfg, batch)             # [B,F,D]
    b, f, d = emb.shape
    flat = emb.reshape(b, f * d)
    if a == "autoint":
        x = emb
        for layer in params["attn"]:
            x = autoint_layer_apply(layer, x, cfg.attn_heads)
        return dense_apply(params["out"], x.reshape(b, -1))[:, 0]
    if a == "xdeepfm":
        cin = cin_apply(params["cin"], emb)
        return (dense_apply(params["cin_out"], cin)[:, 0]
                + mlp_apply(params["dnn"], flat)[:, 0]
                + dense_apply(params["linear"], flat)[:, 0])
    if a == "deepfm":
        return (fm_interaction(emb)[:, 0]
                + mlp_apply(params["dnn"], flat)[:, 0]
                + dense_apply(params["linear"], flat)[:, 0])
    if a == "dcn":
        cross = cross_net_apply(params["cross"], flat)
        deep = mlp_apply(params["dnn"], flat, final_act=jax.nn.relu)
        return dense_apply(params["out"],
                           jnp.concatenate([cross, deep], -1))[:, 0]
    if a == "fibinet":
        se = senet_apply(params["senet"], emb)
        bi1 = bilinear_apply(params["bilinear"], emb)
        bi2 = bilinear_apply(params["bilinear2"], se)
        x = jnp.concatenate([bi1, bi2], axis=-1)
        return mlp_apply(params["dnn"], x)[:, 0]
    raise ValueError(f"forward undefined for {a}")


def tower_vectors(params, cfg: RecsysConfig, batch: dict
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """two-tower: -> (user [B,D], item [B,D]), L2-normalized."""
    emb = _embed(params, cfg, batch["sparse"])
    b = emb.shape[0]
    ku = cfg.n_user_fields
    u = mlp_apply(params["user"], emb[:, :ku].reshape(b, -1))
    v = mlp_apply(params["item"], emb[:, ku:].reshape(b, -1))
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True).clip(1e-6)
    v = v / jnp.linalg.norm(v, axis=-1, keepdims=True).clip(1e-6)
    return u, v


def loss_fn(params, cfg: RecsysConfig, batch: dict) -> Tuple[jnp.ndarray,
                                                             dict]:
    if cfg.arch == "two_tower":
        u, v = tower_vectors(params, cfg, batch)
        logits = (u @ v.T) * 20.0               # in-batch sampled softmax
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.diag(logits)
        loss = (lse - gold).mean()
        return loss, {"loss": loss}
    logits = forward(params, cfg, batch)
    y = batch["label"].astype(jnp.float32)
    ce = jnp.mean(jnp.maximum(logits, 0) - logits * y
                  + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return ce, {"logloss": ce}


def make_project_fn(cfg: RecsysConfig):
    """Post-optimizer projection for the model's params, or None.

    Backends whose stored parameters are not what the math sees (``qrobe``:
    int8 codes behind a learned dequant) expose ``EmbeddingBackend.
    project``; this lifts it from the embedding subtree to the full param
    dict so ``build_train_step(project=...)`` (and the launch cells' inline
    step closures) can apply it after every update.  Float substrates
    return None and train loops skip the hook entirely.
    """
    spec = cfg.embedding_spec()
    backend = get_backend(spec.kind)
    if backend.project is None:
        return None

    def project(params):
        return dict(params,
                    embedding=backend.project(params["embedding"], spec))
    return project


def serve_scores(params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """Online/bulk inference: logits (CTR) or retrieval scores."""
    if cfg.arch == "two_tower":
        # retrieval: one (or few) queries against a candidate id set
        emb_spec = cfg.embedding_spec()
        u, _ = tower_vectors(params, cfg, batch)
        item_fields = tuple(range(cfg.n_user_fields, cfg.n_fields))
        cand = embedding_lookup(
            params["embedding"], emb_spec,
            batch["cand_sparse"].reshape(-1, len(item_fields)),
            fields=item_fields)
        n = cand.shape[0]
        cand = dist.shard(cand, "candidates", None, None)
        vi = mlp_apply(params["item"],
                       cand.astype(cfg.compute_dtype).reshape(n, -1))
        vi = vi / jnp.linalg.norm(vi, axis=-1, keepdims=True).clip(1e-6)
        return (u @ vi.T)                        # [B, n_candidates]
    return forward(params, cfg, batch)
