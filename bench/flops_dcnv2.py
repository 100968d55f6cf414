"""Operations and bytes of DLRM-DCNv2, counted from shapes, never from the
program.

* ``dcnv2_forward_flops`` — multiply-adds of one sample's forward pass,
  times two: every MLP layer (``in × out``) and each low-rank cross layer's
  two products (``N × r`` and ``r × N``, N = bottom output + F·d).  Biases,
  ReLUs, the cross layers' elementwise terms, the pooling and the lookup's
  hashing are not counted.
* ``pooled_lookup_bytes`` — what a pooled multi-hot lookup of ``batch``
  rows has to move whatever implements it: each of the B·ΣL gathered rows
  of d float32 read once, the B·F pooled rows of d float32 written once,
  and the B·ΣL int32 ids read.
"""

from __future__ import annotations


def dcnv2_forward_flops(cfg: dict) -> int:
    f, d = len(cfg["vocab_sizes"]), cfg["embed_dim"]
    n_x0 = cfg["bot_mlp"][-1] + f * d
    bot = [cfg["n_dense"]] + list(cfg["bot_mlp"])
    top = [n_x0] + list(cfg["top_mlp"])
    macs = sum(a * b for a, b in zip(bot, bot[1:]))
    macs += cfg["cross_layers"] * 2 * n_x0 * cfg["cross_rank"]
    macs += sum(a * b for a, b in zip(top, top[1:]))
    return 2 * macs


def pooled_lookup_bytes(cfg: dict, batch: int) -> int:
    ids = batch * sum(cfg["multi_hot_sizes"])
    pooled = batch * len(cfg["vocab_sizes"])
    return (ids + pooled) * cfg["embed_dim"] * 4 + ids * 4
