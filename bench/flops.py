"""Operations and bytes counted from shapes, never from the program.

* ``dlrm_forward_flops`` — multiply-adds of one sample's forward pass,
  times two: every linear layer (``in × out``), and the dot interaction's
  strictly lower triangle (``pairs × d``).  Biases, ReLUs and the lookup's
  hashing are not counted.
* ``lookup_bytes`` — what a lookup of ``batch`` rows has to move whatever
  implements it: each of the B·F rows of d float32 read once and written
  once, and the B·F int32 ids read.
"""

from __future__ import annotations


def dlrm_forward_flops(cfg: dict) -> int:
    f, d = len(cfg["vocab_sizes"]), cfg["embed_dim"]
    pairs = (f + 1) * f // 2
    bot = [cfg["n_dense"]] + list(cfg["bot_mlp"])
    top = [cfg["bot_mlp"][-1] + pairs] + list(cfg["top_mlp"])
    macs = sum(a * b for a, b in zip(bot, bot[1:]))
    macs += pairs * d
    macs += sum(a * b for a, b in zip(top, top[1:]))
    return 2 * macs


def lookup_bytes(cfg: dict, batch: int) -> int:
    rows = batch * len(cfg["vocab_sizes"])
    return 2 * rows * cfg["embed_dim"] * 4 + rows * 4
