"""Sweep all four embedding substrates through the SAME DLRM.

The point of the ``EmbeddingBackend`` protocol: one model, one train loop,
four substrates — the paper's full-vs-ROBE comparison plus the community
baselines (QR hashing, tensor-train), selected by a config string.

    PYTHONPATH=src python examples/embedding_backend_sweep.py [--steps 200]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
from repro.models.recsys import (RecsysConfig, forward, init_params,
                                 loss_fn, make_project_fn)
from repro.nn.embeddings import backend_names
from repro.train.metrics import auc
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)

VOCABS = (20_000, 8_000, 30_000, 2_000)
DIM = 8


def train_one(kind: str, steps: int) -> dict:
    cfg = RecsysConfig(
        name=f"sweep-{kind}", arch="dlrm", n_dense=4, bot_mlp=(32, 8),
        top_mlp=(16, 1), embed_dim=DIM, vocab_sizes=VOCABS, embedding=kind,
        robe_size=max(512, sum(VOCABS) * DIM // 50), robe_block=8,
        tt_rank=8)
    spec = cfg.embedding_spec()
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.1))
    tc = TrainConfig(checkpoint_every=10 ** 9)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc,
                               project=make_project_fn(cfg))
    stream = CtrStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=4,
                                     batch_size=1024))
    rep = run(init_state(params, opt, tc), step_fn, stream.batch_at, steps,
              tc)
    fwd = jax.jit(lambda p, b: forward(p, cfg, b))
    scores, labels = [], []
    for s in range(10_000, 10_008):
        b = stream.batch_at(s)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        scores.append(np.asarray(fwd(rep.state["params"], jb)))
        labels.append(b["label"])
    return {"backend": kind,
            "emb_params": int(spec.param_count),
            "compression": round(float(spec.compression), 1),
            "final_loss": round(float(rep.final_loss), 4),
            "auc": round(auc(np.concatenate(labels),
                             np.concatenate(scores)), 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()
    print(f"{'backend':8s} {'emb params':>11s} {'compress':>9s} "
          f"{'loss':>8s} {'auc':>7s}")
    for kind in backend_names():
        r = train_one(kind, args.steps)
        print(f"{r['backend']:8s} {r['emb_params']:11,d} "
              f"{r['compression']:8.1f}x {r['final_loss']:8.4f} "
              f"{r['auc']:7.4f}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
