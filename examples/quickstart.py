"""Quickstart: train a DLRM whose embedding tables are ONE ROBE array.

Runs on a single CPU in ~a minute.  Shows the paper's core loop:
  * 1000× fewer embedding parameters (one shared hashed array),
  * same training API as the full model (swap ``embedding="full"``, or any
    registered backend — "hashed", "tt"; see
    examples/embedding_backend_sweep.py for the four-substrate sweep),
  * quality tracked with AUC on a held-out slice.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
from repro.models.recsys import RecsysConfig, forward, init_params, loss_fn
from repro.train.metrics import auc
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)

VOCABS = (40_000, 10_000, 60_000, 5_000)        # 115k rows × 16 = 1.84M params


def main():
    cfg = RecsysConfig(
        name="quickstart", arch="dlrm", n_dense=4,
        bot_mlp=(32, 16), top_mlp=(32, 1), embed_dim=16,
        vocab_sizes=VOCABS,
        embedding="robe",                        # the paper's technique
        robe_size=sum(VOCABS) * 16 // 100,       # 100× (scale-consistent:
        robe_block=32)                           # 115k rows vs CriteoTB's 800M
    spec = cfg.embedding_spec()
    print(f"full tables would be {spec.total_rows * spec.dim:,} params; "
          f"ROBE array is {spec.param_count:,} "
          f"({spec.compression:.0f}x compression)")

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.08))
    tc = TrainConfig(checkpoint_every=10**9, log_every=20)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    state = init_state(params, opt, tc)
    stream = CtrStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=4,
                                     batch_size=1024))
    rep = run(state, step_fn, stream.batch_at, 400, tc)
    state = rep.state
    print(f"loss {rep.losses[0]:.4f} -> {rep.losses[-1]:.4f} "
          f"over {rep.steps_done} steps")

    scores, labels = [], []
    fwd = jax.jit(lambda p, b: forward(p, cfg, b))
    for s in range(5000, 5008):
        b = stream.batch_at(s)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        scores.append(np.asarray(fwd(state["params"], jb)))
        labels.append(b["label"])
    print(f"held-out AUC: {auc(np.concatenate(labels), np.concatenate(scores)):.4f}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
