"""End-to-end driver: train a ~100M-parameter DLRM for a few hundred steps
with the full production substrate — ROBE-compressed embeddings, Adagrad,
async checkpointing, fault-tolerant resume, held-out AUC — on one CPU.

The *logical* model is ~100M parameters (6.2M embedding rows × 16); the
trained state is the 100k-slot ROBE array + dense MLPs (1000×).

    PYTHONPATH=src python examples/train_dlrm_robe.py [--steps 300]
"""

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
from repro.models.recsys import RecsysConfig, forward, init_params, loss_fn
from repro.train.metrics import StreamingAuc
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)

# ≈ 6.2M rows × 16 dims ≈ 100M logical parameters
VOCABS = (2_500_000, 1_500_000, 1_200_000, 600_000, 300_000, 100_000)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--inject-fault", type=int, default=None,
                    help="simulate a node failure at this step")
    args = ap.parse_args()

    n_logical = sum(VOCABS) * 16
    cfg = RecsysConfig(
        name="dlrm-100m", arch="dlrm", n_dense=13,
        bot_mlp=(128, 64, 16), top_mlp=(128, 64, 1), embed_dim=16,
        vocab_sizes=VOCABS, embedding="robe",
        robe_size=n_logical // 1000, robe_block=32)
    print(f"logical model: {n_logical/1e6:.0f}M embedding params; "
          f"ROBE array: {cfg.robe_size/1e3:.0f}k slots (1000x)")

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.05))
    tc = TrainConfig(checkpoint_every=50, keep_last=2, max_restarts=2)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    state = init_state(params, opt, tc)
    stream = CtrStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=13,
                                     batch_size=args.batch))

    ckpt_dir = args.ckpt or os.path.join(tempfile.gettempdir(),
                                         "robe_dlrm_100m")
    rep = run(state, step_fn, stream.batch_at, args.steps, tc,
              ckpt_dir=ckpt_dir, inject_fault_at=args.inject_fault)
    state = rep.state
    print(f"steps {rep.steps_done}  loss {rep.losses[0]:.4f} -> "
          f"{rep.final_loss:.4f}  restarts={rep.restarts} "
          f"nan_events={rep.nan_events} stragglers={rep.straggler_steps}")

    sa = StreamingAuc()
    fwd = jax.jit(lambda p, b: forward(p, cfg, b))
    for s in range(50_000, 50_010):
        b = stream.batch_at(s)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        sa.update(b["label"], np.asarray(fwd(state["params"], jb)))
    print(f"held-out streaming AUC: {sa.value():.4f}")
    print(f"checkpoints in {ckpt_dir} (resume by re-running)")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
