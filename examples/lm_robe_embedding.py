"""ROBE beyond recsys: compress an LM's token-embedding table.

Trains two small decoder-only LMs on the synthetic token stream — one with
a full [vocab, d] embedding, one with a ROBE array at 8× compression — and
shows both losses fall together (DESIGN.md §5 secondary applicability).

    PYTHONPATH=src python examples/lm_robe_embedding.py
"""

import jax
import jax.numpy as jnp

from repro.data.lm_data import LmDataConfig, LmStream
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)


def train(embedding: str, steps: int = 120):
    vocab, d = 2048, 64
    cfg = TransformerConfig(
        name=f"lm-{embedding}", n_layers=2, d_model=d, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab=vocab, q_chunk=0,
        embedding=embedding, robe_size=vocab * d // 8, robe_block=32,
        compute_dtype=jnp.float32, remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adam", lr=2e-3))
    tc = TrainConfig(checkpoint_every=10**9)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    state = init_state(params, opt, tc)
    stream = LmStream(LmDataConfig(vocab=vocab, seq_len=64, batch_size=16))
    rep = run(state, step_fn, stream.batch_at, steps, tc)
    n_emb = (cfg.robe_size if embedding == "robe" else vocab * d)
    print(f"{embedding:5s} embed_params={n_emb:8,d}  "
          f"loss {rep.losses[0]:.3f} -> {rep.final_loss:.3f}")
    return rep.final_loss


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    lf = train("full")
    lr = train("robe")
    print(f"gap (robe - full): {lr - lf:+.3f} nats at 8x compression")
