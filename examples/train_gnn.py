"""GatedGCN example: full-graph node classification AND sampled-minibatch
training with the CSR neighbor sampler (the `minibatch_lg` pattern).

    PYTHONPATH=src python examples/train_gnn.py
"""

import jax
import jax.numpy as jnp

from repro.data.graphs import (CsrGraph, GraphSpec, NeighborSampler,
                               SamplerConfig)
from repro.models.gatedgcn import GatedGCNConfig, forward, init_params, \
    loss_fn
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)


def full_graph():
    g = CsrGraph(GraphSpec(n_nodes=600, n_edges=3000, d_feat=16,
                           n_classes=6))
    cfg = GatedGCNConfig(name="fg", n_layers=4, d_hidden=32, d_feat=16,
                         n_classes=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adam", lr=3e-3))
    tc = TrainConfig(checkpoint_every=10**9)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    state = init_state(params, opt, tc)
    batch = g.full_batch()
    rep = run(state, step_fn, lambda s: batch, 60, tc)
    logits = forward(rep.state["params"], cfg,
                     {k: jnp.asarray(v) for k, v in batch.items()})
    acc = float((jnp.argmax(logits[0], -1) == batch["labels"][0]).mean())
    print(f"full-graph: loss {rep.losses[0]:.3f} -> {rep.final_loss:.3f}, "
          f"train acc {acc:.2%}")


def sampled_minibatch():
    g = CsrGraph(GraphSpec(n_nodes=5000, n_edges=40000, d_feat=16,
                           n_classes=6))
    sampler = NeighborSampler(g, SamplerConfig(batch_nodes=64,
                                               fanouts=(10, 5)))
    cfg = GatedGCNConfig(name="mb", n_layers=3, d_hidden=32, d_feat=16,
                         n_classes=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adam", lr=3e-3))
    tc = TrainConfig(checkpoint_every=10**9)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    state = init_state(params, opt, tc)
    rep = run(state, step_fn, sampler.sample, 60, tc)
    print(f"sampled minibatch (fanout 10-5, {sampler.max_nodes} padded "
          f"nodes): loss {rep.losses[0]:.3f} -> {rep.final_loss:.3f}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    full_graph()
    sampled_minibatch()
