"""Data-parallel training over the cell's chips: ``build_train_step``
driven by ``train_loop.run`` under a ``DistContext``.

Traffic keys: ``train``'s; ``batch`` is the global batch, split evenly over
the cell's ``chips``.  The mesh is ``launch.mesh.make_mesh`` over the
first ``chips`` devices (``data`` × a one-wide ``model``, ``Auto`` axes).
The train state (ROBE array, MLPs, adagrad's accumulator) is replicated on
every chip and each batch is put on the chips sharded over ``data`` by the
feed, so the step computes each chip's share of the batch and the
compiler all-reduces the gradients; the embedding lookups stay local.

Set-up is ``train``'s, placed on the mesh: one state and one compiled
step, driven through the first ``check_steps`` steps with the window's own
loop and feed, whose losses, first-gradient norms and change norms the
check compares.  The window, the release and the check (against the
reference over the global batch, on one chip) are ``train``'s own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench.data import CtrStream
from bench.harness import load_module
from bench.serving import check_program

train = load_module("drivers", "train.py")
window, release, check = train.window, train.release, train.check


def setup(run) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.api import DistContext, default_rules, use
    from repro.launch.mesh import make_mesh
    from repro.models.recsys import RecsysConfig, loss_fn
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_loop import (TrainConfig, build_train_step,
                                        init_state)
    from repro.train.train_loop import run as train_run

    # the end-to-end readers know a training record by the name of the
    # driver whose window this is
    run.traffic = dict(run.traffic, driver="train")
    cfg, tr = run.config, run.traffic
    chips = run.cell["chips"]
    if tr["batch"] % chips:
        raise ValueError(f"batch {tr['batch']} does not split over "
                         f"{chips} chips")
    mesh = make_mesh((chips, 1), ("data", "model"),
                     devices=jax.devices()[:chips])
    ctx = DistContext(mesh=mesh, rules=default_rules())
    replicated = NamedSharding(mesh, P())
    by_data = NamedSharding(mesh, P("data"))

    rc = RecsysConfig(name=cfg["name"], arch="dlrm",
                      vocab_sizes=tuple(cfg["vocab_sizes"]),
                      embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
                      bot_mlp=tuple(cfg["bot_mlp"]),
                      top_mlp=tuple(cfg["top_mlp"]),
                      embedding=cfg["embedding"], robe_size=cfg["robe_size"],
                      robe_block=cfg["robe_block"])
    check_program(rc, cfg)
    o = cfg["optimizer"]
    opt = make_optimizer(OptimizerConfig(kind=o["kind"], lr=o["lr"],
                                         eps=o["eps"]))
    tc = TrainConfig(max_restarts=0)        # a failure ends the run
    compiled = build_train_step(lambda p, b: loss_fn(p, rc, b), opt, tc)

    def step_fn(state, batch):
        with use(ctx), TraceAnnotation("step"):
            return compiled(state, batch)

    stream = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], tr["batch"],
                       tr["zipf"], run.seed)
    pool = [stream.batch_at(i) for i in range(tr["pool"])]
    run.phase("pool")

    def feed(step):
        with TraceAnnotation("batch_at"):
            return jax.device_put(pool[step % len(pool)], by_data)

    params = jax.device_put(run.reference.init_params(cfg, run.seed),
                            replicated)
    start = jax.tree.map(jnp.copy, params)
    state = jax.device_put(init_state(params, opt, tc), replicated)
    del params
    run.phase("weights")
    # the check's steps, through the window's own loop, step and feed
    rep = train_run(state, step_fn, feed, 1, tc)
    grad_norms = train.root_sums(rep.state["opt"]["v"])   # v = g² after 1
    run.phase("step 1 (compiles)")
    losses = list(rep.losses)
    rep = train_run(rep.state, step_fn, feed, tr["check_steps"], tc)
    losses += rep.losses
    change = train.change_norms(rep.state["params"], start)
    del start
    run.phase(f"steps 2-{tr['check_steps']}")
    run.state = {"state": rep.state, "step_fn": step_fn, "feed": feed,
                 "tc": tc, "run": train_run, "pool": pool,
                 "losses": losses, "grad_norms": grad_norms,
                 "change": change,
                 "names": train.leaf_names(rep.state["params"])}
