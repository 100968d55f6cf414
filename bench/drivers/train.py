"""Training: ``build_train_step`` driven by ``train_loop.run``.

Traffic keys: ``batch`` (samples per step), ``pool`` (distinct batches made
from the seed in set-up; step ``s`` trains on batch ``s mod pool``),
``zipf``, ``check_steps`` (the first steps, which the check compares).

Set-up builds one train state and one compiled step, drives them through
the first ``check_steps`` steps with the window's own loop and feed, and
reads from them what the check compares: each step's loss, the first
gradient's norm per leaf (from adagrad's accumulator after one step, which
holds g²) and the norm of each leaf's change over those steps.  The window
then goes on with that same state, one ``run`` call per step, until
``--seconds`` is spent.

Check (each gap relative to the reference):

* ``loss_gap`` — largest |loss − reference loss| / |reference loss| over
  the first steps;
* ``grad_gap`` — over the leaves, largest |‖g‖ − ‖g_ref‖| of the first
  gradient, over the larger of ‖g_ref‖ of that leaf and of the median leaf;
* ``change_gap`` — the same for each leaf's change over the first steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (their change is round-off).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench.data import CtrStream
from bench.serving import check_program

#: a leaf whose first reference gradient is under this share of the
#: median leaf's is left out of ``change_gap``
FROZEN_SHARE = 1e-3


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _root_sums(tree):
    return [jnp.sqrt(jnp.sum(x.astype(jnp.float32)))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def norms(tree) -> np.ndarray:
    return np.array([float(x) for x in _norms(tree)], np.float64)


def root_sums(tree) -> np.ndarray:
    return np.array([float(x) for x in _root_sums(tree)], np.float64)


def change_norms(new, old) -> np.ndarray:
    return np.array([float(x) for x in _change_norms(new, old)], np.float64)


def setup(run) -> None:
    from repro.models.recsys import RecsysConfig, loss_fn
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_loop import (TrainConfig, build_train_step,
                                        init_state)
    from repro.train.train_loop import run as train_run

    cfg, tr = run.config, run.traffic
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
        with TraceAnnotation("step"):
            return compiled(state, batch)

    stream = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], tr["batch"],
                       tr["zipf"], run.seed)
    pool = [stream.batch_at(i) for i in range(tr["pool"])]
    run.phase("pool")

    def feed(step):
        with TraceAnnotation("batch_at"):
            return pool[step % len(pool)]

    params = run.reference.init_params(cfg, run.seed)
    start = jax.tree.map(jnp.copy, params)
    state = init_state(params, opt, tc)
    del params
    run.phase("weights")
    # the check's steps, through the window's own loop, step and feed
    rep = train_run(state, step_fn, feed, 1, tc)
    grad_norms = root_sums(rep.state["opt"]["v"])      # v = g² after one step
    run.phase("step 1 (compiles)")
    losses = list(rep.losses)
    rep = train_run(rep.state, step_fn, feed, tr["check_steps"], tc)
    losses += rep.losses
    change = change_norms(rep.state["params"], start)
    del start
    run.phase(f"steps 2-{tr['check_steps']}")
    run.state = {"state": rep.state, "step_fn": step_fn, "feed": feed,
                 "spec": rc.embedding_spec(),
                 "tc": tc, "run": train_run, "pool": pool,
                 "losses": losses, "grad_norms": grad_norms,
                 "change": change, "names": leaf_names(rep.state["params"])}


def window(run) -> None:
    st = run.state
    state, train_run = st["state"], st["run"]
    done = int(state["step"])
    steps, nan_events = 0, 0
    run.setup_done()
    run.start_trace()
    t0 = time.perf_counter()
    while True:
        rep = train_run(state, st["step_fn"], st["feed"], done + steps + 1,
                        st["tc"])
        state = rep.state
        steps += 1
        nan_events += rep.nan_events
        t = time.perf_counter() - t0
        if t >= run.seconds:
            break
    run.stop_trace()
    st["state"] = state
    b = run.traffic["batch"]
    run.record.update(samples=steps * b, elapsed_s=t, steps=steps,
                      attempted=steps, failed=nan_events)


def release(run) -> None:
    run.state.pop("state", None)


def reference_readings(run, numerics=None) -> dict:
    """The reference's losses, first-gradient norms and change norms over
    the check's steps, on the same batches, from weights remade from the
    seed."""
    ref, cfg, tr = run.reference, run.config, run.traffic
    num = numerics or run.numerics()
    o = cfg["optimizer"]
    params = ref.cast(ref.init_params(cfg, run.seed), num)
    start = jax.tree.map(jnp.copy, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = ref.make_train_step(cfg, num, o["lr"], o["eps"])
    losses, grad_norms = [], None
    for s in range(tr["check_steps"]):
        b = run.state["pool"][s]
        params, v, loss, grads = step(
            params, v, jnp.asarray(b["dense"]),
            jnp.asarray(ref.block_bases(cfg, b["sparse"])),
            jnp.asarray(b["label"]))
        losses.append(float(loss))
        if s == 0:
            grad_norms = norms(grads)
        del grads
    return {"losses": losses, "grad_norms": grad_norms,
            "change": change_norms(params, start)}


def gaps(prog: dict, ref: dict) -> dict:
    """The three gaps of the program's readings against the reference's."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape:
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g, gr = prog["grad_norms"], ref["grad_norms"]
    grad_gap = float(np.max(np.abs(g - gr)
                            / np.maximum(gr, np.median(gr))))
    keep = gr >= FROZEN_SHARE * np.median(gr)
    c, cr = prog["change"][keep], ref["change"][keep]
    change_gap = float(np.max(np.abs(c - cr)
                              / np.maximum(cr, np.median(cr))))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def check(run) -> dict:
    st = run.state
    prog = {"losses": st["losses"], "grad_norms": st["grad_norms"],
            "change": st["change"]}
    ref = reference_readings(run)
    got = gaps(prog, ref)
    for i, name in enumerate(st["names"]):
        run.log(f"  leaf {name}: |g| {prog['grad_norms'][i]:.9g} ref "
                f"{ref['grad_norms'][i]:.9g}; |change| "
                f"{prog['change'][i]:.9g} ref {ref['change'][i]:.9g}")
    run.log(f"  losses {prog['losses']} ref {ref['losses']}")
    return {k: {"value": v, "limit": run.limits[k]} for k, v in got.items()}
