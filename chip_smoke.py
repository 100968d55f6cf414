"""Bring-up check: the main serve and train paths on a TPU, at published widths.

    python chip_smoke.py             # one chip: serve dlrm-rm2, train dlrm-criteo-tb
    python chip_smoke.py --chips 4   # four chips: sharded full table, ZeRO-3 robe

One chip (the default):

* serve — dlrm-rm2 (26 CriteoTB fields, d=64, bottom MLP 512-256-64, top
  MLP 512-512-256-1) with its whole 13,067,813-slot ROBE array on the
  chip, through ``EmbeddingServer``: a few ``serve_p99`` batches of 512
  requests at Zipf 1.05, checked against ``models.recsys.forward`` in f32
  at ``Precision.HIGHEST``; then the same batches with ``use_kernel=True``
  (the Pallas DMA gather + dot-interaction kernels), checked against the
  same reference, then scored again with every matmul at HIGHEST — the
  precision of the kernels' own matmuls — and checked against the jnp
  path at HIGHEST; and each kernel checked alone against its reference;
* train — dlrm-criteo-tb (the paper's MLPerf model, d=128, a 26.1M-slot
  ROBE array) at ``train_batch`` 65,536 with adagrad, through
  ``build_train_step`` + ``run`` for a few steps: no restarts, no NaN
  events, every loss finite.

Four chips (``--chips 4``, this phase only): dlrm-rm2 ``serve_p99`` scored
with the ``full`` table row-sharded over a 4-device ``model`` axis,
checked against one-chip scoring of the same rows (``cacheable_rows``,
bit-exact) fed through the batch's ``"emb"`` key; and one ZeRO-3 ``robe``
train step (``robe_shard_model=True``) checked against the replicated
layout.

Weights are random, from ``--seed``.  Every phase prints compile seconds
apart from steady per-batch (per-step) wall time after
``block_until_ready``, its largest error against the stated tolerance and
the device's peak memory.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SERVE_BATCH = 512            # the registry's serve_p99 batch
TRAIN_BATCH = 65536          # the registry's train_batch
N_SERVE_BATCHES = 6
N_TRAIN_STEPS = 5
ZIPF = 1.05

#: scores at the default matmul precision (one bf16 pass per f32 matmul on
#: a TPU, relative error ~2^-8 per layer over 7 layers) against the f32
#: reference: |err| ≤ TOL_DEFAULT · max|reference|
TOL_DEFAULT = 3e-2
#: the same program on two layouts (sharded vs one chip, ZeRO-3 vs
#: replicated): only the order of f32 sums differs
TOL_LAYOUT = 1e-4
#: a kernel path against its jnp path, both at Precision.HIGHEST: only the
#: order of f32 sums differs
TOL_KERNEL = 1e-5
#: rows kept per field for the four-chip full table.  The whole CriteoTB
#: table (204M rows × 64 f32, 52 GB) is 13 GB per chip, but the compiled
#: serving program holds a relayout copy of each shard in 128-lane tiles,
#: 26 GB, past a v5e's 16 GB; at 10M rows per field (54.2M rows) a shard
#: is 3.5 GB and its copy 6.9 GB (compiled for v5e:2x2)
FULL_VOCAB_CAP = 10_000_000


class Phase:
    """Times one phase: compile seconds (from jax's own compile events)
    apart from steady wall time, and the checks it makes."""

    def __init__(self, name: str):
        import jax
        self.name = name
        self.compile_s = 0.0
        self.failures = []
        self._jax = jax

        def listen(event, secs, **_):
            if event.startswith("/jax/core/compile/"):
                self.compile_s += secs

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._listen)

    def check(self, what: str, err: float, tol: float):
        ok = bool(np.isfinite(err)) and err <= tol
        print(f"[{self.name}] {what}: max_err={err:.3e} tol={tol:.3e} "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failures.append(what)

    def require(self, what: str, ok: bool):
        print(f"[{self.name}] {what}: {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failures.append(what)

    def report(self):
        peak = (self._jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        print(f"[{self.name}] compile_s={self.compile_s:.3f} "
              f"peak_bytes_in_use={peak}", flush=True)


def timed(fn, *args):
    """(result, wall seconds) of one call, after block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1e-30, np.max(np.abs(want))))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_phase(seed: int) -> list:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
    from repro.kernels import ref
    from repro.kernels.ops import dot_interaction, robe_lookup
    from repro.models.recsys import forward
    from repro.serve.server import EmbeddingServer, ServerConfig

    ph = Phase("serve dlrm-rm2 robe")
    arch = get_arch("dlrm-rm2").make_config()
    cfg = ServerConfig(vocab_sizes=arch.vocab_sizes, embed_dim=arch.embed_dim,
                       n_dense=arch.n_dense, bot_mlp=arch.bot_mlp,
                       top_mlp=arch.top_mlp, backends=("robe",),
                       robe_block=arch.robe_block, cache_capacity=0,
                       seed=seed)
    server = EmbeddingServer(cfg)
    rc = server.recsys_config("robe")
    ph.require(f"ROBE array at registry width ({rc.robe_size} slots)",
               rc.robe_size == arch.robe_size)
    stream = CtrStream(CtrDataConfig(vocab_sizes=arch.vocab_sizes,
                                     n_dense=arch.n_dense,
                                     batch_size=SERVE_BATCH,
                                     zipf_exponent=ZIPF, seed=seed))
    batches = [stream.batch_at(s) for s in range(N_SERVE_BATCHES)]

    def serve_all(srv):
        # first batch compiles; the rest are steady state
        scores, walls = [], []
        for b in batches:
            s, dt = timed(srv.score, "robe", b)
            scores.append(s)
            walls.append(dt)
        return np.stack(scores), walls

    scores, walls = serve_all(server)
    print(f"[{ph.name}] batch={SERVE_BATCH} fields={len(arch.vocab_sizes)} "
          f"dim={arch.embed_dim} first_call_s={walls[0]:.3f} "
          f"steady_ms_per_batch={1e3 * np.median(walls[1:]):.3f} "
          f"(max {1e3 * max(walls[1:]):.3f})", flush=True)

    params = server.params("robe")
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, b: forward(p, rc, b))
        want = np.stack([np.asarray(fwd(params, {
            "dense": jnp.asarray(b["dense"]),
            "sparse": jnp.asarray(b["sparse"])})) for b in batches])
    ph.require("scores finite", bool(np.all(np.isfinite(scores))))
    ph.check("scores vs f32 forward (HIGHEST)", max_rel_err(scores, want),
             TOL_DEFAULT)

    kserver = EmbeddingServer(dataclasses.replace(cfg, use_kernel=True),
                              params={"robe": params})
    kscores, kwalls = serve_all(kserver)
    print(f"[{ph.name}] use_kernel=True first_call_s={kwalls[0]:.3f} "
          f"steady_ms_per_batch={1e3 * np.median(kwalls[1:]):.3f}",
          flush=True)
    ph.check("use_kernel=True scores vs f32 forward (HIGHEST)",
             max_rel_err(kscores, want), TOL_DEFAULT)
    # the kernels' matmuls run at HIGHEST: with the rest of the model there
    # too (a second compile), both paths compute at one precision
    with jax.default_matmul_precision("highest"):
        hscores, _ = serve_all(kserver)
    ph.check(f"use_kernel=True vs jnp path, both HIGHEST, "
             f"{N_SERVE_BATCHES} batches", max_rel_err(hscores, want),
             TOL_KERNEL)

    # each kernel alone against its jnp reference, every batch
    spec = rc.embedding_spec()
    mem = params["embedding"]["memory"]
    fields = tuple(range(spec.n_fields))
    gather_err = dot_err = 0.0
    for b in batches:
        ids = jnp.asarray(b["sparse"])
        got = robe_lookup(mem, ids, fields, spec.dim, spec.robe, True)
        emb = robe_lookup(mem, ids, fields, spec.dim, spec.robe, False)
        gather_err = max(gather_err, max_rel_err(got, emb))
        feats = jnp.concatenate([jnp.ones((SERVE_BATCH, 1, spec.dim)), emb],
                                1)
        with jax.default_matmul_precision("highest"):
            got = dot_interaction(feats, False, True)
            ref_inter = ref.dot_interaction_ref(feats, False)
        dot_err = max(dot_err, max_rel_err(got, ref_inter))
    ph.check("robe_lookup kernel vs jnp gather", gather_err, 0.0)
    ph.check("dot_interaction kernel vs jnp (HIGHEST)", dot_err, TOL_KERNEL)
    ph.report()
    ph.close()
    return ph.failures


def train_phase(seed: int) -> list:
    import jax
    from repro.configs import get_arch
    from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
    from repro.models.recsys import init_params, loss_fn
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_loop import (TrainConfig, build_train_step,
                                        init_state, run)

    ph = Phase("train dlrm-criteo-tb robe")
    cfg = get_arch("dlrm-criteo-tb").make_config()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.01))
    tc = TrainConfig(max_restarts=0)
    step = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    walls = []

    def timed_step(state, batch):
        out, dt = timed(step, state, batch)
        walls.append(dt)
        return out

    stream = CtrStream(CtrDataConfig(vocab_sizes=cfg.vocab_sizes,
                                     n_dense=cfg.n_dense,
                                     batch_size=TRAIN_BATCH,
                                     zipf_exponent=ZIPF, seed=seed))
    rep = run(init_state(params, opt, tc), timed_step, stream.batch_at,
              N_TRAIN_STEPS, tc)
    print(f"[{ph.name}] batch={TRAIN_BATCH} robe_slots={cfg.robe_size} "
          f"dim={cfg.embed_dim} first_step_s={walls[0]:.3f} "
          f"steady_ms_per_step={1e3 * np.median(walls[1:]):.3f} "
          f"losses={[round(x, 6) for x in rep.losses]}", flush=True)
    ph.require(f"{N_TRAIN_STEPS} steps done", rep.steps_done == N_TRAIN_STEPS)
    ph.require("restarts == 0", rep.restarts == 0)
    ph.require("nan_events == 0", rep.nan_events == 0)
    ph.require("every loss finite", len(rep.losses) == N_TRAIN_STEPS
               and all(np.isfinite(rep.losses)))
    ph.report()
    ph.close()
    return ph.failures


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def sharded_full_phase(seed: int, mesh) -> list:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.configs.recsys_archs import CRITEO_TB_VOCABS
    from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
    from repro.dist import api as dist
    from repro.dist.param_specs import recsys_specs
    from repro.models.recsys import init_params, serve_scores
    from repro.nn.embeddings import get_backend

    ph = Phase("serve dlrm-rm2 full, rows over 4 chips")
    cfg = get_arch("dlrm-rm2").make_config(
        embedding="full", vocab_sizes=tuple(min(v, FULL_VOCAB_CAP)
                                            for v in CRITEO_TB_VOCABS))
    ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
    spec = cfg.embedding_spec()
    init = functools.partial(init_params, cfg=cfg)
    key = jax.random.PRNGKey(seed)
    pspecs = recsys_specs(jax.eval_shape(init, key), ctx.rules,
                          embedding_spec=spec, mesh=mesh)
    params, dt = timed(jax.jit(init, out_shardings=dist.named_shardings(
        ctx, pspecs)), key)
    table = params["embedding"]["table"]
    print(f"[{ph.name}] vocab={spec.total_rows:,} rows (CriteoTB's "
          f"{sum(CRITEO_TB_VOCABS):,}, each field cut to <= "
          f"{FULL_VOCAB_CAP:,}) "
          f"table={table.shape} {table.nbytes / 1e9:.2f} GB, "
          f"{table.addressable_shards[0].data.nbytes / 1e9:.2f} GB per chip "
          f"({table.sharding.spec}); init_s={dt:.3f}", flush=True)

    stream = CtrStream(CtrDataConfig(vocab_sizes=cfg.vocab_sizes,
                                     n_dense=cfg.n_dense,
                                     batch_size=SERVE_BATCH,
                                     zipf_exponent=ZIPF, seed=seed))
    batches = [stream.batch_at(s) for s in range(N_SERVE_BATCHES)]
    with dist.use(ctx):
        score = jax.jit(lambda p, b: serve_scores(p, cfg, b))
        out = [timed(score, params, {k: jnp.asarray(v) for k, v in b.items()
                                     if k != "label"}) for b in batches]
    got = np.stack([np.asarray(s) for s, _ in out])
    walls = [dt for _, dt in out]
    print(f"[{ph.name}] batch={SERVE_BATCH} first_call_s={walls[0]:.3f} "
          f"steady_ms_per_batch={1e3 * np.median(walls[1:]):.3f}", flush=True)

    # one chip: the MLPs on device 0, the rows from the bit-exact
    # cacheable_rows hook through the batch's "emb" key
    dev0 = jax.devices()[0]
    dense = jax.device_put({k: v for k, v in params.items()
                            if k != "embedding"}, dev0)
    backend = get_backend("full")
    score1 = jax.jit(lambda p, b: serve_scores(p, cfg, b))
    want = []
    for b in batches:
        emb = np.stack([backend.cacheable_rows(params["embedding"], spec, f,
                                               b["sparse"][:, f])
                        for f in range(spec.n_fields)], axis=1)
        want.append(np.asarray(score1(dense, {
            "dense": jax.device_put(b["dense"], dev0),
            "emb": jax.device_put(emb, dev0)})))
    ph.require("scores finite", bool(np.all(np.isfinite(got))))
    ph.check("sharded full vs one-chip scores (emb key)",
             max_rel_err(got, np.stack(want)), TOL_LAYOUT)
    ph.report()
    ph.close()
    del params, table
    return ph.failures


def zero3_phase(seed: int, mesh) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
    from repro.dist import api as dist
    from repro.dist.param_specs import recsys_specs
    from repro.models.recsys import init_params, loss_fn
    from repro.train.elastic import train_state_specs
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_loop import (TrainConfig, build_train_step,
                                        init_state)

    ph = Phase("train dlrm-rm2 robe, ZeRO-3 vs replicated on 4 chips")
    ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
    stream = CtrStream(CtrDataConfig(
        vocab_sizes=get_arch("dlrm-rm2").make_config().vocab_sizes,
        n_dense=13, batch_size=TRAIN_BATCH, zipf_exponent=ZIPF, seed=seed))
    batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
    # plain SGD: the update is the slot gradient itself (adagrad's first
    # step is lr·sign(g), which a last-bit difference in a tiny g flips)
    opt = make_optimizer(OptimizerConfig(kind="sgd", lr=1.0))
    tc = TrainConfig(max_restarts=0)
    # ZeRO-3 shards the array evenly over `model`: round its 13,067,813
    # slots up to a multiple of the axis (both layouts use the same size)
    n_model = mesh.shape["model"]
    arch = get_arch("dlrm-rm2").make_config()
    size = -(-arch.robe_size // n_model) * n_model
    print(f"[{ph.name}] batch={TRAIN_BATCH} robe_slots={size} "
          f"(registry {arch.robe_size}, rounded up to a multiple of "
          f"{n_model})", flush=True)
    out = {}
    for name, z3 in (("replicated", False), ("zero3", True)):
        cfg = get_arch("dlrm-rm2").make_config(robe_shard_model=z3,
                                               robe_size=size)
        params = init_params(jax.random.PRNGKey(seed), cfg)
        old = np.asarray(params["embedding"]["memory"])
        state = init_state(params, opt, tc)
        pspecs = recsys_specs(params, ctx.rules,
                              embedding_spec=cfg.embedding_spec(), mesh=mesh)
        specs = train_state_specs(state, pspecs, ctx.rules)
        state = jax.device_put(state, dist.named_shardings(ctx, specs))
        b = jax.device_put(batch, NamedSharding(mesh, P(("data", "model"))))
        with dist.use(ctx):
            step = build_train_step(lambda p, bb, c=cfg: loss_fn(p, c, bb),
                                    opt, tc).lower(state, b).compile()
        colls = sorted(set(re.findall(
            r"\b(all-gather|all-reduce|reduce-scatter|all-to-all)",
            step.as_text())))
        (new, metrics), dt = timed(step, state, b)
        mem = new["params"]["embedding"]["memory"]
        shard = mem.addressable_shards[0].data.shape[0]
        print(f"[{ph.name}] {name}: memory {mem.sharding.spec}, "
              f"{shard} slots per chip, loss={float(metrics['loss']):.6f} "
              f"step_s={dt:.3f} collectives={colls}", flush=True)
        out[name] = (float(metrics["loss"]), np.asarray(mem) - old, shard)
    ph.require("ZeRO-3 holds a quarter of the array per chip",
               out["zero3"][2] * n_model == size)
    ph.check("loss, ZeRO-3 vs replicated",
             abs(out["zero3"][0] - out["replicated"][0])
             / max(1e-30, abs(out["replicated"][0])), TOL_LAYOUT)
    ph.check("ROBE array update, ZeRO-3 vs replicated",
             max_rel_err(out["zero3"][1], out["replicated"][1]), TOL_LAYOUT)
    ph.report()
    ph.close()
    return ph.failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve + train phases; 4: only the sharded "
                         "full-table and ZeRO-3 phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform is "
              f"{devices[0].platform!r}); this check runs on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache

    kind = devices[0].device_kind
    cache = use_compile_cache()
    print(f"device: platform=tpu kind={kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)

    failures = []
    if args.chips == 1:
        failures += serve_phase(args.seed)
        failures += train_phase(args.seed)
    else:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
        failures += sharded_full_phase(args.seed, mesh)
        failures += zero3_phase(args.seed, mesh)

    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed: {failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
