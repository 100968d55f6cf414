"""Serving example: the production serving tier end to end.

Three shapes:

1. ``async_router`` — online scoring through the ``AsyncRouter``: requests
   submitted one by one with a 25ms latency budget, batched adaptively by
   the deadline-aware close-out, scored on the ``EmbeddingServer``'s
   ``full`` substrate through its hot-row cache.
2. ``replay_policies`` — the virtual-clock traffic replay comparing the
   deadline policy against fixed-size batching at equal offered load
   (measured service times, simulated queueing).
3. ``fleet_replay`` — a ``ReplicaFleet`` of three replicas behind one
   admission path, replayed at 3x the single-server offered load on one
   virtual clock, then a staggered-vs-synchronized model rollout on the
   same trace.
4. ``retrieval`` — the one-query-vs-many two-tower shape.

    PYTHONPATH=src python examples/serve_recsys.py
"""

import asyncio
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic_ctr import CtrDataConfig, CtrStream, RequestStream
from repro.models.recsys import RecsysConfig, init_params, serve_scores
from repro.serve import AsyncRouter, DeadlineBatcher, RouterConfig
from repro.serve.fleet import ReplicaFleet
from repro.serve.replay import (ReplayConfig, run_cell, run_fleet_cell,
                                run_fleet_push_cell)
from repro.serve.server import EmbeddingServer, ServerConfig
from repro.train.online import OnlineConfig, OnlineTrainer

VOCABS = (12_000, 6_000, 18_000, 4_000)


def build_server() -> EmbeddingServer:
    t0 = time.monotonic()
    server = EmbeddingServer(ServerConfig(vocab_sizes=VOCABS))
    print(f"server up: substrates {server.backends}, "
          f"{time.monotonic() - t0:.1f}s to init")
    return server


def async_router(server: EmbeddingServer, n: int = 256):
    """Per-request async serving with a latency budget."""
    stream = RequestStream(CtrDataConfig(
        vocab_sizes=VOCABS, n_dense=server.cfg.n_dense, batch_size=256))
    server.warm_caches(stream.id_batches(32, start_step=10_000))
    server.reset_cache_stats()
    score_fn = server.score_fn("full")          # hot cache in front
    router = AsyncRouter(score_fn, DeadlineBatcher(
        RouterConfig(max_batch=32, max_wait_s=0.010)))

    async def main():
        await router.start()
        t0 = time.monotonic()
        scores = await asyncio.gather(*[
            router.submit(stream.request_at(i), budget_s=0.025)
            for i in range(n)])
        dt = time.monotonic() - t0
        await router.stop()
        return scores, dt

    scores, dt = asyncio.run(main())
    stats = server.cache_stats("full")
    print(f"router: {n} requests in {dt*1e3:.0f}ms "
          f"({router.dispatched_batches} batches, "
          f"cache hit rate {stats['hit_rate']:.0%}); "
          f"first scores {[f'{float(s):.3f}' for s in scores[:4]]}")


def replay_policies(server: EmbeddingServer):
    """Deadline-aware vs fixed-size batching at equal offered load."""
    base = ReplayConfig(n_requests=1024, rate_hz=2000.0, deadline_s=0.025,
                        max_batch=32)
    for policy in ("deadline", "fixed"):
        server.reset_cache_stats()
        row = run_cell(server, "full",
                       dataclasses.replace(base, policy=policy),
                       warm_batches=32)
        print(f"replay full+{policy}: p50={row['p50_ms']:.1f}ms "
              f"p99={row['p99_ms']:.1f}ms qps={row['qps']:.0f} "
              f"miss={row['deadline_miss']} "
              f"hit_rate={row.get('hit_rate', 0):.0%}")


def fleet_replay():
    """Three replicas, one admission path, 3x the offered load."""
    fleet = ReplicaFleet(ServerConfig(vocab_sizes=VOCABS,
                                      backends=("full",)), n_replicas=3)
    base = ReplayConfig(n_requests=1024, rate_hz=6000.0, deadline_s=0.025,
                        max_batch=32)
    row = run_fleet_cell(fleet, "full", base, warm_batches=32)
    print(f"fleet r{row['n_replicas']}: p50={row['p50_ms']:.1f}ms "
          f"p99={row['p99_ms']:.1f}ms qps={row['qps']:.0f} "
          f"shed={row['shed']} retried={row['retried']} "
          f"hit_rate={row.get('hit_rate', 0):.0%}")
    # staggered rollout vs everyone-at-once: train a few publishes, then
    # replay the same trace under both push policies.  Staggered drains
    # each replica before its swap (one mid-rollout at a time, the rest
    # serving), so no admitted request waits out a swap; synchronized
    # takes the whole fleet down together and the p99 eats it.
    with tempfile.TemporaryDirectory() as pub:
        stream = CtrStream(CtrDataConfig(
            vocab_sizes=VOCABS, n_dense=fleet.replicas[0].cfg.n_dense,
            batch_size=256, seed=11))
        trainer = OnlineTrainer(
            fleet.replicas[0].recsys_config("full"), stream,
            OnlineConfig(publish_dir=pub, publish_every=8))
        trainer.run(24)
        steps = [p.step for p in trainer.publishes]
        for staggered in (True, False):
            row = run_fleet_push_cell(
                fleet, "full", base, publish_dir=pub, push_steps=steps,
                staggered=staggered, warm_batches=32)
            label = "staggered" if staggered else "synchronized"
            print(f"fleet push {label:12s}: p50={row['p50_ms']:.1f}ms "
                  f"p99={row['p99_ms']:.1f}ms miss={row['deadline_miss']} "
                  f"pushes={row['pushes']}")


def retrieval():
    cfg = RecsysConfig(
        name="retr", arch="two_tower", vocab_sizes=VOCABS * 2,
        embed_dim=32, tower_mlp=(128, 64, 32), n_user_fields=4,
        embedding="robe", robe_size=sum(VOCABS) * 2 * 32 // 1000,
        robe_block=32)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rs = np.random.RandomState(0)
    n_cand = 100_000
    item_vocab = np.asarray(VOCABS, np.int64)
    batch = {
        "sparse": jnp.asarray(rs.randint(0, 1000, (1, 8)), jnp.int32),
        "cand_sparse": jnp.asarray(
            (rs.random_sample((n_cand, 4)) * item_vocab).astype(np.int32))}
    score = jax.jit(lambda p, b: serve_scores(p, cfg, b))
    s = score(params, batch)
    s.block_until_ready()
    t0 = time.monotonic()
    s = score(params, batch)
    top = jax.lax.top_k(s[0], 10)[1].block_until_ready()
    dt = time.monotonic() - t0
    print(f"retrieval: scored {n_cand:,} candidates + top-10 in "
          f"{dt*1e3:.1f}ms -> ids {np.asarray(top)[:5]}...")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    server = build_server()
    async_router(server)
    replay_policies(server)
    fleet_replay()
    retrieval()
