"""Online training + zero-downtime serving, end to end.

The full production loop on one machine:

1. ``OnlineTrainer`` trains the ``full`` substrate live on a
   concept-drifting CTR stream (``drift_period`` rotates the hot head and
   re-salts the label rule), publishing a **full** snapshot first and
   **delta** checkpoints after — only the leaves that changed, plus a
   manifest of the embedding rows the training batches touched.
2. An ``EmbeddingServer`` hot-swaps each publish in with ``push()``:
   delta pushes invalidate exactly the touched rows in the hot-row cache
   (surviving entries stay bit-exact by the delta contract); full pushes
   clear it.  Cache-on vs cache-off scores stay ``np.array_equal`` after
   every swap.
3. The virtual-clock replay serves drifting traffic *while* the remaining
   publishes fire as scheduled push events — the printed row shows what a
   push costs on the timeline (``push_p50_ms``) and how stale the served
   model ran (``mean_staleness_s``).

    PYTHONPATH=src python examples/online_train_serve.py
"""

import tempfile

import numpy as np

from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
from repro.serve.replay import ReplayConfig, run_push_cell
from repro.serve.server import EmbeddingServer, ServerConfig
from repro.train.online import OnlineConfig, OnlineTrainer

VOCABS = (12_000, 6_000, 18_000, 4_000)
N_STEPS = 40


def train_online(server: EmbeddingServer, publish_dir: str) -> OnlineTrainer:
    """Train the server's own architecture on a drifting stream,
    publishing every 10 steps (full @ 0, deltas after)."""
    stream = CtrStream(CtrDataConfig(
        vocab_sizes=VOCABS, n_dense=server.cfg.n_dense, batch_size=256,
        drift_period=N_STEPS // 3, seed=11))
    trainer = OnlineTrainer(
        server.recsys_config("full"), stream,
        OnlineConfig(publish_dir=publish_dir, publish_every=10))
    report = trainer.run(N_STEPS)
    for p in report.publishes:
        print(f"publish step {p.step:>3}: {p.kind:<5} "
              f"{p.n_changed}/{p.n_leaves} leaves changed, "
              f"{p.n_touched} rows touched, {p.wall_s * 1e3:.0f}ms")
    print(f"trained {report.steps_done} steps, "
          f"loss {report.losses[0]:.3f} -> {report.final_loss:.3f}")
    return trainer


def push_with_parity(server: EmbeddingServer, trainer: OnlineTrainer,
                     publish_dir: str):
    """Swap every publish in by hand, checking cache parity after each."""
    probe = trainer.stream.batch_at(10_000)
    batch = {"dense": probe["dense"], "sparse": probe["sparse"]}
    for p in trainer.publishes:
        r = server.push("full", step=p.step, ckpt_dir=publish_dir)
        on = server.score("full", batch, use_cache=True)
        off = server.score("full", batch, use_cache=False)
        assert np.array_equal(on, off)
        print(f"push step {r.step:>3}: {r.kind:<5} "
              f"invalidated={r.invalidated:<5} "
              f"cleared={r.cache_cleared!s:<5} {r.wall_s * 1e3:.1f}ms "
              f"(cache parity ok)")


def serve_through_pushes(server: EmbeddingServer, trainer: OnlineTrainer,
                         publish_dir: str):
    """The push replay cell: drifting traffic, publishes hot-swapped in
    mid-replay on the virtual clock."""
    row = run_push_cell(
        server, "full", ReplayConfig(n_requests=1024, rate_hz=2000.0),
        publish_dir=publish_dir,
        push_steps=[p.step for p in trainer.publishes],
        drift_period=2, warm_batches=32)
    print(f"replay+push: p50={row['p50_ms']:.1f}ms p99={row['p99_ms']:.1f}ms "
          f"qps={row['qps']:.0f} shed={row['shed']} "
          f"pushes={row['pushes']} push_p50={row['push_p50_ms']:.1f}ms "
          f"staleness={row['mean_staleness_s'] * 1e3:.0f}ms "
          f"hit_rate={row.get('hit_rate', 0):.0%}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    with tempfile.TemporaryDirectory() as publish_dir:
        server = EmbeddingServer(ServerConfig(vocab_sizes=VOCABS,
                                              backends=("full",),
                                              model_dir=publish_dir))
        trainer = train_online(server, publish_dir)
        push_with_parity(server, trainer, publish_dir)
        serve_through_pushes(server, trainer, publish_dir)
