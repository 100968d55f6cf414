"""Offline scoring: back-to-back batches through ``EmbeddingServer.score``.

Traffic keys: ``batch`` (rows per call), ``pool`` (distinct batches made
from the seed in set-up; the window cycles through them), ``zipf``.  Each
call takes the batch from the host, moves it to the device, scores it and
brings the scores back: all of that is the measured work.  The window runs
whole calls until ``--seconds`` is spent.

Check: every score the window returned, against the reference's score of
its row (``score_gap``).
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.data import CtrStream
from bench.serving import make_server, reference_scores, score_gap


def setup(run) -> None:
    cfg, tr = run.config, run.traffic
    server = make_server(run)
    run.phase("server")
    stream = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], tr["batch"],
                       tr["zipf"], run.seed)
    pool = [stream.batch_at(i, labels=False) for i in range(tr["pool"])]
    run.phase("pool")
    server.score(cfg["embedding"], pool[0])     # compiles the one shape
    run.phase("compile")
    run.state = {"server": server, "pool": pool}


def window(run) -> None:
    server, pool = run.state["server"], run.state["pool"]
    backend = run.config["embedding"]
    calls = []                              # (pool index, scores)
    run.setup_done()
    run.start_trace()
    t0 = time.perf_counter()
    while True:
        k = len(calls) % len(pool)
        with TraceAnnotation("score"):
            s = server.score(backend, pool[k])
        calls.append((k, s))
        t = time.perf_counter() - t0
        if t >= run.seconds:
            break
    run.stop_trace()
    n = sum(len(s) for _, s in calls)
    run.record.update(samples=n, elapsed_s=t, calls=len(calls),
                      attempted=n,
                      failed=sum(int(np.sum(~np.isfinite(s))) for _, s in calls))
    run.state["calls"] = calls


def release(run) -> None:
    run.state.pop("server", None)


def check(run) -> dict:
    pool, calls = run.state["pool"], run.state["calls"]
    gaps = []
    for k in sorted({k for k, _ in calls}):
        want = reference_scores(run, pool[k]["dense"], pool[k]["sparse"])
        gaps += [score_gap(got, want) for kk, got in calls if kk == k]
    return {"score_gap": {"value": float(np.max(gaps)),
                          "limit": run.limits["score_gap"]}}
