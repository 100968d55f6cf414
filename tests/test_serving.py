"""Serving-tier suite: deadline router, frequency-sketch hot-row cache,
multi-substrate server, and the virtual-clock traffic replay.

Everything here runs on deterministic clocks — the batching policy takes
``now`` explicitly, the replay advances a virtual timeline, and the async
router is exercised through its untimed paths — so tier-1 never sleeps
on the wall clock.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data.synthetic_ctr import (CtrDataConfig, CtrStream,
                                      RequestStream, poisson_arrivals)
from repro.serve.hot_cache import CountMinSketch, HotRowCache
from repro.serve.router import (AsyncRouter, DeadlineBatcher, FixedBatcher,
                                LoadShedError, RouterConfig, stack_and_pad)
from repro.serve.serving import MicroBatcher, percentile

VOCABS = (12_000, 6_000, 18_000, 4_000)


# ---------------------------------------------------------------------------
# percentile fix (satellite: nearest-rank off-by-one)
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank_known_vector():
    """ceil-rank − 1 on a known vector; the old ``int(n·p)`` index read
    the 3rd element as the median of 4."""
    lats = np.asarray([10.0, 20.0, 30.0, 40.0])
    assert percentile(lats, 0.5) == 20.0          # old code returned 30.0
    assert percentile(lats, 0.25) == 10.0
    assert percentile(lats, 0.75) == 30.0
    assert percentile(lats, 0.99) == 40.0
    assert percentile(lats, 1.0) == 40.0
    assert percentile(np.asarray([7.0]), 0.5) == 7.0
    odd = np.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    assert percentile(odd, 0.5) == 3.0
    with pytest.raises(ValueError):
        percentile(np.asarray([]), 0.5)
    with pytest.raises(ValueError):
        percentile(odd, 0.0)


# ---------------------------------------------------------------------------
# the batching policy (pure, clockless)
# ---------------------------------------------------------------------------

def _rc(**kw):
    kw.setdefault("max_batch", 4)
    return RouterConfig(**kw)


def test_deadline_batcher_dispatches_on_fill():
    b = DeadlineBatcher(_rc(max_batch=3))
    for i in range(3):
        assert b.poll(now=0.0) is None or i == 0
        b.admit({"x": np.float32([i])}, now=0.0)
    out = b.poll(now=0.0)
    assert [int(r.features["x"][0]) for r in out] == [0, 1, 2]   # FIFO
    assert len(b) == 0 and b.poll(now=0.0) is None


def test_deadline_batcher_closes_before_tightest_deadline():
    b = DeadlineBatcher(_rc(init_service_s=0.002, close_margin_s=0.001))
    b.admit({"x": np.float32([0])}, now=0.0, deadline=0.100)
    b.admit({"x": np.float32([1])}, now=0.001, deadline=0.020)
    # close-out = min deadline − p50 service − margin, not FIFO order
    assert b.close_at() == pytest.approx(0.020 - 0.002 - 0.001)
    assert b.poll(now=0.010) is None               # not due yet
    out = b.poll(now=0.017)
    assert out is not None and len(out) == 2       # both ship together


def test_deadline_batcher_max_wait_without_deadlines():
    b = DeadlineBatcher(_rc(max_wait_s=0.05))
    b.admit({"x": np.float32([0])}, now=1.0)
    assert b.close_at() == pytest.approx(1.05)
    assert b.poll(now=1.049) is None
    assert len(b.poll(now=1.05)) == 1


def test_deadline_batcher_sheds_on_queue_bound():
    b = DeadlineBatcher(_rc(max_batch=8, max_queue=2))
    b.admit({"x": np.float32([0])}, now=0.0)
    b.admit({"x": np.float32([1])}, now=0.0)
    with pytest.raises(LoadShedError, match="queue_full"):
        b.admit({"x": np.float32([2])}, now=0.0)
    assert b.shed_count == 1 and len(b) == 2       # queue unpoisoned


def test_deadline_batcher_sheds_infeasible_deadline():
    b = DeadlineBatcher(_rc(init_service_s=0.010))
    with pytest.raises(LoadShedError, match="infeasible"):
        b.admit({"x": np.float32([0])}, now=0.0, deadline=0.005)
    # a feasible one is fine
    b.admit({"x": np.float32([1])}, now=0.0, deadline=0.050)
    assert len(b) == 1


def test_service_estimate_is_p50_of_recent_observations():
    b = DeadlineBatcher(_rc(init_service_s=0.123, service_window=4))
    assert b.service_estimate == 0.123             # prior before data
    for s in (0.010, 0.002, 0.004, 0.008):
        b.observe(s)
    assert b.service_estimate == 0.004             # nearest-rank p50 of 4
    b.observe(0.100)                               # window slides off 0.010
    assert b.service_estimate == 0.004             # p50 of {2,4,8,100}ms


def test_fixed_batcher_ignores_deadlines():
    b = FixedBatcher(_rc(max_batch=4, max_wait_s=0.05,
                         init_service_s=0.002))
    b.admit({"x": np.float32([0])}, now=0.0, deadline=0.010)
    assert b.close_at() == pytest.approx(0.05)     # deadline not consulted
    assert b.poll(now=0.04) is None
    # and it never sheds on infeasibility (only on the queue bound)
    b.admit({"x": np.float32([1])}, now=0.0, deadline=0.0001)
    assert len(b) == 2


def test_stack_and_pad_repeats_last_row_and_counts_valid():
    feats = [{"x": np.float32([i, 9])} for i in range(3)]
    batch, n = stack_and_pad(feats, 8)
    assert n == 3 and batch["x"].shape == (8, 2)
    np.testing.assert_array_equal(batch["x"][3], batch["x"][2])
    np.testing.assert_array_equal(batch["x"][7], batch["x"][2])
    with pytest.raises(ValueError, match="empty"):
        stack_and_pad([], 4)
    with pytest.raises(ValueError, match="batch_size"):
        stack_and_pad(feats, 2)


# ---------------------------------------------------------------------------
# the async router (untimed paths only: no wall-clock sleeps in tier-1)
# ---------------------------------------------------------------------------

def _double(batch, n_valid=None):
    return np.asarray(batch["x"][:, 0]) * 2.0


def test_async_router_full_batch_routes_results():
    async def main():
        router = AsyncRouter(_double, DeadlineBatcher(
            _rc(max_batch=4, max_wait_s=30.0)))
        await router.start()
        res = await asyncio.gather(*[
            router.submit({"x": np.float32([i, 0])}) for i in range(4)])
        await router.stop()
        return res

    res = asyncio.run(main())
    assert [float(r) for r in res] == [0.0, 2.0, 4.0, 6.0]


def test_async_router_sheds_and_flushes_on_stop():
    async def main():
        router = AsyncRouter(_double, DeadlineBatcher(
            _rc(max_batch=8, max_queue=2, max_wait_s=30.0)))
        await router.start()
        t1 = asyncio.create_task(router.submit({"x": np.float32([1, 0])}))
        t2 = asyncio.create_task(router.submit({"x": np.float32([2, 0])}))
        await asyncio.sleep(0)                     # let both admit
        with pytest.raises(LoadShedError, match="queue_full"):
            await router.submit({"x": np.float32([3, 0])})
        await router.stop(flush=True)              # scores the partial batch
        return await asyncio.gather(t1, t2)

    r1, r2 = asyncio.run(main())
    assert (float(r1), float(r2)) == (2.0, 4.0)


def test_async_router_requires_start():
    router = AsyncRouter(_double, DeadlineBatcher(_rc()))
    with pytest.raises(RuntimeError, match="not started"):
        asyncio.run(router.submit({"x": np.float32([0, 0])}))


# ---------------------------------------------------------------------------
# MicroBatcher as a thin sync wrapper over the policy
# ---------------------------------------------------------------------------

def test_micro_batcher_poll_uses_deadline_closeout():
    t = [0.0]
    seen_valid = []

    def score(batch, n_valid=None):
        seen_valid.append(n_valid)
        return np.asarray(batch["x"][:, 0])

    mb = MicroBatcher(batch_size=4, score_fn=score, max_wait_ms=2.0,
                      clock=lambda: t[0])
    mb.submit({"x": np.float32([7, 0])})
    assert mb.poll() == []                         # not due at t=0
    t[0] = 0.003                                   # past max_wait
    out = mb.poll()
    assert [float(o) for o in out] == [7.0]
    assert seen_valid == [1]                       # consumer told the tail
    assert len(mb) == 0


def test_micro_batcher_flush_slices_padding_inside():
    def score(batch, n_valid=None):
        # the padded tail is visible to the scorer (compiled shape) but
        # n_valid names the real rows
        assert batch["x"].shape[0] == 4
        return np.asarray(batch["x"][:, 0])

    mb = MicroBatcher(batch_size=4, score_fn=score)
    for i in range(6):
        mb.submit({"x": np.float32([i, 0])})
    out = mb.flush()
    assert [float(o) for o in out] == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]


def test_micro_batcher_bounded_queue():
    mb = MicroBatcher(batch_size=2, score_fn=lambda b: b["x"][:, 0],
                      max_queue=3)
    for i in range(3):
        mb.submit({"x": np.float32([i, 0])})
    with pytest.raises(LoadShedError):
        mb.submit({"x": np.float32([9, 0])})
    assert len(mb.flush()) == 3


# ---------------------------------------------------------------------------
# count-min sketch + hot-row cache
# ---------------------------------------------------------------------------

def test_count_min_sketch_never_undercounts():
    sk = CountMinSketch(width=1 << 10, depth=4, seed=3)
    rs = np.random.RandomState(0)
    keys = rs.randint(0, 1 << 40, 5000).astype(np.int64)
    sk.update(keys)
    uniq, true = np.unique(keys, return_counts=True)
    est = sk.estimate(uniq)
    assert np.all(est >= true)
    # heavy hitters stay sharp even in a small sketch
    hot = np.int64([42])
    sk.update(np.repeat(hot, 500))
    assert sk.estimate(hot)[0] >= 500
    assert sk.total == 5500


def test_count_min_sketch_deterministic_and_shaped():
    a = CountMinSketch(width=1000, depth=3, seed=1)   # rounds to 1024
    b = CountMinSketch(width=1000, depth=3, seed=1)
    assert a.width == 1024
    keys = np.arange(100, dtype=np.int64).reshape(10, 10)
    a.update(keys)
    b.update(keys)
    np.testing.assert_array_equal(a.estimate(keys), b.estimate(keys))
    assert a.estimate(keys).shape == (10, 10)
    assert a.estimate(np.int64([])).shape == (0,)


def _backend_and_spec(kind):
    from repro.nn.embeddings import EmbeddingSpec, embedding_init, \
        get_backend
    spec = EmbeddingSpec(vocab_sizes=(50, 30, 70), dim=8, kind=kind)
    params = embedding_init(jax.random.PRNGKey(0), spec)
    return get_backend(kind), spec, params


@pytest.mark.parametrize("kind", ["full", "hashed"])
def test_cacheable_rows_bit_exact_vs_lookup(kind):
    """The hot-row-cache contract: host rows == the device gather, bit for
    bit, per field."""
    backend, spec, params = _backend_and_spec(kind)
    rs = np.random.RandomState(1)
    idx = np.stack([rs.randint(0, v, 16) for v in spec.vocab_sizes], axis=1)
    ref = np.asarray(backend.lookup(params, spec, jnp.asarray(idx)))
    for f in range(spec.n_fields):
        rows = backend.cacheable_rows(params, spec, f, idx[:, f])
        np.testing.assert_array_equal(rows, ref[:, f])


def test_cacheable_rows_protocol_declines():
    from repro.nn.embeddings import get_backend
    from repro.nn.embedding_backends.base import EmbeddingBackend
    assert EmbeddingBackend.cacheable_rows is None        # base default
    assert get_backend("robe").cacheable_rows is None     # paper's point
    assert get_backend("tt").cacheable_rows is None       # compute-bound
    _, spec, params = _backend_and_spec("full")
    assert HotRowCache.for_backend(get_backend("robe"), spec, params) is None
    with pytest.raises(ValueError, match="declines"):
        HotRowCache(get_backend("robe"), spec, params)


def test_hot_row_cache_exact_rows_and_hit_accounting():
    backend, spec, params = _backend_and_spec("full")
    cache = HotRowCache(backend, spec, params, capacity=64)
    idx = np.asarray([[1, 2, 3], [1, 5, 3], [4, 2, 3]])
    out1 = cache.lookup(idx)
    ref = np.asarray(backend.lookup(params, spec, jnp.asarray(idx)))
    np.testing.assert_array_equal(out1, ref)              # bit-exact, cold
    assert cache.hits == 0 and cache.misses == 9          # 3 rows x 3 fields
    cache.reset_stats()
    out2 = cache.lookup(idx)                              # fully warm now
    np.testing.assert_array_equal(out2, ref)
    assert cache.misses == 0 and cache.hit_rate == 1.0


def test_hot_row_cache_ignores_padded_tail():
    backend, spec, params = _backend_and_spec("full")
    cache = HotRowCache(backend, spec, params, capacity=64)
    idx = np.asarray([[1, 2, 3], [9, 9, 9], [9, 9, 9]])   # rows 1,2 = pad
    out = cache.lookup(idx, n_valid=1)
    # padded rows are still gathered (compiled shape) ...
    ref = np.asarray(backend.lookup(params, spec, jnp.asarray(idx)))
    np.testing.assert_array_equal(out, ref)
    # ... but never counted: one real request x 3 fields
    assert cache.hits + cache.misses == 3
    assert cache.sketch.total == 3


def test_hot_row_cache_capacity_prunes_to_hot_set():
    backend, spec, params = _backend_and_spec("full")
    cache = HotRowCache(backend, spec, params, capacity=8)
    hot = np.asarray([[3, 4, 5]])
    for _ in range(10):                                   # heat 3 rows
        cache.lookup(hot)
    rs = np.random.RandomState(0)
    for _ in range(6):                                    # cold scans
        cache.lookup(np.stack([rs.randint(0, v, 4)
                               for v in spec.vocab_sizes], axis=1))
    assert len(cache._rows) <= 8
    off = spec.offsets
    for f, v in enumerate((3, 4, 5)):                     # hot rows survive
        assert int(v + off[f]) in cache._rows
    cache.reset_stats()
    cache.lookup(hot)
    assert cache.hit_rate == 1.0


def test_hot_row_cache_hit_rate_on_zipf_traffic():
    """The acceptance criterion's engine: on zipf-1.05 skew a 16k-row
    cache over a 40k-row vocab clears 50% hit rate once warm."""
    from repro.nn.embeddings import EmbeddingSpec, embedding_init, \
        get_backend
    spec = EmbeddingSpec(vocab_sizes=VOCABS, dim=8, kind="full")
    params = embedding_init(jax.random.PRNGKey(0), spec)
    cache = HotRowCache(get_backend("full"), spec, params, capacity=16384)
    stream = RequestStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=0,
                                         batch_size=256,
                                         zipf_exponent=1.05))
    cache.warm(stream.id_batches(48, start_step=1000))
    for s in range(8):                                    # measured traffic
        cache.lookup(stream.id_batches(1, start_step=s)[0])
    assert cache.hit_rate >= 0.5, cache.stats()


# ---------------------------------------------------------------------------
# CtrStream skew (satellite: the assumption the hit-rate criterion rests on)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(zipf=st.sampled_from([1.0, 1.05, 1.1]),
       seed=st.integers(min_value=0, max_value=10 ** 6))
def test_ctr_stream_topk_mass_concentrates(zipf, seed):
    """Under ``zipf_exponent`` near 1 the top-10%-of-vocab hottest ids
    carry at least double their proportional share of the traffic — the
    skew the hot-row cache's hit-rate criterion rests on."""
    stream = CtrStream(CtrDataConfig(vocab_sizes=(4000,), batch_size=256,
                                     zipf_exponent=zipf, seed=seed))
    ids = np.concatenate([stream.batch_at(s)["sparse"][:, 0]
                          for s in range(20)])
    _, counts = np.unique(ids, return_counts=True)
    counts = np.sort(counts)[::-1]
    k = max(1, int(0.10 * 4000))
    mass = counts[:k].sum() / counts.sum()
    assert mass >= 0.20, (zipf, seed, mass)


@settings(max_examples=6, deadline=None)
@given(period=st.sampled_from([8, 12]),
       seed=st.integers(min_value=0, max_value=10 ** 6))
def test_ctr_stream_drift_shifts_topk_mass(period, seed):
    """With ``drift_period`` set, the hot head rotates between phases:
    phase 0's top-10% hot set carries far less of phase 1's traffic than
    of its own — the distribution shift online training exists to chase —
    while each phase stays internally skewed (the cache still wins) and
    ``batch_at`` stays pure in (seed, step)."""
    cfg = CtrDataConfig(vocab_sizes=(4000,), batch_size=256,
                        zipf_exponent=1.05, seed=seed, drift_period=period)
    stream = CtrStream(cfg)

    def phase_ids(phase):
        return np.concatenate(
            [stream.batch_at(phase * period + s)["sparse"][:, 0]
             for s in range(period)])

    k = max(1, int(0.10 * 4000))
    ids0, ids1 = phase_ids(0), phase_ids(1)
    vals, counts = np.unique(ids0, return_counts=True)
    hot0 = vals[np.argsort(-counts)][:k]
    own_mass = np.isin(ids0, hot0).mean()
    cross_mass = np.isin(ids1, hot0).mean()
    assert own_mass >= 0.20, (period, seed, own_mass)
    assert cross_mass <= 0.5 * own_mass, (period, seed, own_mass, cross_mass)
    # each phase is still zipf-skewed in its own right
    c1 = np.sort(np.unique(ids1, return_counts=True)[1])[::-1]
    assert c1[:k].sum() / c1.sum() >= 0.20
    # determinism: the drifted batches are pure in (seed, step)
    again = CtrStream(cfg).batch_at(period + 1)
    np.testing.assert_array_equal(again["sparse"],
                                  stream.batch_at(period + 1)["sparse"])


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10 ** 6))
def test_sketch_hot_set_tracks_drift(seed):
    """The serving-side consequence of drift: a frequency sketch fed
    phase-1 traffic ranks phase-1's head far hotter than phase-0's — the
    heat map follows the traffic, which is why ``HotRowCache.clear`` keeps
    the sketch and re-converges in one warm pass."""
    period = 10
    stream = CtrStream(CtrDataConfig(vocab_sizes=(4000,), batch_size=256,
                                     zipf_exponent=1.05, seed=seed,
                                     drift_period=period))

    def hot_set(phase):
        ids = np.concatenate(
            [stream.batch_at(phase * period + s)["sparse"][:, 0]
             for s in range(period)])
        vals, counts = np.unique(ids, return_counts=True)
        return ids, vals[np.argsort(-counts)][:400]

    _, hot0 = hot_set(0)
    ids1, hot1 = hot_set(1)
    sketch = CountMinSketch(width=1 << 14, depth=4, seed=0)
    sketch.update(ids1)
    e1 = sketch.estimate(hot1).mean()
    e0 = sketch.estimate(hot0).mean()
    assert e1 > 2 * e0, (seed, e0, e1)


def test_ctr_stream_cache_capacity_fraction_captures_half():
    """At zipf 1.05 the hottest ~27% of rows carry ≥ half the mass — the
    sizing rule behind the 16k-row cache on the 40k-row serving vocab."""
    stream = CtrStream(CtrDataConfig(vocab_sizes=(4000,), batch_size=256,
                                     zipf_exponent=1.05, seed=11))
    ids = np.concatenate([stream.batch_at(s)["sparse"][:, 0]
                          for s in range(30)])
    _, counts = np.unique(ids, return_counts=True)
    counts = np.sort(counts)[::-1]
    k = int(0.27 * 4000)
    assert counts[:k].sum() / counts.sum() >= 0.45


# ---------------------------------------------------------------------------
# arrivals + request stream
# ---------------------------------------------------------------------------

def test_poisson_arrivals_deterministic_and_calibrated():
    a = poisson_arrivals(1000.0, 4096, seed=5)
    b = poisson_arrivals(1000.0, 4096, seed=5)
    np.testing.assert_array_equal(a, b)
    assert np.all(np.diff(a) >= 0)
    # empirical rate within 10% of offered
    assert abs(4096 / a[-1] - 1000.0) < 100.0
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 8)


def test_request_stream_slices_ctr_batches():
    cfg = CtrDataConfig(vocab_sizes=VOCABS, n_dense=4, batch_size=8)
    stream = RequestStream(cfg)
    raw = CtrStream(cfg).batch_at(1)
    req = stream.request_at(8 + 3)                 # step 1, row 3
    assert "label" not in req
    np.testing.assert_array_equal(req["sparse"], raw["sparse"][3])
    np.testing.assert_array_equal(req["dense"], raw["dense"][3])
    assert len(stream.requests(5)) == 5


# ---------------------------------------------------------------------------
# the replay (virtual clock — deterministic to the float)
# ---------------------------------------------------------------------------

def _mini_requests(n, seed=0):
    stream = RequestStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=4,
                                         batch_size=64, seed=seed))
    return stream.requests(n)


def test_replay_deterministic_under_synthetic_service():
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=256, rate_hz=2000.0, deadline_s=0.025,
                       max_batch=32)
    reqs = _mini_requests(256)
    arr = poisson_arrivals(cfg.rate_hz, 256, seed=1)
    r1 = replay(synthetic_service(), reqs, arr, cfg)
    r2 = replay(synthetic_service(), reqs, arr, cfg)
    assert r1 == r2
    assert r1.completed + r1.shed == 256
    assert r1.p50_ms <= r1.p95_ms <= r1.p99_ms
    assert r1.batches >= 256 // 32


def test_replay_deadline_policy_beats_fixed_p99_at_equal_load():
    """The tentpole's headline behaviour: at an offered load where a
    64-deep batch takes ~32ms to fill, the deadline-aware close-out keeps
    p99 near the 25ms budget while fixed-size batching rides the fill (or
    its 50ms timeout)."""
    import dataclasses
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    base = ReplayConfig(n_requests=1024, rate_hz=2000.0, deadline_s=0.025,
                        max_batch=64, max_wait_s=0.050)
    reqs = _mini_requests(1024)
    arr = poisson_arrivals(base.rate_hz, 1024, seed=2)
    dl = replay(synthetic_service(), reqs, arr, base)
    fx = replay(synthetic_service(), reqs, arr,
                dataclasses.replace(base, policy="fixed"))
    assert dl.completed == fx.completed + fx.shed == 1024 - dl.shed
    assert dl.p99_ms < fx.p99_ms, (dl.p99_ms, fx.p99_ms)
    assert dl.p99_ms <= 26.0                      # the budget holds
    assert fx.p99_ms >= 30.0                      # the fill time shows


def test_replay_sheds_under_overload():
    """Open-loop overload: a slow scorer + a tight queue bound must shed
    explicitly rather than queue without bound."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=512, rate_hz=5000.0, deadline_s=None,
                       max_batch=16, max_queue=32, max_wait_s=0.002)
    reqs = _mini_requests(512)
    arr = poisson_arrivals(cfg.rate_hz, 512, seed=3)
    rep = replay(synthetic_service(base_s=0.050), reqs, arr, cfg)
    assert rep.shed > 0
    assert rep.completed + rep.shed == 512
    assert rep.qps < cfg.rate_hz                  # delivered < offered


def test_replay_infeasible_deadline_sheds_at_admission():
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=64, rate_hz=1000.0, deadline_s=0.001,
                       max_batch=8, init_service_s=0.005)
    reqs = _mini_requests(64)
    arr = poisson_arrivals(cfg.rate_hz, 64, seed=4)
    rep = replay(synthetic_service(base_s=0.005), reqs, arr, cfg)
    assert rep.shed == 64 and rep.completed == 0  # all infeasible


# ---------------------------------------------------------------------------
# the multi-substrate server (end to end)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    from repro.serve.server import EmbeddingServer, ServerConfig
    return EmbeddingServer(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        top_mlp=(16, 1), robe_compression=100, cache_capacity=16384))


def _server_batch(n=16, step=0):
    stream = CtrStream(CtrDataConfig(vocab_sizes=VOCABS, n_dense=4,
                                     batch_size=n))
    b = stream.batch_at(step)
    return {"dense": b["dense"], "sparse": b["sparse"]}


def test_server_routes_all_four_backends(server):
    from repro.models.recsys import serve_scores
    batch = _server_batch()
    for name in ("full", "robe", "hashed", "tt"):
        got = server.score(name, batch)
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        want = np.asarray(serve_scores(server.params(name),
                                       server.recsys_config(name), jb))
        np.testing.assert_allclose(got, want, atol=1e-6, err_msg=name)
    with pytest.raises(KeyError, match="not resident"):
        server.score("nope", batch)


def test_server_cached_full_path_bit_exact(server):
    """Acceptance: bit-exact score parity, cache on vs the uncached
    ``full`` path — same jitted scorer, host-gathered rows."""
    assert server.cache("full") is not None
    assert server.cache("robe") is None            # robe declined
    for step in range(3):
        batch = _server_batch(n=32, step=step)
        cached = server.score("full", batch)
        direct = server.score("full", batch, use_cache=False)
        np.testing.assert_array_equal(cached, direct)
    assert server.cache("full").sketch.total > 0   # the cache really ran


def test_server_cached_hashed_path_bit_exact(server):
    for step in range(2):
        batch = _server_batch(n=16, step=step)
        np.testing.assert_array_equal(
            server.score("hashed", batch),
            server.score("hashed", batch, use_cache=False))


@pytest.mark.parametrize("kind,rtol", [("full", 0), ("hashed", 2e-6)])
def test_server_cached_multi_hot_path_bit_exact(kind, rtol):
    """A multi-hot DLRM-DCNv2 server: the cache gathers every id column
    under its own field, and the scorer pools the cached rows as it pools
    the looked-up ones, to the bit for ``full``.  A ``hashed`` row is a
    product of two table rows, which the compiler fuses into the pooling
    sum on the direct path: there the scores agree to rounding."""
    from repro.serve.server import EmbeddingServer, ServerConfig
    bags = (2, 1, 3) + (1,) * (len(VOCABS) - 3)
    srv = EmbeddingServer(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        top_mlp=(16, 1), arch="dlrm_dcnv2", cross_layers=2, cross_rank=4,
        bag_sizes=bags, backends=(kind,), robe_compression=100))
    rs = np.random.RandomState(0)
    cols = srv.recsys_config(kind).embedding_spec().columns
    batch = {"dense": rs.randn(16, 4).astype(np.float32),
             "sparse": np.stack([rs.randint(0, VOCABS[f], 16)
                                 for f in cols], 1).astype(np.int32)}
    direct = srv.score(kind, batch, use_cache=False)
    for _ in range(2):                 # misses, then hits
        np.testing.assert_allclose(srv.score(kind, batch), direct,
                                   rtol=rtol, atol=0)
    assert srv.cache(kind).hits > 0


def test_server_score_fn_slices_to_valid(server):
    fn = server.score_fn("full")
    batch, n = stack_and_pad(_mini_requests(5), 16)
    out = fn(batch, n_valid=n)
    assert out.shape == (5,)
    full = server.score("full", batch, use_cache=False)
    np.testing.assert_array_equal(out, full[:5])


def test_server_replay_cell_end_to_end(server):
    """One measured-service replay cell through the real server: the
    row's shape, with the hit-rate criterion live."""
    from repro.serve.replay import ReplayConfig, run_cell
    server.reset_cache_stats()
    row = run_cell(server, "full",
                   ReplayConfig(n_requests=512, rate_hz=2000.0,
                                deadline_s=0.025, max_batch=32),
                   zipf=1.05, warm_batches=40)
    for k in ("p50_ms", "p99_ms", "qps", "shed", "hit_rate", "backend",
              "policy", "completed", "mean_batch"):
        assert k in row, k
    assert row["completed"] + row["shed"] == 512
    assert row["hit_rate"] >= 0.5, row
    assert row["p50_ms"] <= row["p99_ms"]


# ---------------------------------------------------------------------------
# satellite regressions (ISSUE 10)
# ---------------------------------------------------------------------------

def test_close_at_ignores_deadline_beyond_batch_prefix():
    """A tight deadline parked at queue position >= max_batch must not
    force a premature close-out of a batch that cannot contain it: poll
    ships the FIFO prefix, so only the first max_batch pending requests'
    deadlines may drive the close-out."""
    b = DeadlineBatcher(_rc(max_batch=4, max_wait_s=0.050,
                            init_service_s=0.002, close_margin_s=0.0))
    for i in range(4):
        b.admit({"x": np.float32([i])}, now=0.0)    # prefix: no deadlines
    b.admit({"x": np.float32([4])}, now=0.0, deadline=0.005)  # parked deep
    # buggy close-out was min(0.050, 0.005 - 0.002) = 0.003 — an early
    # close-out scheduled for a batch that cannot carry the tight request
    assert b.close_at() == pytest.approx(0.050)
    out = b.poll(now=0.0)                           # ships on fill (4-wide)
    assert [int(r.features["x"][0]) for r in out] == [0, 1, 2, 3]
    # now the tight request heads the queue and legitimately drives it
    assert b.close_at() == pytest.approx(0.005 - b.service_estimate)


def test_stack_and_pad_rejects_mismatched_keys():
    """Extra keys were silently dropped and missing keys surfaced as a
    bare KeyError mid-stack; both must be the clear ValueError contract
    MicroBatcher.flush promises."""
    a = {"dense": np.float32([1.0]), "sparse": np.int64([2])}
    missing = {"dense": np.float32([3.0])}
    extra = dict(a, emb=np.float32([4.0]))
    with pytest.raises(ValueError, match="share the same feature keys"):
        stack_and_pad([a, missing], 4)
    with pytest.raises(ValueError, match="share the same feature keys"):
        stack_and_pad([a, extra], 4)
    batch, n = stack_and_pad([a, dict(a)], 4)       # equal keys still fine
    assert n == 2 and set(batch) == {"dense", "sparse"}


def test_replay_all_shed_reports_makespan_not_zero():
    """When every request sheds, the old report forced makespan_s to 0.0
    even though the trace spanned time and fired pushes occupied the
    server; qps stays 0 but the timeline must be honest."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=64, rate_hz=1000.0, deadline_s=0.001,
                       max_batch=8, init_service_s=0.005)
    reqs = _mini_requests(64)
    arr = poisson_arrivals(cfg.rate_hz, 64, seed=4)
    pushed = []
    rep = replay(synthetic_service(base_s=0.005), reqs, arr, cfg,
                 events=[(0.010, lambda: pushed.append(1))])
    assert rep.shed == 64 and rep.completed == 0
    assert pushed == [1] and rep.pushes == 1
    assert rep.makespan_s >= float(arr[-1])         # was 0.0
    assert rep.qps == 0.0
    assert rep.offered_qps == pytest.approx(64 / float(arr[-1]))


def test_replay_single_request_trace_no_zero_division():
    """A 1-request trace arriving at t=0 used to divide offered_qps by
    arrivals[-1] == 0.0."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=1, rate_hz=1000.0, deadline_s=None,
                       max_batch=4, max_wait_s=0.010)
    reqs = _mini_requests(1)
    rep = replay(synthetic_service(), reqs, np.asarray([0.0]), cfg)
    assert rep.completed == 1 and rep.shed == 0
    assert rep.offered_qps == 0.0                   # guarded, not inf/raise
    assert rep.makespan_s > 0.0 and rep.qps > 0.0


def test_run_grid_cell_order_independent(server):
    """Cache state must not leak across grid cells: the z4.0 low-skew
    control's hit rate was polluted by z1.05 heat when cells only reset
    stats.  With the full per-cell HotRowCache.reset (store + sketch) the
    grid commutes — same rows whichever order the zipf cells run."""
    import dataclasses as dc
    from repro.serve.replay import ReplayConfig, run_grid
    cache = server.cache("full")

    def svc(batch, n_valid):
        cache.lookup(batch["sparse"], n_valid)      # deterministic traffic
        return 1e-3

    base = ReplayConfig(n_requests=192, rate_hz=2000.0, max_batch=16)
    kw = dict(policies=("deadline",), backends=("full",), base=base,
              warm_batches=12, service=svc)
    ab = run_grid(server, zipfs=(1.05, 4.0), **kw)
    ba = run_grid(server, zipfs=(4.0, 1.05), **kw)
    key = lambda r: r["zipf"]                        # noqa: E731
    assert sorted(ab, key=key) == sorted(ba, key=key)
    by_zipf = {r["zipf"]: r for r in ab}
    assert by_zipf[1.05]["hit_rate"] > by_zipf[4.0]["hit_rate"]


# ---------------------------------------------------------------------------
# the replica fleet (deterministic clocks throughout)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet():
    from repro.serve.fleet import ReplicaFleet
    from repro.serve.server import ServerConfig
    return ReplicaFleet(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        top_mlp=(16, 1), backends=("full",), robe_compression=100,
        cache_capacity=16384), n_replicas=3)


def test_fleet_scores_equal_single_server(server, fleet):
    """Replicas share one trained model: every replica's scores (and the
    fleet's least-dispatched routing) are array-equal to the single
    server's on identical traffic."""
    for step in range(3):
        batch = _server_batch(n=16, step=step)
        want = server.score("full", batch, use_cache=False)
        for r in range(len(fleet)):
            got = fleet.score("full", batch, replica=r, use_cache=False)
            np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            fleet.score("full", batch, use_cache=False), want)


def test_fleet_admission_retries_on_replica_shed():
    """The one admission path: the least-loaded replica sheds (its own
    service estimate makes the deadline infeasible) and the request is
    delivered by the next replica instead of being dropped."""
    from repro.serve.fleet import ReplicaFleet
    from repro.serve.server import ServerConfig
    fl = ReplicaFleet(ServerConfig(vocab_sizes=(64, 64), embed_dim=4,
                                   n_dense=2, bot_mlp=(8, 4), top_mlp=(8, 1),
                                   backends=("full",), cache_capacity=0),
                      n_replicas=2)
    slow = DeadlineBatcher(_rc(init_service_s=0.020))   # replica 0: sheds
    fast = DeadlineBatcher(_rc(init_service_s=0.001))   # replica 1: admits
    got = fl.admit([slow, fast], {"x": np.float32([0])}, now=0.0,
                   deadline=0.010)
    assert got == 1 and len(slow) == 0 and len(fast) == 1
    # terminal only when EVERY replica sheds
    with pytest.raises(LoadShedError, match="all_replicas_shed"):
        fl.admit([slow, fast], {"x": np.float32([1])}, now=0.0,
                 deadline=0.0001)
    assert fl.admit([slow, fast], {"x": np.float32([2])}, now=0.0) == 0


def test_fleet_replay_counts_retries_and_delivers():
    """Replay-level retry-on-replica: replica 0's pessimistic service
    estimate sheds every admission it is offered first; replica 1 serves
    the whole trace, and the report counts the saves."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=128, rate_hz=2000.0, deadline_s=0.010,
                       max_batch=16)
    reqs = _mini_requests(128)
    arr = poisson_arrivals(cfg.rate_hz, 128, seed=6)
    batchers = [DeadlineBatcher(_rc(max_batch=16, init_service_s=0.050)),
                DeadlineBatcher(_rc(max_batch=16, init_service_s=0.001))]
    rep = replay(synthetic_service(base_s=0.001, per_row_s=1e-5),
                 reqs, arr, cfg, n_replicas=2, batchers=batchers)
    assert rep.shed == 0 and rep.completed == 128
    assert rep.retried > 0                          # saved by the retry
    assert rep.replica_batches[0] == 0              # replica 0 never won
    assert rep.replica_batches[1] == rep.batches


def test_fleet_replay_matches_single_server_at_one_replica():
    """n_replicas=1 must degenerate to the single-server replay exactly
    (same batcher default, same timeline, same report fields)."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=256, rate_hz=2000.0, deadline_s=0.025,
                       max_batch=32)
    reqs = _mini_requests(256)
    arr = poisson_arrivals(cfg.rate_hz, 256, seed=1)
    one = replay(synthetic_service(), reqs, arr, cfg)
    fleet_one = replay(None, reqs, arr, cfg, n_replicas=1,
                       services=[synthetic_service()])
    assert one == fleet_one
    # fleet diagnostics never leak into the serialized row
    row = one.as_row()
    for k in ("n_replicas", "retried", "replica_batches", "push_log"):
        assert k not in row


def test_fleet_replay_spreads_load_and_beats_single_p99():
    """Four replicas at a load that saturates one server: the fleet
    completes everything the single server shed, spreads batches across
    replicas, and pulls p99 down."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=512, rate_hz=8000.0, deadline_s=None,
                       max_batch=16, max_queue=32, max_wait_s=0.004)
    reqs = _mini_requests(512)
    arr = poisson_arrivals(cfg.rate_hz, 512, seed=3)
    svc = synthetic_service(base_s=0.008)
    one = replay(svc, reqs, arr, cfg)
    four = replay(svc, reqs, arr, cfg, n_replicas=4)
    assert one.shed > 0                             # one server drowns
    assert four.shed == 0 and four.completed == 512
    assert four.p99_ms < one.p99_ms
    assert all(b > 0 for b in four.replica_batches)


def _busy_push(seconds):
    """A push fn with a real, roughly known wall cost (no sleeping on any
    harness clock — the replay measures the fn's own wall time)."""
    import time as _time

    def fn():
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < seconds:
            pass

    return fn


def test_staggered_rollout_never_overlaps_swaps():
    """The staggered-push invariant, on the virtual timeline: swap k+1
    starts at swap k's measured end, so no two replicas are ever mid-swap
    in the same virtual instant — and the other replicas keep dispatching
    while one swaps."""
    from repro.serve.replay import ReplayConfig, replay, synthetic_service
    cfg = ReplayConfig(n_requests=512, rate_hz=4000.0, deadline_s=None,
                       max_batch=16, max_wait_s=0.004)
    reqs = _mini_requests(512)
    arr = poisson_arrivals(cfg.rate_hz, 512, seed=5)
    rollout = (0.030, [(r, _busy_push(0.002)) for r in range(3)])
    rep = replay(synthetic_service(), reqs, arr, cfg, n_replicas=3,
                 events=[rollout])
    assert rep.pushes == 3 and len(rep.push_log) == 3
    order = [e[0] for e in rep.push_log]
    assert order == [0, 1, 2]                       # rollout order held
    for (_, _, _, end_prev), (_, _, start, _) in zip(rep.push_log,
                                                     rep.push_log[1:]):
        assert start >= end_prev                    # never two mid-swap
    assert all(b > 0 for b in rep.replica_batches)  # fleet kept serving
    # synchronized control: all three swaps anchored at the same instant
    sync = [(0.030, _busy_push(0.002), r) for r in range(3)]
    rep2 = replay(synthetic_service(), reqs, arr, cfg, n_replicas=3,
                  events=sync)
    assert rep2.pushes == 3
    assert all(t == 0.030 for _, t, _, _ in rep2.push_log)


def test_fleet_staggered_push_cache_parity(tmp_path):
    """After a staggered push_all, every replica sits on the same publish
    step, replica scores agree array-exactly, and each replica's hot
    cache is bit-exact against its own uncached path."""
    from repro.data.synthetic_ctr import CtrDataConfig as CDC
    from repro.data.synthetic_ctr import CtrStream as CS
    from repro.serve.fleet import ReplicaFleet
    from repro.serve.server import ServerConfig
    from repro.train.online import OnlineConfig, OnlineTrainer
    vocabs = (1200, 600, 1800)
    pub = str(tmp_path / "pub")
    fl = ReplicaFleet(ServerConfig(
        vocab_sizes=vocabs, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        backends=("full",), cache_capacity=4096, model_dir=pub),
        n_replicas=3)
    stream = CS(CDC(vocab_sizes=vocabs, n_dense=4, batch_size=64,
                    drift_period=10, seed=5))
    tr = OnlineTrainer(fl.replicas[0].recsys_config("full"), stream,
                       OnlineConfig(publish_dir=pub, publish_every=8,
                                    full_every=10))
    tr.run(24)
    reports = fl.push_all("full", step=0)           # baseline full push
    assert [p.kind for p in reports] == ["full"] * 3
    fl.warm_caches([stream.batch_at(i)["sparse"] for i in range(6)])
    reports = fl.push_all("full", step=24)          # staggered delta chain
    assert [p.kind for p in reports] == ["delta"] * 3
    assert fl.pushed_steps("full") == [24, 24, 24]
    b = stream.batch_at(999)
    batch = {"dense": b["dense"], "sparse": b["sparse"]}
    want = fl.replicas[0].score("full", batch, use_cache=False)
    for rep in fl.replicas:                         # per-replica parity
        np.testing.assert_array_equal(
            rep.score("full", batch, use_cache=True), want)
        np.testing.assert_array_equal(
            rep.score("full", batch, use_cache=False), want)


def test_fleet_cell_row_shape(fleet):
    """run_fleet_cell's row: explicit n_replicas/retried columns,
    fleet-pooled hit rate, and the plain-cell schema otherwise."""
    from repro.serve.replay import ReplayConfig, run_fleet_cell
    fleet.reset_caches()
    row = run_fleet_cell(fleet, "full",
                         ReplayConfig(n_requests=256, rate_hz=4000.0,
                                      deadline_s=0.025, max_batch=32),
                         zipf=1.05, warm_batches=16)
    assert row["n_replicas"] == 3
    assert row["completed"] + row["shed"] == 256
    for k in ("retried", "hit_rate", "cache_resident", "p99_ms", "qps"):
        assert k in row, k
    assert "push_log" not in row and "replica_batches" not in row
