"""Streaming online training + zero-downtime model push (ISSUE 9).

Covers the publish→push pipeline end to end:

* ``checkpoint.save_delta``/``restore_delta`` — changed-leaf storage,
  threshold semantics, chain restore onto a base, delta-aware GC — plus
  the ``restore_latest(step=)`` regression edge cases (pinned step
  missing, partial ``tmp-*`` dir racing the GC).
* ``train.online.OnlineTrainer`` — publish cadence, touched-row
  manifests, the zero-grad-optimizer safety gate, and the bit-stability
  premise the cache-invalidation contract rests on.
* ``HotRowCache.invalidate`` — touched rows dropped (exact for ``full``,
  bucket-widened for ``hashed``), untouched entries survive, refetches
  bit-equal to the device gather on the NEW params.
* ``AsyncRouter`` swap semantics — requests admitted before ``push()``
  complete without shedding and never score on mixed params
  (deterministic ``FaultClock``-style clock).
* ``serve.replay`` push events — fire between batches on the virtual
  clock, occupy the server, and feed the push-latency/staleness columns.
* the acceptance scenario (``@pytest.mark.online``): a drifting stream
  trained live with a ``FaultPlan``-injected re-slice mid-run, ≥3 pushes
  hot-swapped into the replay grid, zero dropped in-flight requests, and
  cache-on == cache-off score parity after every push.
"""

import asyncio
import json
import os

import jax
import numpy as np
import pytest

from repro.data.synthetic_ctr import (CtrDataConfig, CtrStream,
                                      RequestStream, poisson_arrivals)
from repro.models.recsys import RecsysConfig
from repro.nn.embeddings import get_backend
from repro.serve.hot_cache import HotRowCache
from repro.serve.replay import (ReplayConfig, measured_service, replay,
                                run_push_cell)
from repro.serve.router import (AsyncRouter, DeadlineBatcher, RouterConfig,
                                stack_and_pad)
from repro.serve.server import EmbeddingServer, ServerConfig
from repro.train import checkpoint as ck
from repro.train import train_loop
from repro.train.elastic import FaultClock, FaultPlan
from repro.train.online import OnlineConfig, OnlineTrainer, RowRecorder
from repro.train.optimizer import OptimizerConfig, make_optimizer

VOCABS = (1200, 600, 1800)


def _model_cfg(embedding="full", vocabs=VOCABS, **kw):
    return RecsysConfig(name=f"online-{embedding}", arch="dlrm",
                        vocab_sizes=vocabs, embed_dim=8, n_dense=4,
                        bot_mlp=(16, 8), top_mlp=(16, 1),
                        embedding=embedding, robe_size=2048, **kw)


def _stream(vocabs=VOCABS, batch=64, drift=10, seed=5, n_dense=4):
    return CtrStream(CtrDataConfig(vocab_sizes=vocabs, n_dense=n_dense,
                                   batch_size=batch, drift_period=drift,
                                   seed=seed))


# ---------------------------------------------------------------------------
# delta checkpoints
# ---------------------------------------------------------------------------

def _t0():
    return {"a": np.arange(6, dtype=np.float32),
            "b": np.ones((2, 3), np.float32),
            "c": np.zeros(4, np.int8)}


def test_save_delta_stores_only_changed_leaves(tmp_path):
    d = str(tmp_path)
    t0 = _t0()
    t1 = dict(t0, a=t0["a"] + 1.0)
    ck.save(d, 0, t0, keep_last=0)
    path = ck.save_delta(d, 10, t1, t0, 0, touched={0: [3, 1]})
    man = json.load(open(os.path.join(path, "manifest.json")))
    # leaves flatten in key order a, b, c — only 'a' changed
    assert [m["changed"] for m in man["leaves"]] == [True, False, False]
    stored = np.load(os.path.join(path, "arrays.npz"))
    assert set(stored.files) == {"leaf_0"}
    assert man["touched"] == {"0": [1, 3]}          # sorted, int
    tree, rman = ck.restore_delta(d, t0)
    assert rman["step"] == 10 and rman["base_full_step"] == 0
    for k in t1:
        assert np.array_equal(tree[k], t1[k]), k


def test_save_delta_threshold_suppresses_small_float_changes(tmp_path):
    d = str(tmp_path)
    t0 = _t0()
    t1 = dict(t0, a=t0["a"] + 1e-6, b=t0["b"] + 1.0)
    ck.save(d, 0, t0, keep_last=0)
    path = ck.save_delta(d, 5, t1, t0, 0, threshold=1e-3)
    man = json.load(open(os.path.join(path, "manifest.json")))
    assert [m["changed"] for m in man["leaves"]] == [False, True, False]
    tree, _ = ck.restore_delta(d, t0)
    # the sub-threshold drift on 'a' is deliberately dropped (bounded
    # staleness); 'b' restores to the new value
    assert np.array_equal(tree["a"], t0["a"])
    assert np.array_equal(tree["b"], t1["b"])


def test_restore_delta_chain_onto_base(tmp_path):
    d = str(tmp_path)
    t0 = _t0()
    t1 = dict(t0, a=t0["a"] + 1.0)
    t2 = dict(t1, b=t1["b"] * 2.0)
    ck.save(d, 0, t0, keep_last=0)
    ck.save_delta(d, 10, t1, t0, 0, touched={0: [1, 2]})
    ck.save_delta(d, 20, t2, t1, 10, touched={1: [7]})
    tree, man = ck.restore_delta(d, t0)
    for k in t2:
        assert np.array_equal(tree[k], t2[k]), k
    assert man["base_full_step"] == 0
    assert [c["step"] for c in man["chain"]] == [10, 20]
    assert man["touched"] == {"0": [1, 2], "1": [7]}      # chain union
    # pinned intermediate step restores the mid-chain state
    mid, mman = ck.restore_delta(d, t0, step=10)
    assert np.array_equal(mid["a"], t1["a"])
    assert np.array_equal(mid["b"], t0["b"])
    assert mman["touched"] == {"0": [1, 2]}


def test_restore_delta_broken_chain_falls_back(tmp_path):
    import shutil
    d = str(tmp_path)
    t0, t1 = _t0(), dict(_t0(), a=_t0()["a"] + 1)
    t2 = dict(t1, b=t1["b"] * 3)
    ck.save(d, 0, t0, keep_last=0)
    ck.save_delta(d, 10, t1, t0, 0)
    ck.save_delta(d, 20, t2, t1, 10)
    shutil.rmtree(os.path.join(d, f"delta-{10:010d}"))    # break the chain
    tree, man = ck.restore_delta(d, t0)
    # delta-20 is unrestorable → falls back to the full base, like
    # restore_latest skips corrupted snapshots
    assert man["step"] == 0
    assert np.array_equal(tree["a"], t0["a"])


def test_gc_deltas_drops_pre_full_chains(tmp_path):
    d = str(tmp_path)
    t0 = _t0()
    ck.save(d, 0, t0, keep_last=0)
    ck.save_delta(d, 10, t0, t0, 0)
    ck.save(d, 20, t0, keep_last=0)
    ck.save_delta(d, 30, t0, t0, 20)
    names = sorted(os.listdir(d))
    assert f"delta-{10:010d}" not in names        # obsolete: pre-newest-full
    assert f"delta-{30:010d}" in names
    assert f"step-{0:010d}" in names and f"step-{20:010d}" in names


# ---------------------------------------------------------------------------
# restore_latest(step=) regression edge cases (satellite)
# ---------------------------------------------------------------------------

def test_restore_latest_pinned_step_missing_returns_none(tmp_path):
    d = str(tmp_path)
    t0 = _t0()
    ck.save(d, 5, t0, keep_last=0)
    assert ck.restore_latest(d, t0, step=999) is None
    got = ck.restore_latest(d, t0, step=5)
    assert got is not None and got[1]["step"] == 5


def test_restore_latest_ignores_partial_tmp_dir(tmp_path):
    """A save killed between tmp-write and rename leaves ``tmp-*`` debris;
    restores must skip it and the next save's GC must reap it."""
    d = str(tmp_path)
    t0 = _t0()
    ck.save(d, 5, t0, keep_last=3)
    partial = os.path.join(d, "tmp-7")
    os.makedirs(partial)
    with open(os.path.join(partial, "manifest.json"), "w") as f:
        f.write('{"step": 7')                       # truncated mid-write
    got = ck.restore_latest(d, t0)
    assert got is not None and got[1]["step"] == 5
    assert ck.restore_latest(d, t0, step=7) is None
    ck.save(d, 9, t0, keep_last=3)                  # GC races the debris
    assert not os.path.exists(partial)
    assert ck.restore_latest(d, t0)[1]["step"] == 9


# ---------------------------------------------------------------------------
# RowRecorder + OnlineTrainer
# ---------------------------------------------------------------------------

def test_row_recorder_records_sparse_and_bags_then_drains():
    rec = RowRecorder(2)
    rec.record({"sparse": np.array([[3, 5], [3, 9]]),
                "sparse_bag": np.array([[[7], [5]]])})
    touched = rec.drain()
    assert touched == {0: [3, 7], 1: [5, 9]}
    assert rec.drain() == {}                        # reset on drain


def test_online_trainer_publish_cadence_and_restore(tmp_path):
    pub = str(tmp_path / "pub")
    tr = OnlineTrainer(_model_cfg("full"), _stream(),
                       OnlineConfig(publish_dir=pub, publish_every=8,
                                    full_every=3))
    rep = tr.run(24)
    assert rep.steps_done == 24
    assert [(p.step, p.kind) for p in rep.publishes] == \
        [(0, "full"), (8, "delta"), (16, "delta"), (24, "full")]
    assert all(p.n_touched > 0 for p in rep.publishes[1:])
    # the newest publish restores bit-identically to the live params
    final = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                         rep.state["params"])
    tree, man = ck.restore_delta(pub, final)
    assert man["step"] == 24
    for got, want in zip(jax.tree.leaves(tree), jax.tree.leaves(final)):
        assert np.array_equal(got, want)


def test_online_trainer_rejects_momentum_optimizers(tmp_path):
    adam = make_optimizer(OptimizerConfig(kind="adam", lr=1e-3))
    with pytest.raises(ValueError, match="zero-gradient"):
        OnlineTrainer(_model_cfg("full"), _stream(),
                      OnlineConfig(publish_dir=str(tmp_path)),
                      optimizer=adam)
    # acknowledged: allowed (full-snapshot pushes clear the cache anyway)
    OnlineTrainer(_model_cfg("full"), _stream(),
                  OnlineConfig(publish_dir=str(tmp_path),
                               unsafe_optimizer=True), optimizer=adam)


def test_online_trainer_untouched_rows_are_bitstable(tmp_path):
    """The premise the exact-invalidation contract rests on: with a
    zero-grad-safe optimizer (adagrad), embedding rows NOT in the touched
    manifest are bit-identical across the publish interval."""
    pub = str(tmp_path / "pub")
    cfg = _model_cfg("full")
    tr = OnlineTrainer(cfg, _stream(), OnlineConfig(publish_dir=pub,
                                                    publish_every=6))
    rep = tr.run(6)
    base, _ = ck.restore_delta(pub, rep.state["params"], step=0)
    newt, man = ck.restore_delta(pub, rep.state["params"], step=6)
    spec = cfg.embedding_spec()
    offsets = spec.offsets
    t_old = np.asarray(jax.tree.leaves(base["embedding"])[0])
    t_new = np.asarray(jax.tree.leaves(newt["embedding"])[0])
    for f, vocab in enumerate(spec.vocab_sizes):
        touched = np.asarray(man["touched"].get(str(f), []), np.int64)
        untouched = np.setdiff1d(np.arange(vocab, dtype=np.int64), touched)
        rows = untouched + int(offsets[f])
        assert np.array_equal(t_old[rows], t_new[rows]), f
        # and the manifest is not vacuous — training moved real rows
        moved = touched + int(offsets[f])
        assert not np.array_equal(t_old[moved], t_new[moved])


def test_online_trainer_qrobe_project_hook(tmp_path):
    """The qrobe int8 substrate trains through the publish path: the
    ``project`` requantization hook runs every step and the published
    tree keeps the int8 code leaves."""
    pub = str(tmp_path / "pub")
    cfg = _model_cfg("qrobe")
    tr = OnlineTrainer(cfg, _stream(), OnlineConfig(publish_dir=pub,
                                                    publish_every=4))
    rep = tr.run(4)
    tree, man = ck.restore_delta(pub, rep.state["params"])
    dtypes = {np.asarray(x).dtype for x in jax.tree.leaves(tree["embedding"])}
    assert np.dtype(np.int8) in dtypes, dtypes
    assert man["step"] == 4


# ---------------------------------------------------------------------------
# HotRowCache invalidation (satellite)
# ---------------------------------------------------------------------------

def _cache_for(kind):
    cfg = _model_cfg(kind)
    spec = cfg.embedding_spec()
    backend = get_backend(kind)
    params = backend.init(jax.random.PRNGKey(0), spec)
    cache = HotRowCache(backend, spec, params, capacity=4096,
                        admit_threshold=1)
    return backend, spec, params, cache


@pytest.mark.parametrize("kind", ["full", "hashed"])
def test_hot_cache_invalidation_on_push(kind):
    backend, spec, params, cache = _cache_for(kind)
    ids = np.arange(64, dtype=np.int64)
    idx = np.stack([ids % v for v in spec.vocab_sizes], axis=1)
    cache.lookup(idx)                               # warm all fields
    resident_before = dict(cache._rows)

    # "train" some rows of field 0: perturb the underlying storage
    touched = np.array([3, 11], np.int64)
    new_params = jax.tree.map(lambda x: np.array(x, copy=True), params)
    if kind == "full":
        table = jax.tree.leaves(new_params)[0]
        table[touched + int(spec.offsets[0])] += 0.5
    else:
        from repro.nn.embedding_backends.hashed import _m, qr_layout
        m = _m(spec)
        _, q_off, _ = qr_layout(spec.vocab_sizes, m)
        new_params["q_table"][touched // m + int(q_off[0])] += 0.5

    cache.set_params(new_params)
    dropped = cache.invalidate(0, touched)
    assert dropped > 0
    if kind == "full":
        # exact: only the touched gids left field 0
        gone = {int(t + spec.offsets[0]) for t in touched}
        assert set(resident_before) - set(cache._rows) == gone
    else:
        # widened: bucket-mates of the touched ids are gone too
        assert dropped >= len(touched)
    # untouched entries survived...
    survivors = set(cache._rows)
    assert survivors and survivors < set(resident_before)
    # ...and every row the cache now serves is bit-equal to the device
    # gather on the NEW params — both the refetched and the surviving ones
    out = cache.lookup(idx)
    dev = np.asarray(backend.lookup(
        jax.tree.map(lambda x: np.asarray(x), new_params), spec,
        idx.astype(np.int32)))
    assert np.array_equal(out, dev)


def test_hot_cache_invalidate_manifest_accepts_json_keys():
    _, spec, _, cache = _cache_for("full")
    idx = np.stack([np.arange(8) % v for v in spec.vocab_sizes], axis=1)
    cache.lookup(idx)
    n = len(cache._rows)
    manifest = json.loads(json.dumps({0: [1, 2], 1: [4]}))   # str keys
    dropped = cache.invalidate_manifest(manifest)
    assert dropped == 3 and len(cache._rows) == n - 3
    assert cache.invalidate(0, []) == 0
    assert cache.clear() == n - 3 and not cache._rows


# ---------------------------------------------------------------------------
# AsyncRouter swap semantics (satellite)
# ---------------------------------------------------------------------------

def test_async_router_swap_between_batches():
    """Requests admitted before ``push()`` complete without LoadShedError
    and never score on mixed params: the swap lands between dispatched
    micro-batches, on a deterministic FaultClock."""
    clock = FaultClock()
    version = {"v": 0}
    batches = []

    def score_fn(batch, n_valid=None):
        batches.append((version["v"], n_valid))
        return np.full(batch["x"].shape[0], float(version["v"]))

    async def scenario():
        router = AsyncRouter(
            score_fn,
            DeadlineBatcher(RouterConfig(max_batch=4, max_queue=64,
                                         max_wait_s=10.0)),
            clock=clock)
        await router.start()
        subs = [asyncio.ensure_future(router.submit({"x": np.zeros(3)}))
                for _ in range(6)]
        # first full batch (4 requests) dispatches on the old params
        await asyncio.gather(*subs[:4])
        clock.advance(0.001)
        swapped = await router.apply(
            lambda: version.__setitem__("v", 1) or "swapped")
        assert swapped == "swapped"
        # the 2 requests admitted BEFORE the push are still queued: they
        # must complete (no shed) on the new params, in one batch
        await router.stop(flush=True)
        return await asyncio.gather(*subs)

    scores = asyncio.run(scenario())
    assert batches == [(0, 4), (1, 2)]              # no mixed-version batch
    assert [float(s) for s in scores] == [0.0] * 4 + [1.0] * 2


# ---------------------------------------------------------------------------
# replay push events on the virtual clock
# ---------------------------------------------------------------------------

def test_replay_push_events_fire_between_batches():
    cfg = ReplayConfig(n_requests=256, rate_hz=3000.0, max_batch=16,
                       seed=9)
    stream = RequestStream(CtrDataConfig(vocab_sizes=(500, 300), n_dense=4,
                                         batch_size=64, seed=9))
    requests = stream.requests(cfg.n_requests)
    arrivals = poisson_arrivals(cfg.rate_hz, cfg.n_requests, seed=1)
    version = {"v": 0}
    seen = []

    def service(batch, n_valid):
        seen.append(version["v"])
        return 1e-3

    span = float(arrivals[-1])
    events = [(span * (k + 1) / 4,
               lambda: version.__setitem__("v", version["v"] + 1))
              for k in range(3)]
    rep = replay(service, requests, arrivals, cfg, events=events)
    assert rep.pushes == 3 and rep.shed == 0
    assert rep.completed + rep.shed == cfg.n_requests
    # versions are non-decreasing (a push never lands mid-batch) and every
    # model generation actually served traffic
    assert seen == sorted(seen) and set(seen) == {0, 1, 2, 3}
    assert rep.mean_staleness_s > 0.0
    row = rep.as_row()
    for k in ("pushes", "push_p50_ms", "push_max_ms", "mean_staleness_s"):
        assert k in row
    # plain replays keep the old row schema
    plain = replay(service, requests, arrivals, cfg).as_row()
    assert "pushes" not in plain and "mean_staleness_s" not in plain


# ---------------------------------------------------------------------------
# EmbeddingServer.push
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pushed(tmp_path_factory):
    """A server plus a finished online-training run publishing into its
    ``model_dir`` (full @ 0, deltas @ 8/16/24 with full_every high)."""
    pub = str(tmp_path_factory.mktemp("pub"))
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        backends=("full",), cache_capacity=4096, model_dir=pub))
    tr = OnlineTrainer(server.recsys_config("full"), _stream(),
                       OnlineConfig(publish_dir=pub, publish_every=8,
                                    full_every=10))
    rep = tr.run(24)
    return server, rep, pub


def _warm_ids(n=8):
    s = _stream()
    return [s.batch_at(i)["sparse"] for i in range(n)]


def test_server_push_swaps_and_invalidates(pushed):
    server, rep, pub = pushed
    assert server.pushed_step("full") is None
    r0 = server.push("full", step=0)                # model_dir default
    assert r0.kind == "full" and r0.cache_cleared
    assert server.pushed_step("full") == 0
    server.cache("full").warm(_warm_ids())
    before = len(server.cache("full")._rows)
    r1 = server.push("full", step=8)
    assert r1.kind == "delta" and not r1.cache_cleared
    assert 0 < r1.invalidated <= before
    # anchored skip: 8 → 24 walks deltas 16 and 24, invalidating both
    # manifests' rows without clearing
    r2 = server.push("full", step=24)
    assert r2.kind == "delta" and not r2.cache_cleared
    assert server.pushed_step("full") == 24
    # parity after the swaps: cache-on == cache-off on the new params
    b = _stream().batch_at(999)
    batch = {"dense": b["dense"], "sparse": b["sparse"]}
    assert np.array_equal(server.score("full", batch, use_cache=True),
                          server.score("full", batch, use_cache=False))


def test_server_push_missing_publish_raises(pushed, tmp_path):
    server, _, _ = pushed
    with pytest.raises(FileNotFoundError):
        server.push("full", step=12345)
    with pytest.raises(FileNotFoundError):
        server.push("full", ckpt_dir=str(tmp_path / "empty"))


def test_server_push_requires_some_dir():
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=(64, 64), embed_dim=8, n_dense=4, bot_mlp=(8, 8),
        backends=("full",), cache_capacity=0))
    with pytest.raises(ValueError, match="model_dir"):
        server.push("full")


def test_server_push_unanchored_delta_clears_cache(tmp_path):
    """A server that skipped past a full base cannot bound what changed
    from the manifests alone — it must drop the whole cache."""
    pub = str(tmp_path / "pub")
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        backends=("full",), cache_capacity=4096, model_dir=pub))
    tr = OnlineTrainer(server.recsys_config("full"), _stream(),
                       OnlineConfig(publish_dir=pub, publish_every=8,
                                    full_every=2))
    tr.run(8)    # publishes: 0 full, 8 delta(0)
    server.push("full", step=8)
    server.cache("full").warm(_warm_ids())
    tr.run(24)   # continues: 16 full, 24 delta(16); GC reaps delta-8
    r = server.push("full", step=24)   # chain anchors at 16; server is at 8
    assert r.kind == "delta" and r.cache_cleared and r.invalidated == 0
    b = _stream().batch_at(999)
    batch = {"dense": b["dense"], "sparse": b["sparse"]}
    assert np.array_equal(server.score("full", batch, use_cache=True),
                          server.score("full", batch, use_cache=False))


# ---------------------------------------------------------------------------
# the acceptance scenario
# ---------------------------------------------------------------------------

@pytest.mark.online
def test_online_end_to_end(tmp_path):
    """ISSUE 9 acceptance: drifting stream trained live (≥3 publishes, one
    FaultPlan-injected re-slice mid-run), publishes hot-swapped into the
    replay grid with zero dropped in-flight requests, and cache-on ==
    cache-off score parity after every push."""
    vocabs = (1200, 600, 1800, 400)
    pub = str(tmp_path / "pub")
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=vocabs, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        backends=("full",), cache_capacity=4096, model_dir=pub))
    stream = CtrStream(CtrDataConfig(vocab_sizes=vocabs, n_dense=4,
                                     batch_size=64, drift_period=10,
                                     seed=5))
    plan = FaultPlan(slow_steps={14: 1.0, 15: 1.0, 16: 1.0}, base_dt=0.01)
    tr = OnlineTrainer(server.recsys_config("full"), stream,
                       OnlineConfig(publish_dir=pub, publish_every=10),
                       train_cfg=train_loop.TrainConfig(
                           checkpoint_every=10_000, straggler_patience=3))
    reslice_steps = []

    def stub_reslice(state, step):
        # the tier-1 elastic stub pattern: same params, re-wrapped step_fn
        # (a real re-slice rebuilds the mesh; test_elastic covers that)
        reslice_steps.append(step)
        return state, plan.wrap_step_fn(tr._step_fn)

    rep = tr.run(40, fault_plan=plan, reslice_fn=stub_reslice,
                 ckpt_dir=str(tmp_path / "ft"))
    assert rep.reslices == 1 and reslice_steps == [17]
    assert [p.step for p in rep.publishes] == [0, 10, 20, 30, 40]

    probe = stream.batch_at(999)
    probe_batch = {"dense": probe["dense"], "sparse": probe["sparse"]}
    parity_log = []

    def push_and_check(step):
        r = server.push("full", step=step)
        on = server.score("full", probe_batch, use_cache=True)
        off = server.score("full", probe_batch, use_cache=False)
        assert np.array_equal(on, off), f"parity broken after push {step}"
        parity_log.append((step, r.kind))

    rcfg_data = CtrDataConfig(vocab_sizes=vocabs, n_dense=4,
                              batch_size=256, drift_period=2, seed=23)
    for policy in ("deadline", "fixed"):
        server.push("full", step=0)
        rstream = RequestStream(rcfg_data)
        cfg = ReplayConfig(n_requests=512, rate_hz=2000.0, policy=policy,
                           max_batch=32, max_queue=1024)
        requests = rstream.requests(cfg.n_requests)
        arrivals = poisson_arrivals(cfg.rate_hz, cfg.n_requests, seed=3)
        server.cache("full").warm(rstream.id_batches(8))
        score_fn = server.score_fn("full")
        batch, nv = stack_and_pad(requests[:1], cfg.max_batch)
        score_fn(batch, n_valid=nv)                  # compile off-timeline
        span = float(arrivals[-1])
        events = [(span * (k + 1) / 5, lambda s=s: push_and_check(s))
                  for k, s in enumerate([10, 20, 30, 40])]
        r = replay(measured_service(score_fn), requests, arrivals, cfg,
                   events=events)
        # zero dropped in-flight requests: everything admitted completes
        assert r.shed == 0 and r.completed == cfg.n_requests
        assert r.pushes == 4 and r.mean_staleness_s > 0.0
    assert len(parity_log) == 8            # 4 checked pushes × 2 policies
    assert {k for _, k in parity_log} == {"delta"}


@pytest.mark.online
def test_run_push_cell_produces_bench_row(tmp_path):
    """The push cell's row: online-train then replay drifting traffic
    with scheduled pushes; the row carries the push columns."""
    pub = str(tmp_path / "pub")
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=8, n_dense=4, bot_mlp=(16, 8),
        backends=("full",), cache_capacity=4096))
    tr = OnlineTrainer(server.recsys_config("full"),
                       _stream(batch=256, drift=8, seed=11),
                       OnlineConfig(publish_dir=pub, publish_every=8))
    tr.run(24)
    row = run_push_cell(server, "full",
                        ReplayConfig(n_requests=512, rate_hz=2000.0),
                        publish_dir=pub,
                        push_steps=[p.step for p in tr.publishes],
                        drift_period=2, warm_batches=8)
    assert row["pushes"] == 3 and row["shed"] == 0
    assert row["push_steps"] == 4 and row["drift_period"] == 2
    for k in ("push_p50_ms", "push_max_ms", "mean_staleness_s",
              "hit_rate"):
        assert k in row
    assert row["mean_staleness_s"] > 0.0
