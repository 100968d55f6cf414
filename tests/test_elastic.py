"""Elastic re-slice + deterministic fault injection (``repro.train.elastic``).

Two tiers:

* in-process tests (tier-1): the straggler EWMA regression suite, the
  re-slice trigger logic with a stub ``reslice_fn``, and the fault paths
  the train_loop docstring has always claimed — NaN → restore + skip,
  bounded ``max_restarts``, async-checkpoint atomicity.  All step timing
  runs on ``FaultClock``, so nothing here depends on the wall.
* ``@pytest.mark.elastic`` subprocess tests (own CI job, deselected from
  the default run via addopts): the end-to-end 16→8-device re-slice for
  every embedding backend, and restore-onto-a-degraded-mesh for the two
  placements that actually move bytes (full ``placement="2d"``, ZeRO-3
  ROBE) with HLO collective checks, ``test_distributed.py`` style.
"""

import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ck
from repro.train import elastic
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)

from conftest import run_forced_subprocess

BACKENDS = ("full", "robe", "hashed", "tt")


def _run_sub(body: str, n_devices: int = 16):
    return run_forced_subprocess(body, n_devices=n_devices)


def _toy_problem(n_dense: int = 4):
    from repro.models.recsys import RecsysConfig, init_params, loss_fn
    vocabs = (500, 300, 800)
    cfg = RecsysConfig(name="d", arch="dlrm", n_dense=n_dense,
                       bot_mlp=(16, 8), top_mlp=(16, 1), embed_dim=8,
                       vocab_sizes=vocabs, robe_size=2048, robe_block=8,
                       embedding="robe")
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = CtrStream(CtrDataConfig(vocab_sizes=vocabs, n_dense=n_dense,
                                     batch_size=256))
    return cfg, params, stream, loss_fn


def _fresh(params):
    """Fresh buffers — ``build_train_step`` donates its input state, so a
    params tree can seed at most one run."""
    return jax.tree.map(jnp.copy, params)


def _loop(cfg, params, loss_fn, *, checkpoint_every=5, max_restarts=3,
          patience=3):
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.05))
    tc = TrainConfig(checkpoint_every=checkpoint_every,
                     max_restarts=max_restarts,
                     straggler_factor=3.0, straggler_patience=patience)
    step_fn = build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)
    return opt, tc, step_fn


# ---------------------------------------------------------------------------
# straggler monitor (satellite: EWMA false positives)
# ---------------------------------------------------------------------------

def test_straggler_ewma_ignores_compile_and_ckpt_steps():
    """A synthetic step-time trace where only the compile step and the
    steps right after a checkpoint save are slow must flag NOTHING — those
    dts are warm-up, not stragglers."""
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=5)
    # step 0 = compile (2s); every step following a save at 5,10,…,35 pays
    # ckpt I/O (0.5s); everything else is a flat 10ms
    slow = {0: 2.0}
    slow.update({s: 0.5 for s in range(5, 40, 5)})
    plan = elastic.FaultPlan(slow_steps=slow, base_dt=0.01)
    tmp = tempfile.mkdtemp()
    try:
        state = init_state(_fresh(params), opt, tc)
        rep = run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 40,
                  tc, ckpt_dir=tmp, timer=plan.clock)
        assert rep.steps_done == 40
        assert rep.straggler_steps == 0, rep.straggler_steps
        assert rep.reslices == 0
    finally:
        shutil.rmtree(tmp)


def test_straggler_flags_genuinely_slow_step():
    """Positive control: a slow step that is NOT save-adjacent still
    flags, and with reslice_fn=None the monitor stays passive."""
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=100)
    plan = elastic.FaultPlan(slow_steps={7: 1.0}, base_dt=0.01)
    state = init_state(_fresh(params), opt, tc)
    rep = run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 15, tc,
              timer=plan.clock)
    assert rep.straggler_steps == 1
    assert rep.reslices == 0                 # no reslice_fn: count only


def test_reslice_hook_fires_after_patience_and_resets():
    """``straggler_patience`` consecutive flags hand (state, step_fn) to
    ``reslice_fn``; the loop resumes the same global step and the EWMA
    resets so the rebuild does not immediately re-trigger."""
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=100,
                             patience=3)
    plan = elastic.FaultPlan(slow_steps={6: 1.0, 7: 1.0, 8: 1.0},
                             base_dt=0.01)
    calls = []

    def stub_reslice(state, step):
        calls.append(step)
        return state, plan.wrap_step_fn(step_fn)

    state = init_state(_fresh(params), opt, tc)
    rep = run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 20, tc,
              reslice_fn=stub_reslice, timer=plan.clock)
    assert calls == [9]                      # right after the 3rd flag
    assert rep.reslices == 1
    assert rep.steps_done == 20              # same global step count
    assert rep.straggler_steps == 3


def test_reslice_still_fires_when_trigger_step_goes_nan():
    """Slow AND corrupting hardware is one failure, not two: a NaN loss on
    the step that reaches ``straggler_patience`` must not swallow the
    pending re-slice."""
    cfg, params, stream, loss_fn = _toy_problem(n_dense=4)
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=100,
                             patience=3)
    plan = elastic.FaultPlan(slow_steps={6: 1.0, 7: 1.0, 8: 1.0},
                             nan_steps={8}, base_dt=0.01)
    calls = []

    def stub_reslice(state, step):
        calls.append(step)
        return state, plan.wrap_step_fn(step_fn)

    state = init_state(_fresh(params), opt, tc)
    rep = run(state, plan.wrap_step_fn(step_fn),
              plan.wrap_batch_at(stream.batch_at), 20, tc,
              reslice_fn=stub_reslice, timer=plan.clock)
    assert calls == [9]
    assert rep.reslices == 1 and rep.nan_events == 1
    assert rep.steps_done == 20


def test_reslice_nan_trigger_on_ckpt_boundary_still_flushes():
    """A NaN trigger step that lands on a checkpoint boundary never ran
    the boundary save — the reslice flush must still write the snapshot
    the rebuild is contracted to restore."""
    cfg, params, stream, loss_fn = _toy_problem(n_dense=4)
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=10,
                             patience=3)
    plan = elastic.FaultPlan(slow_steps={7: 1.0, 8: 1.0, 9: 1.0},
                             nan_steps={9}, base_dt=0.01)
    tmp = tempfile.mkdtemp()
    calls = []

    def stub_reslice(state, step):
        # contract: the checkpoint for THIS step is on disk when called
        assert os.path.isdir(os.path.join(tmp, f"step-{step:010d}")), \
            os.listdir(tmp)
        calls.append(step)
        return state, plan.wrap_step_fn(step_fn)

    try:
        state = init_state(_fresh(params), opt, tc)
        rep = run(state, plan.wrap_step_fn(step_fn),
                  plan.wrap_batch_at(stream.batch_at), 20, tc,
                  ckpt_dir=tmp, reslice_fn=stub_reslice, timer=plan.clock)
        assert calls == [10]
        assert rep.reslices == 1 and rep.nan_events == 1
    finally:
        shutil.rmtree(tmp)


def test_restart_rewind_resets_straggler_monitor():
    """A restart rewinds and replays steps: stale consecutive-flag counts
    must not leak across it and fire a re-slice on fewer than `patience`
    genuinely consecutive post-restart flags."""
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=5,
                             patience=3)
    plan = elastic.FaultPlan(slow_steps={6: 1.0, 7: 1.0},
                             raise_steps={8: "node died"}, base_dt=0.01)
    calls = []

    def stub_reslice(state, step):
        calls.append(step)
        return state, plan.wrap_step_fn(step_fn)

    tmp = tempfile.mkdtemp()
    try:
        state = init_state(_fresh(params), opt, tc)
        rep = run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 20,
                  tc, ckpt_dir=tmp, reslice_fn=stub_reslice,
                  timer=plan.clock)
        # only ever 2 consecutive flags (replayed after the rewind too):
        # the monitor must never reach patience=3
        assert calls == [], calls
        assert rep.restarts == 1 and rep.reslices == 0
    finally:
        shutil.rmtree(tmp)


def test_restore_latest_accepts_live_shardings_with_none_leaves():
    """The NaN/exception restore paths re-place arrays onto the state's
    own resident shardings; leaves without one (host numpy) pass through."""
    from repro.train.train_loop import _live_shardings
    tmp = tempfile.mkdtemp()
    try:
        tree = {"a": jnp.arange(4.0), "b": np.arange(3.0)}
        ck.save(tmp, 1, tree)
        sh = _live_shardings(tree)
        assert sh["a"] is not None and sh["b"] is None
        restored, manifest = ck.restore_latest(tmp, tree, shardings=sh)
        assert manifest["step"] == 1
        assert restored["a"].sharding == tree["a"].sharding
        np.testing.assert_array_equal(np.asarray(restored["b"]),
                                      np.arange(3.0))
    finally:
        shutil.rmtree(tmp)


def test_failing_reslice_is_a_restart_not_a_retry_storm():
    """A reslice_fn that raises is absorbed by the restart machinery and
    must NOT be re-invoked on every following step: the monitor resets
    before the hook runs, so re-triggering takes another ``patience``
    flagged steps."""
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=100,
                             patience=3)
    plan = elastic.FaultPlan(slow_steps={6: 1.0, 7: 1.0, 8: 1.0},
                             base_dt=0.01)
    calls = []

    def broken_reslice(state, step):
        calls.append(step)
        raise RuntimeError("no spare capacity")

    tmp = tempfile.mkdtemp()
    try:
        state = init_state(_fresh(params), opt, tc)
        rep = run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 20,
                  tc, ckpt_dir=tmp, reslice_fn=broken_reslice,
                  timer=plan.clock)
        assert calls == [9]                  # invoked exactly once
        assert rep.restarts == 1
        assert rep.reslices == 0
        assert rep.steps_done == 20
    finally:
        shutil.rmtree(tmp)


# ---------------------------------------------------------------------------
# fault paths the docstring claims (satellite: NaN / restarts / atomicity)
# ---------------------------------------------------------------------------

def test_nan_batch_restores_and_skips():
    cfg, params, stream, loss_fn = _toy_problem(n_dense=4)
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=5)
    tmp = tempfile.mkdtemp()
    try:
        plan = elastic.FaultPlan(nan_steps={12})
        state = init_state(_fresh(params), opt, tc)
        rep = run(state, plan.wrap_step_fn(step_fn),
                  plan.wrap_batch_at(stream.batch_at), 20, tc,
                  ckpt_dir=tmp, timer=plan.clock)
        assert rep.nan_events == 1
        assert rep.steps_done == 20
        assert len(rep.losses) == 19         # the poisoned step is skipped
        assert np.isfinite(rep.losses).all()
        # the restore genuinely rewound: without a checkpoint the loop
        # keeps the (step-12) state and the post-fault trajectory differs
        plan2 = elastic.FaultPlan(nan_steps={12})
        state2 = init_state(_fresh(params), opt, tc)
        rep2 = run(state2, plan2.wrap_step_fn(step_fn),
                   plan2.wrap_batch_at(stream.batch_at), 20, tc,
                   timer=plan2.clock)
        assert rep2.nan_events == 1
        tail = np.asarray(rep.losses[-7:])
        tail2 = np.asarray(rep2.losses[-7:])
        assert np.max(np.abs(tail - tail2)) > 0.0
    finally:
        shutil.rmtree(tmp)


def test_nan_restore_is_deterministic():
    """Same plan, same stream → bit-identical loss trajectory (the whole
    point of a *deterministic* fault harness)."""
    cfg, params, stream, loss_fn = _toy_problem(n_dense=4)
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=5)
    reps = []
    for _ in range(2):
        tmp = tempfile.mkdtemp()
        try:
            plan = elastic.FaultPlan(nan_steps={7})
            state = init_state(_fresh(params), opt, tc)
            reps.append(run(state, plan.wrap_step_fn(step_fn),
                            plan.wrap_batch_at(stream.batch_at), 15, tc,
                            ckpt_dir=tmp, timer=plan.clock))
        finally:
            shutil.rmtree(tmp)
    np.testing.assert_array_equal(np.asarray(reps[0].losses),
                                  np.asarray(reps[1].losses))


def test_bounded_restarts_on_raised_exceptions():
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=5,
                             max_restarts=3)
    tmp = tempfile.mkdtemp()
    try:
        plan = elastic.FaultPlan(
            raise_steps={6: "node died", 7: "node died", 8: "node died"})
        state = init_state(_fresh(params), opt, tc)
        rep = run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 20,
                  tc, ckpt_dir=tmp, timer=plan.clock)
        assert rep.restarts == 3
        assert rep.steps_done == 20
    finally:
        shutil.rmtree(tmp)


def test_max_restarts_exceeded_raises():
    cfg, params, stream, loss_fn = _toy_problem()
    opt, tc, step_fn = _loop(cfg, params, loss_fn, checkpoint_every=5,
                             max_restarts=3)
    tmp = tempfile.mkdtemp()
    try:
        plan = elastic.FaultPlan(
            raise_steps={5: "x", 6: "x", 7: "x", 8: "x"})
        state = init_state(_fresh(params), opt, tc)
        with pytest.raises(RuntimeError):
            run(state, plan.wrap_step_fn(step_fn), stream.batch_at, 20,
                tc, ckpt_dir=tmp, timer=plan.clock)
    finally:
        shutil.rmtree(tmp)


def test_async_checkpoint_atomicity_kill_before_rename(monkeypatch):
    """A crash between the tmp-write and the rename must leave the
    previous snapshot as the restore target; the half-written tmp dir is
    never picked up and is GC'd by the next successful save."""
    tmp = tempfile.mkdtemp()
    try:
        tree = {"a": jnp.arange(4.0)}
        ck.save(tmp, 1, tree)
        real_rename = os.rename

        def killed(src, dst, *a, **kw):
            if os.path.basename(str(src)).startswith("tmp-"):
                raise RuntimeError("killed between write and rename")
            return real_rename(src, dst, *a, **kw)

        monkeypatch.setattr(os, "rename", killed)
        saver = ck.AsyncCheckpointer(tmp)
        saver.save(2, jax.tree.map(lambda x: x * 2, tree))
        with pytest.raises(RuntimeError):
            saver.wait()                     # the async error surfaces
        monkeypatch.undo()
        # restore sees step 1, not the orphaned tmp-2
        restored, manifest = ck.restore_latest(tmp, tree)
        assert manifest["step"] == 1
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.arange(4.0))
        assert any(d.startswith("tmp-2") for d in os.listdir(tmp))
        ck.save(tmp, 3, tree)                # next good save GCs the orphan
        assert not any(d.startswith("tmp-") for d in os.listdir(tmp))
    finally:
        shutil.rmtree(tmp)


def test_restore_latest_pinned_step():
    tmp = tempfile.mkdtemp()
    try:
        tree = {"a": jnp.arange(3.0)}
        ck.save(tmp, 10, tree)
        ck.save(tmp, 20, jax.tree.map(lambda x: x + 1, tree))
        _, manifest = ck.restore_latest(tmp, tree, step=10)
        assert manifest["step"] == 10
        assert ck.restore_latest(tmp, tree, step=15) is None
    finally:
        shutil.rmtree(tmp)


# ---------------------------------------------------------------------------
# spec re-resolution units (no devices needed beyond the forced 8)
# ---------------------------------------------------------------------------

def test_degrade_mesh_and_prune_specs():
    from jax.sharding import PartitionSpec as P

    from repro.dist import api as dist
    from repro.launch.mesh import degrade_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    half = degrade_mesh(mesh, "model")
    assert dict(half.shape) == {"data": 2, "model": 2}
    assert half.axis_names == ("data", "model")
    with pytest.raises(ValueError):
        degrade_mesh(mesh, "pod")
    with pytest.raises(ValueError):
        degrade_mesh(mesh, "model", keep=4)

    SDS = jax.ShapeDtypeStruct
    shapes = {"table": SDS((12, 8), jnp.float32),   # 12 % (2·2)=0 → keeps
              "odd": SDS((6, 8), jnp.float32),      # 6 % 4 ≠ 0 → replicates
              "pod_sharded": SDS((8, 8), jnp.float32)}
    specs = {"table": P(("data", "model"), None),
             "odd": P(("data", "model"), None),
             "pod_sharded": P(("pod", "data"), None)}   # pod axis is gone
    out = dist.prune_specs(specs, shapes, half)
    assert out["table"] == P(("data", "model"), None)
    assert out["odd"] == P(None, None)
    assert out["pod_sharded"] == P("data", None)


def test_train_state_specs_shards_error_feedback_over_data():
    """The grad-compression error-feedback residuals are model-sized and
    live sharded over the data axes — a re-slice restore must keep them
    there, not replicate them onto the capacity-reduced mesh."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.api import default_rules

    state = {"params": {"w": jnp.zeros((4, 4))},
             "opt": {"m": {"w": jnp.zeros((4, 4))}},
             "step": jnp.zeros((), jnp.int32),
             "ef": {"w": jnp.zeros((2, 4, 4))}}
    pspecs = {"w": P(None, "model")}
    specs = elastic.train_state_specs(state, pspecs, default_rules())
    assert specs["params"] == pspecs
    assert specs["opt"]["m"]["w"] == P(None, "model")
    assert specs["step"] == P()
    assert specs["ef"]["w"] == P("data")
    # without rules the ef fallback stays replicated (legacy callers)
    assert elastic.train_state_specs(state, pspecs)["ef"]["w"] == P()


def test_backend_param_specs_re_resolve_on_degraded_mesh():
    """Every backend's param_specs(..., mesh=) must stay legal when an
    axis disappears — the re-slice contract (ROADMAP §Elastic training)."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from repro.dist.api import default_rules
    from repro.nn.embedding_backends import get_backend
    from repro.nn.embeddings import EmbeddingSpec
    from repro.core.robe import RobeSpec

    rules = default_rules()
    mesh = make_mesh((2, 4), ("data", "model"))
    robe = RobeSpec(size=512, block_size=8, seed=11)
    base = EmbeddingSpec(vocab_sizes=(64, 96, 32), dim=8, kind="robe",
                         robe=robe)
    for kind in BACKENDS:
        spec = dataclasses.replace(base, kind=kind)
        tree = get_backend(kind).param_specs(spec, rules, mesh=mesh)
        # same tree as production when every axis survives
        assert tree == get_backend(kind).param_specs(spec, rules)
    # full 2d keeps (data, model); z3 robe keeps model
    spec2d = dataclasses.replace(base, kind="full", placement="2d")
    assert get_backend("full").param_specs(spec2d, rules, mesh=mesh) == \
        {"table": P(("data", "model"), None)}
    z3 = dataclasses.replace(base, kind="robe", placement="model")
    assert get_backend("robe").param_specs(z3, rules, mesh=mesh) == \
        {"memory": P("model")}
    # a mesh with no model axis: sharded placements fall back
    dp_only = make_mesh((8,), ("data",))
    assert get_backend("robe").param_specs(z3, rules, mesh=dp_only) == \
        {"memory": P()}
    assert get_backend("full").param_specs(spec2d, rules, mesh=dp_only) == \
        {"table": P("data", None)}


# ---------------------------------------------------------------------------
# end-to-end: injected straggler → 16→8-device re-slice, per backend
# ---------------------------------------------------------------------------

_E2E_BODY = """
    from repro.dist import api as dist
    from repro.dist.param_specs import recsys_specs
    from repro.launch.mesh import degrade_context
    from repro.models.recsys import RecsysConfig, init_params, loss_fn
    from repro.data.synthetic_ctr import CtrDataConfig, CtrStream
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_loop import (TrainConfig, build_train_step,
                                        init_state, run)
    from repro.train import elastic
    from repro.train import checkpoint as ck

    vocabs = (512, 256, 384)
    cfg = RecsysConfig(name="e", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                       top_mlp=(16, 1), embed_dim=8, vocab_sizes=vocabs,
                       embedding="{backend}", robe_size=2048, robe_block=8,
                       compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = CtrStream(CtrDataConfig(vocab_sizes=vocabs, n_dense=4,
                                     batch_size=256))
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.05))
    tc = TrainConfig(checkpoint_every=5, straggler_factor=3.0,
                     straggler_patience=3)
    emb_spec = cfg.embedding_spec()
    pshapes = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                             jax.random.PRNGKey(0))

    def specs_for(ctx, state):
        pspecs = recsys_specs(pshapes, ctx.rules, embedding_spec=emb_spec,
                              mesh=ctx.mesh)
        return elastic.train_state_specs(state, pspecs, ctx.rules)

    def build_step(ctx):
        return build_train_step(lambda p, b: loss_fn(p, cfg, b), opt, tc)

    tmp = tempfile.mkdtemp()
    mesh16 = make_mesh((2, 8), ("data", "model"))
    ctx16 = dist.DistContext(mesh=mesh16, rules=dist.default_rules())
    # three consecutive slow steps at 7-9 trip patience=3 right at the
    # step-10 checkpoint boundary
    plan = elastic.FaultPlan(slow_steps={{7: 1.0, 8: 1.0, 9: 1.0}})
    ctrl = elastic.ResliceController(state_specs=specs_for,
                                     build_step=build_step, ckpt_dir=tmp)
    with dist.use(ctx16):
        state = init_state(params, opt, tc)
        rep = run(state, plan.wrap_step_fn(build_step(ctx16)),
                  stream.batch_at, 20, tc, ckpt_dir=tmp,
                  reslice_fn=ctrl, timer=plan.clock)
        # the swap is visible to the enclosing block: survivors only
        assert dist.current().n_devices == 8, dist.current().mesh
    assert rep.reslices == 1 and rep.steps_done == 20, rep
    assert len(rep.losses) == 20
    ev = ctrl.events[0]
    assert ev.devices_before == 16 and ev.devices_after == 8, ev
    # resumed at the SAME global step it checkpointed
    assert ev.step == 10 and ev.restored_step == 10, ev

    # clean run: restore the SAME snapshot onto a fresh 8-device context
    ctx8 = degrade_context(ctx16)
    with dist.use(ctx8):
        state_t = init_state(params, opt, tc)
        restored = ck.restore_onto(tmp, state_t, ctx8,
                                   specs_for(ctx8, state_t), step=10)
        assert restored is not None
        state_c, manifest = restored
        assert int(manifest["step"]) == 10
        rep_c = run(state_c, build_step(ctx8), stream.batch_at, 20, tc)
    err = np.max(np.abs(np.asarray(rep.losses[10:])
                        - np.asarray(rep_c.losses)))
    assert err < 1e-5, err
    shutil.rmtree(tmp)
    print("ok", err)
"""


@pytest.mark.elastic
@pytest.mark.parametrize("backend", BACKENDS)
def test_elastic_reslice_16_to_8(backend):
    """Acceptance: an injected straggler triggers a 16→8-device re-slice
    and training resumes at the same global step with a loss trajectory
    within 1e-5 (f32) of a clean run restored from the same checkpoint."""
    out = _run_sub(_E2E_BODY.format(backend=backend), n_devices=16)
    assert "ok" in out


# ---------------------------------------------------------------------------
# restore-onto-a-degraded-mesh, the two placements that move bytes
# ---------------------------------------------------------------------------

@pytest.mark.elastic
def test_restore_onto_degraded_mesh_full_2d():
    """full placement="2d": rows re-shard over the surviving (data, model)
    mesh; the compiled lookup still carries the index all-gather + batch
    reduce-scatter, and the loss matches the single-device value."""
    _run_sub("""
        from repro.dist import api as dist
        from repro.dist.param_specs import recsys_specs
        from repro.launch.mesh import degrade_context
        from repro.models.recsys import RecsysConfig, init_params, loss_fn
        from repro.train import checkpoint as ck
        kw = dict(name="d", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                  top_mlp=(16, 1), embed_dim=8, vocab_sizes=(64, 96, 32),
                  compute_dtype=jnp.float32)
        cfg = RecsysConfig(embedding="full", full_table_shard="2d", **kw)
        spec = cfg.embedding_spec()
        params = init_params(jax.random.PRNGKey(0), cfg)
        rs = np.random.RandomState(0)
        batch = {"dense": jnp.asarray(rs.randn(16, 4), jnp.float32),
                 "sparse": jnp.asarray(rs.randint(0, 30, (16, 3)),
                                       jnp.int32),
                 "label": jnp.asarray(rs.randint(0, 2, (16,)), jnp.int32)}
        l_ref, _ = loss_fn(params, cfg, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        pspecs = recsys_specs(params, ctx.rules, embedding_spec=spec,
                              mesh=ctx.mesh)
        # place + checkpoint on the healthy mesh
        placed = jax.tree.map(
            jax.device_put, params,
            dist.named_shardings(ctx, dist.prune_specs(pspecs, params,
                                                       ctx.mesh)))
        tmp = tempfile.mkdtemp()
        ck.save(tmp, 1, placed)

        # half the model axis dies: restore onto the survivors
        ctx_d = degrade_context(ctx)
        assert ctx_d.n_devices == 4
        pspecs_d = recsys_specs(params, ctx_d.rules, embedding_spec=spec,
                                mesh=ctx_d.mesh)
        restored, _ = ck.restore_onto(tmp, params, ctx_d, pspecs_d)
        sh = restored["embedding"]["table"].sharding
        assert sh.mesh.devices.size == 4, sh
        assert sh.spec == P(("data", "model"), None), sh
        with dist.use(ctx_d):
            step = jax.jit(lambda p, b: loss_fn(p, cfg, b))
            l_d, _ = step(restored, batch)
            hlo = step.lower(restored, batch).compile().as_text()
        # the 2d exchange is real on the degraded mesh too
        assert "all-gather" in hlo
        assert "reduce-scatter" in hlo
        assert abs(float(l_ref) - float(l_d)) < 1e-5, (float(l_ref),
                                                       float(l_d))
        shutil.rmtree(tmp)
        print("ok")
    """, n_devices=8)


@pytest.mark.elastic
def test_restore_onto_degraded_mesh_robe_z3():
    """robe_shard_model=True: the ZeRO-3 array re-shards over the smaller
    model axis; the per-step all-gather survives in the HLO and the loss
    matches the replicated value."""
    _run_sub("""
        from repro.dist import api as dist
        from repro.dist.param_specs import recsys_specs
        from repro.launch.mesh import degrade_context
        from repro.models.recsys import RecsysConfig, init_params, loss_fn
        from repro.train import checkpoint as ck
        kw = dict(name="d", arch="dlrm", n_dense=4, bot_mlp=(16, 8),
                  top_mlp=(16, 1), embed_dim=8, vocab_sizes=(64, 96, 32),
                  robe_size=512, robe_block=8, compute_dtype=jnp.float32)
        cfg = RecsysConfig(embedding="robe", robe_shard_model=True, **kw)
        spec = cfg.embedding_spec()
        params = init_params(jax.random.PRNGKey(0), cfg)
        rs = np.random.RandomState(0)
        batch = {"dense": jnp.asarray(rs.randn(16, 4), jnp.float32),
                 "sparse": jnp.asarray(rs.randint(0, 30, (16, 3)),
                                       jnp.int32),
                 "label": jnp.asarray(rs.randint(0, 2, (16,)), jnp.int32)}
        cfg_rep = RecsysConfig(embedding="robe", **{k: v for k, v in
                               kw.items()})
        l_ref, _ = loss_fn(params, cfg_rep, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = dist.DistContext(mesh=mesh, rules=dist.default_rules())
        pspecs = recsys_specs(params, ctx.rules, embedding_spec=spec,
                              mesh=ctx.mesh)
        placed = jax.tree.map(
            jax.device_put, params,
            dist.named_shardings(ctx, dist.prune_specs(pspecs, params,
                                                       ctx.mesh)))
        tmp = tempfile.mkdtemp()
        ck.save(tmp, 1, placed)

        ctx_d = degrade_context(ctx)
        pspecs_d = recsys_specs(params, ctx_d.rules, embedding_spec=spec,
                                mesh=ctx_d.mesh)
        restored, _ = ck.restore_onto(tmp, params, ctx_d, pspecs_d)
        sh = restored["embedding"]["memory"].sharding
        assert sh.mesh.devices.size == 4, sh
        assert sh.spec == P("model"), sh
        with dist.use(ctx_d):
            step = jax.jit(lambda p, b: loss_fn(p, cfg, b))
            l_d, _ = step(restored, batch)
            hlo = step.lower(restored, batch).compile().as_text()
        assert "all-gather" in hlo           # the ZeRO-3 gather survives
        assert abs(float(l_ref) - float(l_d)) < 1e-5, (float(l_ref),
                                                       float(l_d))
        shutil.rmtree(tmp)
        print("ok")
    """, n_devices=8)
