"""Launch and serving units: cell registry, serving batcher, elastic
checkpoint resume, the weighted ROBE bag lookup."""

import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np


def test_registry_covers_all_assigned_cells():
    from repro.configs import all_arch_ids, get_arch
    assert len(all_arch_ids()) == 10
    total_cells = sum(len(get_arch(a).shapes) for a in all_arch_ids())
    assert total_cells == 40


def test_micro_batcher_pads_and_orders():
    from repro.serve.serving import MicroBatcher
    calls = []

    def score(batch):
        calls.append(batch["x"].shape)
        return jnp.asarray(batch["x"][:, 0], jnp.float32)

    mb = MicroBatcher(batch_size=4, score_fn=score)
    for i in range(6):
        mb.submit({"x": np.asarray([i, 0], np.float32)})
    out = mb.flush()
    assert len(out) == 6
    assert [float(o) for o in out] == [0, 1, 2, 3, 4, 5]
    assert all(s == (4, 2) for s in calls)       # fixed compiled shape


def test_micro_batcher_pad_tail_repeats_last_row():
    """The short tail pads by repeating the last request up to the compiled
    shape, and only the real rows come back."""
    from repro.serve.serving import MicroBatcher
    seen = []

    def score(batch):
        seen.append(np.asarray(batch["x"]))
        return jnp.asarray(batch["x"][:, 0], jnp.float32)

    mb = MicroBatcher(batch_size=4, score_fn=score)
    for i in range(3):                   # 3 < batch_size: pure pad-tail path
        mb.submit({"x": np.asarray([i, 9], np.float32)})
    out = mb.flush()
    assert [float(o) for o in out] == [0, 1, 2]
    assert seen[0].shape == (4, 2)
    np.testing.assert_array_equal(seen[0][3], seen[0][2])   # repeated tail


def test_micro_batcher_rejects_mismatched_keys():
    """A bad request is rejected at submit (clear error, queue unpoisoned)
    instead of surfacing as a KeyError deep in np.stack at flush."""
    from repro.serve.serving import MicroBatcher
    import pytest
    mb = MicroBatcher(batch_size=4,
                      score_fn=lambda b: jnp.asarray(b["x"][:, 0],
                                                     jnp.float32))
    mb.submit({"x": np.asarray([7, 0], np.float32)})
    with pytest.raises(ValueError, match="keys"):
        mb.submit({"x": np.zeros(2, np.float32), "dense": np.zeros(1)})
    out = mb.flush()                     # queued request still servable
    assert [float(o) for o in out] == [7]


def test_elastic_checkpoint_resume_across_shapes():
    """A checkpoint written under one 'mesh' restores onto another: arrays
    are saved in logical shapes, the loader re-applies new shardings."""
    from repro.train import checkpoint as ck
    tmp = tempfile.mkdtemp()
    try:
        tree = {"w": jnp.arange(32.0).reshape(8, 4), "step": jnp.int32(7)}
        ck.save(tmp, 7, tree)
        # "new mesh": single-device shardings (CPU) — device_put path
        shardings = jax.tree.map(
            lambda x: jax.sharding.SingleDeviceSharding(jax.devices()[0]),
            tree)
        restored, manifest = ck.restore_latest(tmp, tree,
                                               shardings=shardings)
        assert manifest["step"] == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(32.0).reshape(8, 4))
    finally:
        shutil.rmtree(tmp)


def test_robe_lookup_bag_weighted():
    from repro.core.robe import RobeSpec, init_memory, robe_lookup, \
        robe_lookup_bag
    spec = RobeSpec(size=512, block_size=8, seed=0)
    mem = init_memory(jax.random.PRNGKey(0), spec)
    rows = jnp.asarray([[[2, 5]]], jnp.int32)
    w = jnp.asarray([[[0.25, 0.75]]], jnp.float32)
    out = robe_lookup_bag(mem, spec, jnp.asarray([[0]]), rows, 8, weights=w)
    e2 = robe_lookup(mem, spec, 0, jnp.asarray([2]), 8)[0]
    e5 = robe_lookup(mem, spec, 0, jnp.asarray([5]), 8)[0]
    np.testing.assert_allclose(np.asarray(out[0, 0]),
                               np.asarray(0.25 * e2 + 0.75 * e5), atol=1e-6)
