"""A whole run of each cell at a small size on the CPU, with the chip's look
skipped: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.  The faults are those a cell can
have: a train step that returns its state unchanged; a train step that
leaves out half of its batch and takes the mean over the rest; a score
altered where the server produces it.  (One chip: no exchange between
chips to leave out.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.conftest import run_small


@pytest.mark.parametrize("cell", ["rm2-bulk", "tb-train"])
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _unchanged_step(loss_fn, optimizer, cfg, project=None):
    loss = jax.jit(lambda p, b: loss_fn(p, b)[0])

    def step(state, batch):
        return (dict(state, step=state["step"] + 1),
                {"loss": loss(state["params"], batch),
                 "finite": jnp.float32(1)})
    return step


def _half_batch_step(build):
    def builder(loss_fn, optimizer, cfg, project=None):
        def half(params, batch):
            n = batch["label"].shape[0] // 2
            return loss_fn(params, {k: v[:n] for k, v in batch.items()})
        return build(half, optimizer, cfg, project)
    return builder


def test_unchanged_state_is_caught(monkeypatch):
    monkeypatch.setattr("repro.train.train_loop.build_train_step",
                        _unchanged_step)
    res = run_small("tb-train")
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > 0.5


def test_half_batch_is_caught(monkeypatch):
    import repro.train.train_loop as tl
    monkeypatch.setattr(tl, "build_train_step",
                        _half_batch_step(tl.build_train_step))
    res = run_small("tb-train")
    assert not res["correct"]


def test_altered_answer_is_caught(monkeypatch):
    from repro.serve.server import EmbeddingServer
    score = EmbeddingServer.score
    calls = {"n": 0}

    def altered(self, backend, batch, n_valid=None, **kw):
        out = np.array(score(self, backend, batch, n_valid, **kw))
        calls["n"] += 1
        if calls["n"] > 1:                # one row of each served call
            out[len(out) // 2] += 0.05 * np.max(np.abs(out))
        return out

    monkeypatch.setattr(EmbeddingServer, "score", altered)
    res = run_small("rm2-bulk")
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] > 0.01
