"""The reduction from a trace to the benchmark's numbers: on intervals
built by hand, and on a small trace recorded on a v5e chip
(``data/small.xplane.pb``, made by ``record_trace.py``: three runs of
``bench_small``, each in a ``score`` span and followed by 20 ms asleep in a
``batch_at`` span)."""

import os

import numpy as np
import pytest

from bench.trace import Trace, overlap, union

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6                                        # ns


def test_union_merges_overlaps_and_nesting():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 6.5], [10, 11]], float)
    np.testing.assert_array_equal(union(iv), [[0, 3], [5, 7], [10, 11]])
    assert union(np.zeros((0, 2))).shape == (0, 2)


def test_overlap_of_two_sets():
    a = np.array([[0, 3], [5, 7]], float)
    b = np.array([[2, 6], [6.5, 10]], float)
    assert overlap(a, b) == pytest.approx(1 + 1 + 0.5)


def _hand_trace():
    # device busy 0-2, 3-4 (two ops overlapping), 10-12 ms; host spans
    ops = {"/device:TPU:0": [("fusion.1", 0, 2 * MS), ("dot.2", 3 * MS, MS),
                             ("dot.2", 3.5 * MS, 0.5 * MS),
                             ("fusion.1", 10 * MS, 2 * MS)]}
    modules = {"/device:TPU:0": [("jit_bench_lookup(1)", 0, 4 * MS),
                                 ("jit_step(2)", 10 * MS, 2 * MS)]}
    host = [("batch_at", 2 * MS, 0.5 * MS), ("score", 4 * MS, 3 * MS),
            ("unrelated", 0, 20 * MS)]
    return Trace(ops, modules, host)


def test_busy_is_the_union_of_operations():
    assert _hand_trace().busy_s() == pytest.approx(5e-3)


def test_top_ops_sum_per_name():
    top = _hand_trace().top_ops(10)
    assert top[0] == ["fusion.1", pytest.approx(4e-3)]
    assert top[1] == ["dot.2", pytest.approx(1.5e-3)]


def test_idle_gaps_go_to_the_covering_label():
    got = dict(_hand_trace().idle_by_label(("score", "batch_at"), 10))
    # gaps: 2-3 ms (0.5 under batch_at) and 4-10 ms (3 under score)
    assert got == {"score": pytest.approx(3e-3),
                   "other": pytest.approx(3.5e-3),
                   "batch_at": pytest.approx(0.5e-3)}


def test_module_runs_by_name():
    assert _hand_trace().module_runs("bench_lookup") == [pytest.approx(4e-3)]
    assert _hand_trace().module_runs("nothing") == []


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = os.path.join(DATA, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.fail("the recorded trace is missing")
    d = tmp_path_factory.mktemp("trace")
    with open(path, "rb") as f, open(d / "t.xplane.pb", "wb") as g:
        g.write(f.read())
    return Trace.load(str(d))


def test_recorded_trace_has_the_device_and_the_runs(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    runs = recorded.module_runs("bench_small")
    assert len(runs) == 3 and all(0 < r < 0.02 for r in runs)
    busy = recorded.busy_s()
    assert 0 < busy <= sum(runs) * 1.0001


def test_recorded_clock_skew(recorded):
    # each run's Done event on the host comes about 3.9 ms after the run's
    # end by the device's clock in this trace
    assert 3.5e6 < recorded.skew_ns("/device:TPU:0") < 4.2e6


def test_skew_is_zero_without_done_events():
    assert _hand_trace().skew_ns("/device:TPU:0") == 0.0


def test_recorded_idle_gaps_are_the_sleeps(recorded):
    got = dict(recorded.idle_by_label(("score", "batch_at"), 10))
    # the two sleeps between the three runs: 20 ms each, all idle
    assert 0.038 < got["batch_at"] < 0.06
    assert got.get("other", 0) < 0.01
