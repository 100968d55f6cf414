"""Device time by the program's scopes and idle time under its host spans:
the ``tf_op`` reader on the recorded traces, the sums on traces built by
hand, and each metric that reads them.

``data/scoped.xplane.pb`` was made on a v5e by ``record_scoped_trace.py``:
three runs of one jitted gradient step (``bench_scoped``: a gather under
``embedding``, a scatter that the compiler makes a ``while`` loop of under
``interaction``, the mean of a 64 MB dense batch under ``bottom_mlp``, a
product under ``top_mlp``, their backward, an unscoped update), each in a
``step`` span holding ``train.h2d`` (20 ms asleep and the batch's copy
started), ``train.run`` and ``train.sync`` spans; the device waits for the
copy after the dispatch."""

import os
import shutil
from types import SimpleNamespace

import pytest

from bench import harness, scopes
from bench import trace as trace_lib
from bench.scopes import ScopedTrace, read_tf_ops, scope_of
from bench.tests.conftest import bench_json, small_cell
from bench.trace import HOST_LABELS, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6                                        # ns
PLANE = "/device:TPU:0"


def _load(tmp_path_factory, name: str) -> ScopedTrace:
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.fail(f"the recorded trace {name} is missing")
    d = tmp_path_factory.mktemp("scoped")
    shutil.copy(path, d / "t.xplane.pb")
    return ScopedTrace.load(str(d))


# -- the reader -------------------------------------------------------------

def test_tf_op_reader_on_the_small_trace(tmp_path_factory):
    with open(os.path.join(DATA, "small.xplane.pb"), "rb") as f:
        got = read_tf_ops(f.read())
    assert list(got) == [PLANE]
    fusions = {k.split(" = ")[0]: v for k, v in got[PLANE].items()}
    assert fusions == {"%convolution_tanh_fusion":
                       "jit(bench_small)/dot_general:",
                       "%fusion": "jit(bench_small)/dot_general:"}
    # keyed by the names Trace gives the operations
    tr = _load(tmp_path_factory, "small.xplane.pb")
    names = {n for n, _, _ in tr.ops[PLANE]}
    assert set(got[PLANE]) <= names


@pytest.mark.parametrize("tf_op,want", [
    ("jit(train_step)/jvp(embedding)/jit(_take)/gather:", ("embedding", False)),
    ("jit(train_step)/transpose(jvp(embedding))/scatter-add:",
     ("embedding", True)),
    ("jit(train_step)/transpose(jvp(bottom_mlp))/transpose",
     ("bottom_mlp", True)),
    ("jit(serve_scores)/interaction/bfd,bgd->bfg/dot_general:",
     ("interaction", False)),
    ("jit(train_step)/optimizer/jit(_where)/select_n:", ("optimizer", False)),
    ("jit(train_step)/jvp()/reduce_sum:", (None, False)),
    ("jit(bench_small)/dot_general:", (None, False)),
    ("", (None, False))])
def test_scope_of_a_path(tf_op, want):
    assert scope_of(tf_op) == want


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return _load(tmp_path_factory, "scoped.xplane.pb")


def test_recorded_ops_carry_their_scope(scoped):
    paths = scoped.tf_op[PLANE]
    # the gather forward, its scatter-add (a sort and a scatter) backward
    fwd = {k.split(" = ")[0] for k, v in paths.items()
           if scope_of(v) == ("embedding", False)}
    bwd = [k for k, v in paths.items() if scope_of(v) == ("embedding", True)]
    assert "%fusion" in fwd
    assert any(" sort(" in k for k in bwd)
    assert all("scatter-add" in paths[k] for k in bwd)
    assert {scope_of(v) for v in paths.values()} == {
        ("embedding", False), ("embedding", True), ("interaction", False),
        ("interaction", True), ("bottom_mlp", False), ("top_mlp", False),
        ("top_mlp", True), (None, False)}


def test_recorded_scope_sums(scoped):
    runs = scoped.module_runs("bench_scoped")
    assert len(runs) == 3
    emb_f = scoped.scope_s(("embedding",), backward=False)
    emb_b = scoped.scope_s(("embedding",), backward=True)
    top_f = scoped.scope_s(("top_mlp",), backward=False)
    top_b = scoped.scope_s(("top_mlp",), backward=True)
    assert all(t > 0 for t in (emb_f, emb_b, top_f, top_b))
    assert scoped.scope_s(("embedding",)) == pytest.approx(emb_f + emb_b)
    # the unscoped rest is the update; all of it adds up to the busy time
    by = scopes.by_scope(scoped)
    assert 0 < by["unscoped"] < by["interaction"]
    assert sum(by.values()) == pytest.approx(scoped.busy_s(), rel=1e-6)
    assert sum(by.values()) <= sum(runs)


def test_a_recorded_while_loop_counts_under_the_scope_of_its_ops(scoped):
    events = scoped.ops[PLANE]
    paths = scoped.tf_op[PLANE]
    loops = [e for e in events if " while(" in e[0]]
    assert len(loops) == 3 and not any(e[0] in paths for e in loops)
    counted = {e[0]: (own, scope) for e, (_, own, scope) in
               zip(events, scopes._attributed(events, paths))}
    for name, s, d in loops:
        own, scope = counted[name]
        # the ops inside the loop carry the interaction's scope
        inside = [e for e in events if s <= e[1] and e[1] + e[2] <= s + d
                  and e[0] != name]
        assert inside and {scope_of(paths.get(e[0], ""))[0]
                           for e in inside} == {"interaction"}
        assert scope == ("interaction", False) and own < 0.01 * d
    loop_s = sum(d for _, _, d in loops) * 1e-9
    assert scopes.by_scope(scoped)["interaction"] > loop_s * 0.99
    assert not any(" while(" in r[0] for r in scopes.top_unscoped(scoped, 50))


def test_recorded_idle_under_the_program_spans(scoped):
    h2d = scoped.span_idle_s("train.h2d")
    run = scoped.span_idle_s("train.run")
    sync = scoped.span_idle_s("train.sync")
    # three 20 ms sleeps and the copies' start: the device idles through
    # train.h2d; then it waits for the 64 MB copy while the host syncs
    assert 0.06 < h2d < 0.07
    assert run < 0.005 and sync > 0.015
    # the three spans tile each step span
    assert scoped.span_idle_s("step") == pytest.approx(h2d + run + sync,
                                                       abs=5e-4)
    # the gaps between the three runs: two sleeps, labelled train.h2d, and
    # two waits for the copy, labelled train.sync
    gaps = dict(scoped.idle_by_label(scopes.PROGRAM_SPANS, 10))
    assert 0.04 < gaps["train.h2d"] < 0.045
    assert gaps["train.sync"] > 5 * gaps["train.run"]


def test_recorded_input_wait_counts_the_copy_in_flight(scoped):
    h2d = scoped.span_idle_s("train.h2d")
    wait = scoped.input_wait_s("train.h2d", "train.run", "bench_scoped")
    after = wait - h2d
    # jnp.asarray returned at once: each run waited more than 5 ms after
    # its dispatch for the 64 MB copy, and the host waited in train.sync
    assert after > 3 * 5e-3
    assert after <= scoped.span_idle_s("train.sync") + 1e-4


# -- sums on a trace built by hand ----------------------------------------

def _hand_trace() -> ScopedTrace:
    # two runs of train_step: device busy 0-4 ms (gather 2, dot 1,
    # scatter 1) and 10-12.5 ms (update 2, loss 0.5).  Host, before each
    # run: train.h2d (-3 to -2 ms, 4-9 ms: the device idles throughout),
    # train.run, the dispatch (-2 to -1 ms, 9-9.5 ms), after which the
    # runs wait 1 ms and 0.5 ms for their input; train.sync 3-4.5 ms
    ops = {PLANE: [("gather.1", 0, 2 * MS), ("dot.2", 2 * MS, MS),
                   ("scatter.3", 3 * MS, MS), ("add.4", 10 * MS, 2 * MS),
                   ("loss.5", 12 * MS, 0.5 * MS)]}
    modules = {PLANE: [("jit_train_step(7)", 0, 4 * MS),
                       ("jit_train_step(7)", 10 * MS, 2.5 * MS)]}
    tf_op = {PLANE: {
        "gather.1": "jit(train_step)/jvp(embedding)/gather:",
        "dot.2": "jit(train_step)/jvp(top_mlp)/dot_general:",
        "scatter.3": "jit(train_step)/transpose(jvp(embedding))/scatter-add:",
        "add.4": "jit(train_step)/optimizer/add:",
        "loss.5": "jit(train_step)/jvp()/log1p:"}}
    host = [("train.h2d", -3 * MS, MS), ("train.run", -2 * MS, MS),
            ("train.sync", 3 * MS, 1.5 * MS),
            ("train.h2d", 4 * MS, 5 * MS), ("train.run", 9 * MS, 0.5 * MS),
            ("step", 9 * MS, 3 * MS)]
    return ScopedTrace(ops, modules, host, tf_op)


def test_scope_seconds_forward_and_backward():
    tr = _hand_trace()
    assert tr.scope_s(("embedding",)) == pytest.approx(3e-3)
    assert tr.scope_s(("embedding",), backward=False) == pytest.approx(2e-3)
    assert tr.scope_s(("embedding",), backward=True) == pytest.approx(1e-3)
    assert tr.scope_s(scopes.DENSE) == pytest.approx(1e-3)
    assert tr.scope_s(("optimizer",)) == pytest.approx(2e-3)
    assert tr.scope_s(("bottom_mlp",)) is None
    by = scopes.by_scope(tr)
    assert by["unscoped"] == pytest.approx(0.5e-3)
    assert sum(by.values()) == pytest.approx(6.5e-3)
    assert scopes.top_unscoped(tr, 3)[0][:2] == [
        "loss.5", "jit(train_step)/jvp()/log1p:"]


def test_an_op_that_holds_others_counts_only_its_own_time():
    # a while loop 0-10 ms without a tf_op holds two scatter steps of the
    # interaction's backward (1-4, 5-9 ms) and one of the embedding's
    # (9-9.5 ms); a scoped loop 20-30 ms holds an unscoped op 21-22 ms
    tr = ScopedTrace({PLANE: [
        ("while.1", 0, 10 * MS), ("scatter.2", MS, 3 * MS),
        ("scatter.3", 5 * MS, 4 * MS), ("add.4", 9 * MS, 0.5 * MS),
        ("dot.5", 12 * MS, 2 * MS), ("while.6", 20 * MS, 10 * MS),
        ("copy.7", 21 * MS, MS)]}, {}, [], {PLANE: {
            "scatter.2": "jit(s)/transpose(jvp(interaction))/scatter-add:",
            "scatter.3": "jit(s)/transpose(jvp(interaction))/scatter-add:",
            "add.4": "jit(s)/transpose(jvp(embedding))/add:",
            "dot.5": "jit(s)/top_mlp/dot_general:",
            "while.6": "jit(s)/optimizer/while:"}})
    by = scopes.by_scope(tr)
    assert by == pytest.approx({"interaction bwd": 9.5e-3,
                                "embedding bwd": 0.5e-3, "top_mlp": 2e-3,
                                "optimizer": 9e-3, "unscoped": 1e-3})
    # nothing counted twice: the sums add up to the busy time
    assert sum(by.values()) == pytest.approx(tr.busy_s())
    assert tr.scope_s(scopes.DENSE) == pytest.approx(11.5e-3)
    assert [r[0] for r in scopes.top_unscoped(tr, 5)] == ["copy.7"]


def test_span_idle_is_span_length_less_busy():
    tr = _hand_trace()
    # train.h2d 1 + 5 ms, all idle; train.run 1 + 0.5 ms, all idle;
    # train.sync 3-4.5 ms: busy to 4 ms
    assert tr.span_idle_s("train.h2d") == pytest.approx(6e-3)
    assert tr.span_idle_s("train.run") == pytest.approx(1.5e-3)
    assert tr.span_idle_s("train.sync") == pytest.approx(0.5e-3)
    assert tr.span_idle_s("serve.d2h") is None


def test_input_wait_is_the_idle_under_h2d_and_after_dispatch():
    tr = _hand_trace()
    # 6 ms under train.h2d, 1 + 0.5 ms from each dispatch's end to its run
    assert tr.input_wait_s("train.h2d", "train.run", "train_step") == \
        pytest.approx(7.5e-3)
    assert tr.input_wait_s("train.h2d", "train.run", "serve_scores") is None
    assert tr.input_wait_s("train.h2d", "serve.run", "train_step") is None
    # a run that starts inside its dispatch span waited for nothing after
    # it; busy time after the dispatch is no wait
    tr.host = [("train.run", -2 * MS, 3 * MS),
               ("train.run", 9 * MS, 0.5 * MS), ("train.run", 13 * MS, MS)]
    tr.ops[PLANE].append(("fill.6", 9.5 * MS, 0.25 * MS))
    tr.modules[PLANE].append(("jit_train_step(7)", 20 * MS, MS))
    # run 1 from -1 ms: none; run 2: 10 - 9.75 ms; run 3: 20 - 14 ms
    assert tr.input_wait_s("train.h2d", "train.run", "train_step") == \
        pytest.approx(6.25e-3)


def test_input_wait_counts_a_run_dispatched_before_the_last_ended():
    # run 2 is handed over at 2-3 ms, while run 1 (0-4 ms) runs, and
    # starts at 6 ms: the 2 ms from the end of run 1 are a wait
    ops = {PLANE: [("a.1", 0, 4 * MS), ("a.2", 6 * MS, MS)]}
    modules = {PLANE: [("jit_serve_scores(1)", 0, 4 * MS),
                       ("jit_serve_scores(1)", 6 * MS, MS)]}
    host = [("serve.run", -MS, 0.5 * MS), ("serve.run", 2 * MS, MS)]
    tr = ScopedTrace(ops, modules, host, {})
    assert tr.input_wait_s("serve.h2d", "serve.run", "serve_scores") == \
        pytest.approx(2.5e-3)


def test_a_plain_trace_has_no_scopes():
    tr = ScopedTrace(_hand_trace().ops, {}, [])
    assert tr.scope_s(scopes.SCOPES) is None


# -- the metrics ------------------------------------------------------------

NEW = {"lookup_ms.bulk": ("calls", 3e-3 / 2 * 1e3),
       "dense_ms.bulk": ("calls", 1e-3 / 2 * 1e3),
       "h2d_idle_ms.bulk": ("calls", None),
       "embed_fwd_ms.train": ("steps", 2e-3 / 2 * 1e3),
       "embed_bwd_step_ms.train": ("steps", 1e-3 / 2 * 1e3),
       "dense_ms.train": ("steps", 1e-3 / 2 * 1e3),
       "update_ms.train": ("steps", 2e-3 / 2 * 1e3),
       "h2d_idle_ms.train": ("steps", 7.5e-3 / 2 * 1e3)}


def _metric(name):
    return harness.load_module("metrics", name + ".py")


def test_the_new_metrics_are_in_the_benchmark():
    b = bench_json()
    per = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        cell = "rm2-bulk" if name.endswith(".bulk") else "tb-train"
        assert per[name]["workloads"] == [cell]
        assert per[name]["source"] == "device_trace"
        assert per[name]["unit"] == "ms" and per[name]["better"] == "lower"


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_on_a_hand_built_trace(name):
    per, want = NEW[name]
    tr = _hand_trace()
    if name == "h2d_idle_ms.bulk":
        # the same waits, under the serving spans and program
        tr.host = [(n.replace("train.", "serve."), s, d)
                   for n, s, d in tr.host]
        tr.modules = {PLANE: [("jit_serve_scores(3)", s, d)
                              for _, s, d in tr.modules[PLANE]]}
        want = 7.5e-3 / 2 * 1e3
    run = SimpleNamespace(traces={"window": tr}, record={per: 2})
    assert _metric(name).read(run) == pytest.approx(want)
    # nothing to read: no trace, a plain Trace (a program without scopes or
    # spans), an empty window
    for traces, record in (({}, {per: 2}),
                           ({"window": Trace(tr.ops, {}, tr.host)}, {per: 2}),
                           ({"window": tr}, {per: 0})):
        run = SimpleNamespace(traces=traces, record=record)
        assert _metric(name).read(run) is None


def test_keep_scopes_loads_the_window_as_a_scoped_trace():
    import jax
    import jax.numpy as jnp
    parts = small_cell("rm2-bulk")
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == "rm2-bulk")
    run = harness.Run(cell, parts["config"], parts["traffic"],
                      parts["limits"], 1, 1.0, True, 0.0, "cpu",
                      log=lambda m: None)
    for name in NEW:
        if name.endswith(".bulk"):
            _metric(name).warm(run)
    run.start_trace()
    with jax.profiler.TraceAnnotation("serve.h2d"):
        jnp.ones(8).block_until_ready()
    run.stop_trace()
    tr = run.traces["window"]
    assert isinstance(tr, ScopedTrace)
    assert any(e[0] == "serve.h2d" for e in tr.host)
    assert trace_lib.Trace is Trace          # the swap is undone
    # the probe's trace is read for no scope: it loads as it did
    run.start_trace("probe")
    jnp.ones(8).block_until_ready()
    run.stop_trace("probe")
    assert type(run.traces["probe"]) is Trace
    # a run that is not traced is left as it is
    run.trace = False
    stop = run.stop_trace
    scopes.keep_scopes(run)
    assert run.stop_trace is stop


def test_no_program_span_is_a_benchmark_label():
    assert not set(scopes.PROGRAM_SPANS) & set(HOST_LABELS)
