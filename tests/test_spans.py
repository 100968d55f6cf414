"""The program's names in a profile: the device scopes that the compiled
programs carry as op metadata, and the host spans that serving and
training write into a ``jax.profiler`` trace.

Device scopes (``jax.named_scope``): ``embedding``, ``bottom_mlp``,
``interaction``, ``top_mlp`` in ``models/recsys.py`` and ``optimizer`` in
``train/train_loop.py``.  Host spans (``jax.profiler.TraceAnnotation``):
``serve.h2d``/``serve.run``/``serve.d2h`` in ``EmbeddingServer.score`` and
``train.h2d``/``train.run``/``train.sync`` in ``train_loop.run``.
"""

import glob
import importlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.recsys import RecsysConfig, init_params, loss_fn
from repro.serve.server import EmbeddingServer, ServerConfig
from repro.train.optimizer import OptimizerConfig, make_optimizer
from repro.train.train_loop import (TrainConfig, build_train_step,
                                    init_state, run)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("embedding", "bottom_mlp", "interaction", "top_mlp", "optimizer")
SERVE_SPANS = ("serve.h2d", "serve.run", "serve.d2h")
TRAIN_SPANS = ("train.h2d", "train.run", "train.sync")
VOCABS = (1000, 500, 2000, 100)
BATCH = 64

#: one instruction of a compiled module: its opcode and its op_name
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = (?:\(.*?\)|\S+) ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _config() -> RecsysConfig:
    return RecsysConfig(name="spans", arch="dlrm", vocab_sizes=VOCABS,
                        embed_dim=16, n_dense=13, bot_mlp=(32, 16),
                        top_mlp=(16, 1), embedding="robe", robe_size=512,
                        robe_block=8)


def _batch(seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {"dense": rng.normal(size=(BATCH, 13)).astype(np.float32),
            "sparse": np.stack([rng.integers(0, v, BATCH) for v in VOCABS],
                               1).astype(np.int32),
            "label": rng.integers(0, 2, BATCH).astype(np.float32)}


def _train_step():
    rc = _config()
    opt = make_optimizer(OptimizerConfig(kind="adagrad", lr=0.01))
    tc = TrainConfig(max_restarts=0)
    step = build_train_step(lambda p, b: loss_fn(p, rc, b), opt, tc)
    state = init_state(init_params(jax.random.PRNGKey(0), rc), opt, tc)
    return step, state, opt, tc


@pytest.fixture(scope="module")
def server():
    return EmbeddingServer(ServerConfig(
        vocab_sizes=VOCABS, embed_dim=16, n_dense=13, bot_mlp=(32, 16),
        top_mlp=(16, 1), backends=("robe",)))


@pytest.fixture(scope="module")
def compiled(server):
    """{"serve" | "train": (module name, [(opcode, op_name), ...])}."""
    b = {k: jnp.asarray(v) for k, v in _batch().items()}
    serve = server._jit["robe"].lower(
        server.params("robe"), {"dense": b["dense"], "sparse": b["sparse"]})
    step, state, _, _ = _train_step()
    out = {}
    for kind, lowered in (("serve", serve), ("train", step.lower(state, b))):
        ops = []
        for line in lowered.compile().as_text().splitlines():
            m = _INSTR.match(line)
            if m:
                name = _OP_NAME.search(line)
                ops.append((m.group(1), name.group(1) if name else None))
        out[kind] = (lowered.as_text().split()[1], ops)
    return out


@pytest.fixture(scope="module")
def innermost():
    """The benchmark's reader of an op's path (``bench/scopes.scope_of``):
    (the innermost scope on the path or None, whether it is backward:
    inside ``transpose(``)."""
    sys.path.insert(0, ROOT)
    try:
        scope_of = importlib.import_module("bench.scopes").scope_of
    finally:
        sys.path.remove(ROOT)
    return lambda op_name: scope_of(op_name or "", SCOPES)


@pytest.mark.parametrize("kind,module,scopes", [
    ("serve", "@jit_serve_scores", SCOPES[:4]),
    ("train", "@jit_train_step", SCOPES)])
def test_compiled_programs_carry_the_scopes(compiled, innermost, kind,
                                            module, scopes):
    name, ops = compiled[kind]
    assert name == module
    seen = {innermost(n)[0] for _, n in ops}
    assert set(scopes) <= seen


def test_embedding_forward_and_backward_split_by_transpose(compiled,
                                                          innermost):
    _, ops = compiled["train"]
    emb = [(op, n) for op, n in ops if innermost(n)[0] == "embedding"]
    fwd = {op for op, n in emb if not innermost(n)[1]}
    bwd = {op for op, n in emb if innermost(n)[1]}
    # the lookup gathers forward and scatter-adds into the array backward
    assert "gather" in fwd and "scatter" not in fwd
    assert "scatter" in bwd and "gather" not in bwd
    assert all("transpose(" in n for op, n in emb if op == "scatter")


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_every_heavy_op_lies_under_a_scope(compiled, innermost, kind):
    """Every gather, scatter, dot and sort, inside fusions too, lies under
    a scope; in the served program so does every fusion that carries op
    metadata (the train step's loss and step counter lie outside the five
    layers, and stay unscoped)."""
    _, ops = compiled[kind]
    heavy = [(op, n) for op, n in ops
             if op in ("gather", "scatter", "dot", "sort", "convolution")
             or (kind == "serve" and op == "fusion" and n)]
    assert heavy
    bare = [(op, n) for op, n in heavy if innermost(n)[0] is None]
    assert not bare, bare


def _host_events(trace_dir: str, names) -> list:
    """[(name, start ns, end ns)] of the host events named ``names``, in
    start order."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in pd.planes if p.name.startswith("/host:")
           for line in p.lines for e in line.events if e.name in names]
    return sorted(out, key=lambda e: e[1])


def _traced(tmp_path, fn) -> str:
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return d


def _assert_nested(events, outer: str, inner, n: int) -> None:
    """``n`` spans ``outer``, each holding one of each ``inner`` span, in
    that order and apart."""
    outs = [e for e in events if e[0] == outer]
    assert len(outs) == n
    for _, lo, hi in outs:
        got = [e for e in events if e[0] in inner and lo <= e[1]
               and e[2] <= hi]
        assert [e[0] for e in got] == list(inner)
        assert all(a[2] <= b[1] for a, b in zip(got, got[1:]))


def test_score_writes_its_host_spans(tmp_path, server):
    batch = _batch()
    server.score("robe", batch)                 # compiles outside the trace

    def calls():
        for _ in range(2):
            with jax.profiler.TraceAnnotation("caller"):
                server.score("robe", batch)

    events = _host_events(_traced(tmp_path, calls), SERVE_SPANS + ("caller",))
    _assert_nested(events, "caller", SERVE_SPANS, 2)


def test_train_loop_writes_its_host_spans(tmp_path):
    step, state, _, tc = _train_step()
    batches = [_batch(s) for s in range(3)]

    def feed(s):
        with jax.profiler.TraceAnnotation("feed"):
            return batches[s]

    def step_fn(state, batch):
        with jax.profiler.TraceAnnotation("dispatch"):
            return step(state, batch)

    rep = run(state, step_fn, feed, 1, tc)      # compiles outside the trace
    box = {}

    def steps():
        box["rep"] = run(rep.state, step_fn, feed, 3, tc)

    events = _host_events(_traced(tmp_path, steps),
                          TRAIN_SPANS + ("feed", "dispatch"))
    assert box["rep"].steps_done == 2
    for span, inner in (("train.h2d", "feed"), ("train.run", "dispatch")):
        _assert_nested(events, span, (inner,), 2)
    spans = [e for e in events if e[0] in TRAIN_SPANS]
    assert [e[0] for e in spans] == list(TRAIN_SPANS) * 2
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))


def test_no_program_span_is_a_benchmark_label(tmp_path, server):
    """The benchmark labels idle time by its own spans (``HOST_LABELS``); a
    program span of the same name would be counted twice."""
    spec = importlib.util.spec_from_file_location(
        "bench_trace_labels", os.path.join(ROOT, "bench", "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    step, state, _, tc = _train_step()
    batch = _batch()
    server.score("robe", batch)
    rep = run(state, step, lambda s: batch, 1, tc)

    def both():
        server.score("robe", batch)
        run(rep.state, step, lambda s: batch, 2, tc)

    d = _traced(tmp_path, both)
    names = {e[0] for e in _host_events(d, SERVE_SPANS + TRAIN_SPANS)}
    assert names == set(SERVE_SPANS + TRAIN_SPANS)
    assert not names & set(mod.HOST_LABELS)
