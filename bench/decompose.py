#!/usr/bin/env python3
"""Where one traced run of a cell spends the chip's time, by the program's
layers.

    python3 bench/decompose.py --workload <cell> --seed <n> [--seconds 10]

Runs the cell once with ``--trace 1``, as ``run.py`` does, and prints its
result line; then a last line with one JSON object, per call or step of
the traced window: the device ms under each of the program's scopes
(backward apart), the unscoped rest and its costliest operations, the
share of the window's busy time that lies under a scope, the device idle
ms under each host span, the program's (``scopes.PROGRAM_SPANS``) and the
benchmark's (``trace.HOST_LABELS``), as ``idle_by_label`` labels gaps and
as ``span_idle_s`` counts them, the idle ms inside the program's runs and
between them (``program_gaps``), the wait for the input that
``h2d_idle_ms.*`` reads (``input_wait_s``), and the operations that hold
others (``containers``).  Without a TPU it exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def program_gaps(tr, program: str, labels) -> dict:
    """Device idle time around the runs of the program named ``program``,
    on the first device: ``inside`` the runs (between their operations),
    ``between`` consecutive runs, and of that the part under each host span
    in ``labels`` (on the host's clock)."""
    import numpy as np
    from bench.trace import _intervals, overlap, union
    plane = sorted(tr.ops)[0]
    runs = union(_intervals([m for m in tr.modules.get(plane, [])
                             if program in m[0]]))
    busy = union(_intervals(tr.ops[plane]))
    inside = float((runs[:, 1] - runs[:, 0]).sum()) - overlap(runs, busy)
    gaps = np.stack([runs[:-1, 1], runs[1:, 0]], axis=1) + tr.skew_ns(plane)
    under = {}
    for label in labels:
        spans = union(_intervals([e for e in tr.host if e[0] == label]))
        t = overlap(gaps, spans)
        if t > 0:
            under[label] = t * 1e-9
    return {"inside": inside * 1e-9,
            "between": float((gaps[:, 1] - gaps[:, 0]).sum()) * 1e-9,
            "between_under": under}


def containers(tr, n: int) -> list:
    """[[op name, seconds, seconds of its own, scope, backward], ...]: the
    costliest operations that hold others, on the first device, with the
    time that none of those covers and the scope they count under."""
    from bench.scopes import _attributed
    plane = sorted(tr.ops)[0]
    events = tr.ops[plane]
    tot: dict = {}
    for (name, _, d), (_, own, (scope, bwd)) in zip(
            events, _attributed(events, tr.tf_op.get(plane, {}))):
        if own < d:
            k = (name[:160], scope, bwd)
            t = tot.get(k, (0.0, 0.0))
            tot[k] = (t[0] + d * 1e-9, t[1] + own * 1e-9)
    return [[k[0], v[0], v[1], k[1], k[2]] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1][0])[:n]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        log("decompose: no TPU found; the benchmark runs on a TPU only")
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import scopes
    from bench.harness import run_cell
    from bench.trace import HOST_LABELS

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    keep: dict = {}
    result = run_cell(bench, args.workload, args.seed, args.seconds, True,
                      T_START, platform="tpu", log=log, keep=keep)
    print(json.dumps(result), flush=True)
    run = keep["run"]
    tr = run.traces["window"]
    if not isinstance(tr, scopes.ScopedTrace):
        log("decompose: the cell's metrics keep no scopes")
        return 1
    per, program, h2d, dispatch = (
        ("calls", "serve_scores", "serve.h2d", "serve.run")
        if "calls" in run.record
        else ("steps", "train_step", "train.h2d", "train.run"))
    n = run.record[per]
    ms = {k: v / n * 1e3 for k, v in scopes.by_scope(tr).items()}
    scoped = tr.scope_s(scopes.SCOPES) or 0.0
    labels = scopes.PROGRAM_SPANS + HOST_LABELS
    # top_ops names each operation by the first 160 characters of its HLO
    paths = {k[:160]: v for p in tr.tf_op.values() for k, v in p.items()}
    out = {
        "per": per, "n": n, "window_s": run.window_s, "busy_s": tr.busy_s(),
        "scoped_share_of_busy": scoped / tr.busy_s(),
        "scope_ms": ms,
        "top_unscoped": [[name, path, s / n * 1e3] for name, path, s in
                         scopes.top_unscoped(tr, 8)],
        "idle_gap_ms_by_label": {k: v / n * 1e3 for k, v in
                                 tr.idle_by_label(labels, len(labels) + 1)},
        "span_idle_ms": {k: (v / n * 1e3 if v is not None else None)
                         for k, v in ((k, tr.span_idle_s(k))
                                      for k in labels)},
        "top_ops": [[name, paths.get(name, ""), s / n * 1e3]
                    for name, s in tr.top_ops(10)],
        "program_gaps_ms": {k: v / n * 1e3 if isinstance(v, float) else
                            {kk: vv / n * 1e3 for kk, vv in v.items()}
                            for k, v in program_gaps(
                                tr, program, labels).items()},
        "input_wait_ms": (tr.input_wait_s(h2d, dispatch, program) or 0.0)
        / n * 1e3,
        "containers": [[name, s / n * 1e3, own / n * 1e3, scope, bwd]
                       for name, s, own, scope, bwd in containers(tr, 8)]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
