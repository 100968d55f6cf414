#!/usr/bin/env python3
"""Readings that the limits of the check are set from; not a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--seconds s]

In one process, on the accelerator this machine holds:

* for each ``--seeds`` seed, one whole run of the cell (set-up, a window of
  ``--seconds``, the check), printing the numbers compared: the lower
  readings;
* for each ``--control-seeds`` seed, the control: the reference computed in
  the precision below the configuration's (``precision.control``) in the
  program's place, compared as the program is, on the rows or steps a run
  compares; for a training cell also the fault of a step that averages
  over half of its batch, planted in the reference put in its place.

Each reading is printed as one JSON line on standard output.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def control_readings(run, seed: int) -> dict:
    """The control's numbers for ``seed``, on the rows a run compares."""
    from bench.data import CtrStream
    from bench.serving import reference_scores, score_gap

    cfg, tr = run.config, run.traffic
    low = run.numerics(cfg["precision"]["control"])
    if tr["driver"] == "train":
        from bench.drivers import train
        stream = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], tr["batch"],
                           tr["zipf"], seed)
        run.state = {"pool": [stream.batch_at(i)
                              for i in range(tr["check_steps"])]}
        want = train.reference_readings(run)
        out = {"control": train.gaps(train.reference_readings(run, low),
                                     want)}
        out["stated_vs_f32"] = train.gaps(
            want, train.reference_readings(run, run.numerics("f32")))
        half = tr["batch"] // 2
        run.state["pool"] = [{k: v[:half] for k, v in b.items()}
                             for b in run.state["pool"]]
        out["half_batch"] = train.gaps(train.reference_readings(run), want)
        return out
    stream = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], tr["batch"],
                       tr["zipf"], seed)
    rows = [stream.batch_at(i, labels=False) for i in range(tr["pool"])]
    f32 = run.numerics("f32")
    gap, gap32 = [], []
    for r in rows:
        want = reference_scores(run, r["dense"], r["sparse"])
        gap.append(score_gap(reference_scores(run, r["dense"], r["sparse"],
                                              low), want))
        gap32.append(score_gap(want, reference_scores(run, r["dense"],
                                                      r["sparse"], f32)))
    return {"control": {"score_gap": max(gap)},
            "stated_vs_f32": {"score_gap": max(gap32)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    import jax
    platform = jax.devices()[0].platform
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    for seed in seeds:
        t = time.perf_counter()
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, t, platform=platform, log=log)
        emit(kind="program", seed=seed, correct=res["correct"],
             checks={k: c["value"] for k, c in res["checks"].items()},
             metrics={k: m["value"] for k, m in res["metrics"].items()},
             memory_peak_bytes=res["device"]["memory_peak_bytes"],
             wall_s=time.perf_counter() - t)
        gc.collect()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    for seed in control:
        run = harness.Run(
            cell, harness.load_json("configs", cell["config"] + ".json"),
            harness.load_json("traffic", cell["traffic"] + ".json"),
            harness.load_json("limits", args.workload + ".json"), seed,
            args.seconds, False, time.perf_counter(), platform)
        t = time.perf_counter()
        emit(kind="control", seed=seed, readings=control_readings(run, seed),
             wall_s=time.perf_counter() - t)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
