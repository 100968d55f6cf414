"""The control, the reference computed one precision below the
configuration's (bfloat16) and put in the program's place, comes out not
correct under each cell's limits, at a small size on the CPU."""

import time

from bench import harness
from bench.tests.conftest import bench_json, small_cell


def _run(cell):
    small = small_cell(cell)
    w = next(x for x in bench_json()["workloads"] if x["name"] == cell)
    return harness.Run(w, small["config"], small["traffic"], small["limits"],
                       2 ** 31 + 99, 0.5, False, time.perf_counter(), "cpu")


def test_serving_control_fails():
    from bench.data import CtrStream
    from bench.serving import reference_scores, score_gap
    run = _run("rm2-bulk")
    cfg = run.config
    rows = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], 2048, 1.05,
                     run.seed).batch_at(0, labels=False)
    low = run.numerics(cfg["precision"]["control"])
    gap = score_gap(reference_scores(run, rows["dense"], rows["sparse"], low),
                    reference_scores(run, rows["dense"], rows["sparse"]))
    assert gap > run.limits["score_gap"]


def test_training_control_fails():
    from bench.data import CtrStream
    from bench.drivers import train
    run = _run("tb-train")
    cfg, tr = run.config, run.traffic
    stream = CtrStream(cfg["vocab_sizes"], cfg["n_dense"], tr["batch"],
                       tr["zipf"], run.seed)
    run.state = {"pool": [stream.batch_at(i)
                          for i in range(tr["check_steps"])]}
    low = run.numerics(cfg["precision"]["control"])
    got = train.gaps(train.reference_readings(run, low),
                     train.reference_readings(run))
    assert any(got[k] > run.limits[k] for k in got), got
