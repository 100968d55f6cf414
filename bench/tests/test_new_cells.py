"""The cells ``dcnv2-bulk`` and ``tb-train-dp4``, at a small size on the CPU:
whole runs through ``run_cell`` (``tb-train-dp4`` on four forced host
devices, in a process of its own), the control that the ``dcnv2-bulk``
limit has to refuse, and each new per-layer metric on a trace built by
hand and, for the served program's scopes, on
``data/dcnv2.xplane.pb``, recorded on a v5e by ``record_dcnv2_trace.py``.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench.scopes import ScopedTrace
from bench.tests.conftest import ROOT, SMALL_VOCABS, bench_json
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6                                        # ns
PLANE = "/device:TPU:0"
SMALL_BAGS = [3, 1, 2, 1, 5, 2]


def small_parts(name: str) -> dict:
    """The cell's configuration, traffic and limits at a CPU size: every
    width cut, the batch cut, the structure kept."""
    cell = next(w for w in bench_json()["workloads"] if w["name"] == name)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    cfg.update(vocab_sizes=SMALL_VOCABS, embed_dim=16, bot_mlp=[32, 16],
               top_mlp=[16, 1], robe_block=8,
               robe_size=max(512, sum(SMALL_VOCABS) * 16 // 1000))
    if "multi_hot_sizes" in cfg:
        cfg.update(multi_hot_sizes=SMALL_BAGS, cross_rank=8)
    tr = harness.load_json("traffic", cell["traffic"] + ".json")
    tr.update(batch=512, pool=4 if "check_steps" in tr else 2)
    return {"config": cfg, "traffic": tr,
            "limits": harness.load_json("limits", name + ".json")}


def _metric(name):
    return harness.load_module("metrics", name + ".py")


def test_the_cells_are_in_the_benchmark():
    b = bench_json()
    cells = {w["name"]: w for w in b["workloads"]}
    assert cells["dcnv2-bulk"]["chips"] == 1
    assert cells["tb-train-dp4"]["chips"] == 4
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "dcnv2-bulk" in e2e["bulk_samples_per_s"]["workloads"]
    assert "tb-train-dp4" in e2e["train_samples_per_s"]["workloads"]
    per = {m["name"]: m for m in b["per_layer"]}
    for name in ("lookup_ms.dcnv2", "pool_ms.dcnv2", "cross_ms.dcnv2",
                 "lookup_roofline.dcnv2", "mfu.dcnv2", "device_idle.dcnv2"):
        assert per[name]["workloads"] == ["dcnv2-bulk"], name
        assert per[name]["moves"] == "bulk_samples_per_s"
    assert per["allreduce_ms.dp4"]["workloads"] == ["tb-train-dp4"]
    assert per["allreduce_ms.dp4"]["moves"] == "train_samples_per_s"


def test_dcnv2_bulk_small_run_is_correct():
    keep = {}
    res = harness.run_cell(bench_json(), "dcnv2-bulk", 2 ** 31 + 12345, 0.5,
                           False, time.perf_counter(), platform="cpu",
                           log=lambda m: None, keep=keep,
                           **small_parts("dcnv2-bulk"))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"bulk_samples_per_s", "setup_s"}
    run = keep["run"]
    assert "server" not in run.state            # released after the window
    assert run.state["pool"][0]["sparse"].shape == (512, sum(SMALL_BAGS))


def test_dcnv2_bulk_control_fails():
    """The reference one precision below the configuration's (bfloat16),
    in the program's place, is refused by the cell's limit."""
    drv = harness.load_module("drivers", "bulk_multihot.py")
    parts = small_parts("dcnv2-bulk")
    w = next(x for x in bench_json()["workloads"] if x["name"] ==
             "dcnv2-bulk")
    run = harness.Run(w, parts["config"], parts["traffic"], parts["limits"],
                      2 ** 31 + 99, 0.5, False, time.perf_counter(), "cpu")
    low = run.config["precision"]["control"]
    assert drv.control_gap(run, low, run.seed) > run.limits["score_gap"]


def test_dcnv2_bulk_refuses_a_program_that_departs():
    drv = harness.load_module("drivers", "bulk_multihot.py")
    from repro.models.recsys import RecsysConfig
    cfg = small_parts("dcnv2-bulk")["config"]
    rc = RecsysConfig(name="x", arch="dlrm_dcnv2",
                      vocab_sizes=tuple(cfg["vocab_sizes"]),
                      embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
                      bot_mlp=tuple(cfg["bot_mlp"]),
                      top_mlp=tuple(cfg["top_mlp"]),
                      cross_layers=cfg["cross_layers"],
                      cross_rank=cfg["cross_rank"],
                      bag_sizes=tuple(cfg["multi_hot_sizes"]),
                      embedding="robe", robe_size=cfg["robe_size"],
                      robe_block=cfg["robe_block"])
    drv.check_multihot(rc, cfg)
    with pytest.raises(ValueError, match="bag_sizes"):
        drv.check_multihot(rc, dict(cfg, multi_hot_sizes=[1] * 6))


def test_tb_train_dp4_small_run_on_four_devices():
    """Data-parallel over four forced host devices: correct against the
    reference over the global batch, and reported by the training rate."""
    code = f"""
import json, sys, time
sys.path[:0] = [{os.path.join(ROOT, 'src')!r}, {ROOT!r}]
import repro.launch.cache
repro.launch.cache.use_compile_cache = lambda: "off"
from bench import harness
from bench.tests.test_new_cells import small_parts
from bench.tests.conftest import bench_json
import jax
assert len(jax.devices()) == 4
res = harness.run_cell(bench_json(), "tb-train-dp4", 2 ** 31 + 7, 0.5, False,
                       time.perf_counter(), platform="cpu",
                       log=lambda m: None, **small_parts("tb-train-dp4"))
print(json.dumps(res))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


# -- the metrics on traces built by hand -------------------------------------

def _dcnv2_trace() -> ScopedTrace:
    # two score calls: gather 4 ms and pool 1 ms under embedding (the pool
    # under embedding/bag_pool), cross 2 ms under interaction/cross, top
    # 1 ms; the device idles 2 ms between the calls
    ops = {PLANE: [("gather.1", 0, 4 * MS), ("add.2", 4 * MS, MS),
                   ("dot.3", 5 * MS, 2 * MS), ("dot.4", 7 * MS, MS),
                   ("gather.1", 10 * MS, 4 * MS), ("add.2", 14 * MS, MS),
                   ("dot.3", 15 * MS, 2 * MS), ("dot.4", 17 * MS, MS)]}
    tf_op = {PLANE: {
        "gather.1": "jit(serve_scores)/embedding/robe_blocks/gather:",
        "add.2": "jit(serve_scores)/embedding/bag_pool/add:",
        "dot.3": "jit(serve_scores)/interaction/cross/dot_general:",
        "dot.4": "jit(serve_scores)/top_mlp/dot_general:"}}
    return ScopedTrace(ops, {}, [], tf_op)


@pytest.mark.parametrize("name,want", [
    ("lookup_ms.dcnv2", 5.0), ("pool_ms.dcnv2", 1.0),
    ("cross_ms.dcnv2", 2.0)])
def test_scope_metrics_on_a_hand_built_trace(name, want):
    tr = _dcnv2_trace()
    run = SimpleNamespace(traces={"window": tr}, record={"calls": 2})
    assert _metric(name).read(run) == pytest.approx(want)
    for traces, record in (({}, {"calls": 2}),
                           ({"window": Trace(tr.ops, {}, [])}, {"calls": 2}),
                           ({"window": tr}, {"calls": 0})):
        run = SimpleNamespace(traces=traces, record=record)
        assert _metric(name).read(run) is None


def test_lookup_roofline_and_mfu(monkeypatch):
    cfg = harness.load_json("configs", "dlrm-dcnv2-robe.json")
    roof = _metric("lookup_roofline.dcnv2")
    mfu = _metric("mfu.dcnv2")
    for mod in (roof, mfu):
        monkeypatch.setattr(mod, "device_kind", lambda: "TPU v5 lite")
    run = SimpleNamespace(traces={"window": _dcnv2_trace()},
                          record={"calls": 2, "samples": 32768,
                                  "elapsed_s": 0.5},
                          config=cfg, traffic={"batch": 16384})
    # 16,384 rows: 3,506,176 ids and 425,984 pooled rows of 128 floats,
    # and the ids: 2,027,290,624 bytes at 819 GB/s, over 5 ms a call
    want = 2_027_290_624 / 819e9 / 5e-3 * 100
    assert roof.read(run) == pytest.approx(want)
    # 32,060,928 FLOPs a row at 65,536 rows/s over 197 TFLOP/s
    assert mfu.read(run) == pytest.approx(32_060_928 * 65536 / 197e12 * 100)
    assert roof.read(SimpleNamespace(traces={}, record={}, config=cfg,
                                     traffic={"batch": 16384})) is None
    assert mfu.read(SimpleNamespace(record={}, config=cfg)) is None


def test_allreduce_metric_on_a_hand_built_trace():
    # two steps on two chips: all-reduce 3 + 1 ms on one, 2 + 2 ms on the
    # other (start and done halves counted alike), besides other ops
    ops = {PLANE: [("_all-reduce.17___f32_512", 0, 3 * MS),
                   ("fusion.2", 3 * MS, 5 * MS),
                   ("_all-reduce-start.3", 10 * MS, MS)],
           "/device:TPU:1": [("_all-reduce.17___f32_512", 0, 2 * MS),
                             ("_all-reduce-done.3", 10 * MS, 2 * MS),
                             ("reduce.4", 12 * MS, MS)]}
    run = SimpleNamespace(traces={"window": Trace(ops, {}, [])},
                          record={"steps": 2})
    assert _metric("allreduce_ms.dp4").read(run) == pytest.approx(2.0)
    quiet = {PLANE: [("fusion.2", 0, MS)]}
    for traces, record in (({}, {"steps": 2}),
                           ({"window": Trace(quiet, {}, [])}, {"steps": 2}),
                           ({"window": Trace(ops, {}, [])}, {"steps": 0})):
        run = SimpleNamespace(traces=traces, record=record)
        assert _metric("allreduce_ms.dp4").read(run) is None


# -- the scope metrics on a trace recorded on a v5e --------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = os.path.join(DATA, "dcnv2.xplane.pb")
    if not os.path.exists(path):
        pytest.fail("the recorded trace dcnv2.xplane.pb is missing")
    d = tmp_path_factory.mktemp("dcnv2")
    shutil.copy(path, d / "t.xplane.pb")
    return ScopedTrace.load(str(d))


def test_scope_metrics_on_the_recorded_trace(recorded):
    """Three calls of the served DLRM-DCNv2 program: the pooling lies
    inside the lookup, and both it and the cross layers read a time."""
    run = SimpleNamespace(traces={"window": recorded}, record={"calls": 3})
    lookup = _metric("lookup_ms.dcnv2").read(run)
    pool = _metric("pool_ms.dcnv2").read(run)
    cross = _metric("cross_ms.dcnv2").read(run)
    assert lookup and pool and cross
    assert 0 < pool < lookup
    busy_ms = recorded.busy_s() / 3 * 1e3
    assert lookup + cross < busy_ms * 1.0001
