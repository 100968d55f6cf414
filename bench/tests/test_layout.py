"""``BENCHMARK.json`` and the files it names: every piece is found by name,
and the command refuses to run without a TPU."""

import os
import re
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT, bench_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = os.path.join(ROOT, "bench")


def test_top_level_keys():
    assert set(bench_json()) == {"command", "paths", "run_seconds", "configs",
                                 "workloads", "end_to_end", "per_layer"}


def test_every_piece_has_its_file():
    b = bench_json()
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert NAME.match(c["name"])
    names = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4)
        for part in (("traffic", w["traffic"] + ".json"),
                     ("limits", w["name"] + ".json")):
            assert os.path.isfile(os.path.join(BENCH, *part))
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e


def test_each_cell_reports_enough():
    b = bench_json()
    for w in b["workloads"]:
        e2e = [m["name"] for m in b["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        per = [m for m in b["per_layer"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert per


@pytest.mark.parametrize("argv", [
    ["--workload", "rm2-bulk", "--seed", "1", "--seconds", "1",
     "--trace", "0"]])
def test_no_tpu_no_result(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py")] + argv,
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
