"""Shared set-up of the benchmark's own tests, run by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Cells run here at a small size on the CPU (``small_cell``), with the
harness's look for a chip skipped by calling ``run_cell`` directly.
"""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402

SMALL_VOCABS = [1000, 500, 2000, 100, 50, 300]


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """Tests keep no persistent compile cache."""
    monkeypatch.setattr("repro.launch.cache.use_compile_cache",
                        lambda: "off")


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def small_cell(name: str) -> dict:
    """The cell's configuration, traffic and limits at a CPU size: every
    width cut, the batch and load cut, the structure kept."""
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    cfg.update(vocab_sizes=SMALL_VOCABS, embed_dim=16, bot_mlp=[32, 16],
               top_mlp=[16, 1], robe_block=8,
               robe_size=max(512, sum(SMALL_VOCABS) * 16 // 1000))
    tr = harness.load_json("traffic", cell["traffic"] + ".json")
    tr.update({"bulk": dict(batch=1024, pool=2),
               "train": dict(batch=512, pool=4)}[tr["driver"]])
    return {"config": cfg, "traffic": tr,
            "limits": harness.load_json("limits", name + ".json")}


def run_small(name: str, seed: int = 2 ** 31 + 12345, seconds: float = 0.5,
              keep=None) -> dict:
    return harness.run_cell(bench_json(), name, seed, seconds, False,
                            time.perf_counter(), platform="cpu",
                            log=lambda m: None, keep=keep, **small_cell(name))
