"""Benchmark harness — one module per paper table.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call where a wall-clock
measurement exists; derived carries the table's headline quantity).
"""

from __future__ import annotations

import json
import sys
import time


def _emit(name, us, derived):
    print(f"{name},{us},{json.dumps(derived, sort_keys=True)}")


def _backends(fast: bool) -> None:
    """Per-backend lookup throughput + params -> BENCH_backends.json."""
    from benchmarks import backends_bench as bb
    t0 = time.monotonic()
    rows = bb.run(batch=2048 if fast else 8192, iters=4 if fast else 16)
    bb.write_json(rows)
    for r in rows:
        r = dict(r)
        _emit(r.pop("name"), r.pop("us_per_batch"), r)
    _emit("backends/wall_s", round((time.monotonic() - t0) * 1e6), {})


def _serving(fast: bool) -> None:
    """Serving-tier replay grid -> BENCH_serving.json (see
    benchmarks/table4_inference_throughput.serving_rows)."""
    from benchmarks import table4_inference_throughput as t4
    t0 = time.monotonic()
    rows = t4.serving_rows(fast=fast)
    t4.write_serving_json(rows)
    for r in rows:
        r = dict(r)
        _emit(r.pop("name"), "", r)
    _emit("serving/wall_s", round((time.monotonic() - t0) * 1e6), {})


def main() -> None:
    fast = "--fast" in sys.argv
    print("name,us_per_call,derived")

    if "--backends-only" in sys.argv:
        _backends(fast)
        return

    if "--serving-only" in sys.argv:
        _serving(fast)
        return

    _backends(fast)
    _serving(fast)

    from benchmarks import table1_memory_fetches as t1
    t0 = time.monotonic()
    for r in t1.run():
        _emit(r.pop("name"), "", r)
    _emit("table1/wall_s", round((time.monotonic() - t0) * 1e6), {})

    from benchmarks import table2_criteotb_auc as t2
    t0 = time.monotonic()
    for r in t2.run(steps=80 if fast else 240):
        _emit(r.pop("name"), "", r)
    _emit("table2/wall_s", round((time.monotonic() - t0) * 1e6), {})

    from benchmarks import table3_kaggle_models as t3
    t0 = time.monotonic()
    for r in t3.run(steps=40 if fast else 120):
        _emit(r.pop("name"), "", r)
    _emit("table3/wall_s", round((time.monotonic() - t0) * 1e6), {})

    from benchmarks import table4_inference_throughput as t4
    t0 = time.monotonic()
    for r in t4.run(batch=4096 if fast else 16384):
        n = r.pop("name")
        sps = r.get("samples_per_s")
        us = round(1e6 / sps * 16384) if sps else ""
        _emit(n, us, r)
    _emit("table4/wall_s", round((time.monotonic() - t0) * 1e6), {})


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
