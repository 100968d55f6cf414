#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  The run makes its weights and traffic from ``--seed``, warms
up (set-up), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared beside its limit, which are also the
last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"run: no workload {args.workload!r}; cells: {sorted(cells)}")
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"run: no TPU found (jax platform is {devices[0].platform!r}); "
            f"the benchmark runs on a TPU only")
        return 2
    chips = cells[args.workload]["chips"]
    if len(devices) < chips:
        log(f"run: {args.workload} needs {chips} chips, found "
            f"{len(devices)}")
        return 2

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench.harness import run_cell

    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, platform="tpu", log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
