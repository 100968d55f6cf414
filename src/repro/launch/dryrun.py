"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:  jax.jit(fn, in_shardings=…).lower(*ShapeDtypeStructs)
→ .compile() → record memory_analysis(), cost_analysis() and the collective
schedule parsed from the post-SPMD HLO.  No arrays are ever allocated.

Results cache to results/dryrun/<cell>.json so the sweep is resumable; the
roofline report (launch/roofline.py) reads these JSONs.  Run as a script,
it forces 512 host devices (XLA_FLAGS, before jax starts) and exits
non-zero when any cell failed; importing the module changes nothing.

Usage:
  python -m repro.launch.dryrun                       # all cells, both meshes
  python -m repro.launch.dryrun --arch dlrm-rm2 --shape train_batch \
        --mesh single --embedding full
"""

import argparse
import json
import os
import re
import sys
import time
import traceback

import jax

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# HLO collective ops and the per-device wire-byte factor applied to the
# op's OUTPUT bytes (ring algorithms; see EXPERIMENTS.md §Methodology).
_COLL_FACTOR = {
    "all-gather": 1.0,          # receives (n-1)/n · out ≈ out
    "all-reduce": 2.0,          # reduce-scatter + all-gather
    "reduce-scatter": 1.0,      # sends (n-1)/n · in ≈ out · n ≈ … use out·1?
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute)"
    r"[-a-z]*\(", re.M)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def cost_dict(compiled) -> dict:
    """compiled.cost_analysis(), or {} when the backend reports none."""
    return compiled.cost_analysis() or {}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective in the compiled HLO."""
    out = {}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def wire_bytes(colls: dict) -> float:
    return sum(_COLL_FACTOR.get(op, 1.0) * rec["bytes"]
               for op, rec in colls.items())


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             embedding: str = "default", force: bool = False,
             save_hlo: bool = False) -> dict:
    from repro.dist import api as dist
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_context

    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    key = f"{arch_id}__{shape_name}__{mesh_name}__{embedding}".replace(
        "/", "_")
    path = os.path.join(RESULTS_DIR, key + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    ctx = make_context(multi_pod=multi_pod)
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "embedding": embedding, "ok": False}
    t0 = time.time()
    try:
        with dist.use(ctx):
            cell = build_cell(arch_id, shape_name, ctx, embedding)
            rec["cell_id"] = cell.cell_id
            rec["note"] = cell.note
            if cell.skip:
                rec.update(ok=True, skipped=cell.skip)
            else:
                rec["model_flops_per_step"] = cell.model_flops_per_step
                lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings
                                  ).lower(*cell.arg_shapes)
                t1 = time.time()
                compiled = lowered.compile()
                mem = compiled.memory_analysis()
                cost = cost_dict(compiled)
                hlo = compiled.as_text()
                colls = parse_collectives(hlo)
                rec.update(
                    ok=True,
                    lower_s=round(t1 - t0, 1),
                    compile_s=round(time.time() - t1, 1),
                    flops=cost.get("flops"),
                    bytes_accessed=cost.get("bytes accessed"),
                    memory={
                        "argument_bytes": mem.argument_size_in_bytes,
                        "output_bytes": mem.output_size_in_bytes,
                        "temp_bytes": mem.temp_size_in_bytes,
                        "alias_bytes": mem.alias_size_in_bytes,
                    },
                    collectives=colls,
                    collective_wire_bytes=wire_bytes(colls),
                    n_devices=int(len(ctx.mesh.devices.flat)),
                )
                if save_hlo:
                    with open(os.path.join(RESULTS_DIR, key + ".hlo"),
                              "w") as f:
                        f.write(hlo)
    except BaseException as e:       # record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def default_cells():
    """The 40 assigned cells (+ recsys embedding-substrate variants)."""
    from repro.configs import all_arch_ids, get_arch
    cells = []
    for arch in all_arch_ids():
        bundle = get_arch(arch)
        for shape in bundle.shapes:
            cells.append((arch, shape, "default"))
            if bundle.kind == "recsys":
                # the paper's full-table baseline + the community
                # compression baselines, through the same cells
                for emb in ("full", "hashed", "tt"):
                    cells.append((arch, shape, emb))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--embedding", default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    cells = default_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    if args.embedding:
        cells = [(a, s, args.embedding) for a, s, _ in cells
                 if _ == args.embedding or True]
        cells = list(dict.fromkeys(cells))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if not cells:
        print(f"no cells match --arch {args.arch} --shape {args.shape}",
              file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    failed = 0
    for arch, shape, emb in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, mp, emb, force=args.force,
                           save_hlo=args.save_hlo)
            failed += not rec.get("ok")
            status = ("SKIP " + rec.get("skipped", "")[:40]) if \
                rec.get("skipped") else \
                ("OK" if rec.get("ok") else "FAIL " + rec.get("error",
                                                              "")[:80])
            mesh_name = "multi" if mp else "single"
            print(f"[{mesh_name:6s}] {arch}/{shape}[{emb}]: {status} "
                  f"({rec.get('wall_s', 0)}s)", flush=True)
    if failed:
        print(f"{failed} cell(s) failed; see results/dryrun/*.json",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
