"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms (per device; the HLO module IS the per-device SPMD program):
    compute    = HLO_FLOPs_dev / peak_FLOPs        (197 TF/s bf16, v5e)
    memory     = HLO_bytes_dev / HBM_bw            (819 GB/s)
    collective = wire_bytes_dev / link_bw          (~50 GB/s/link ICI)

Correction: XLA's cost analysis counts a ``while`` (lax.scan) body ONCE, so
for scan-over-layers LMs we compile two shallow probes (same width, L=k and
L=k+1) and extrapolate:  total = probe(k) + (L_full − k)·Δ, where
Δ = probe(k+1) − probe(k).  The same correction applies to the parsed
collective bytes (the body's collectives also appear once).

Run as a script it forces 512 host devices (XLA_FLAGS, before jax
starts) for its probes; importing the module changes nothing.
"""

import argparse
import json
import os
from typing import Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s
LINK_BW = 50e9               # B/s per ICI link

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "roofline")


def _load(key: str) -> Optional[dict]:
    p = os.path.join(RESULTS_DIR, key + ".json")
    if os.path.exists(p):
        with open(p) as f:
            return json.load(f)
    return None


def run_probe(arch_id: str, shape_name: str, n_layers: int,
              embedding: str = "default", force: bool = False) -> dict:
    """Compile a shallow-layer variant of an LM cell (single-pod mesh)."""
    from repro.configs import get_arch
    from repro.dist import api as dist
    from repro.launch import dryrun
    from repro.launch.cells import build_lm_cell
    from repro.launch.mesh import make_context
    import jax

    key = (f"{arch_id}__{shape_name}__single__{embedding}"
           f"__probeL{n_layers}").replace("/", "_")
    path = os.path.join(RESULTS_DIR, key + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    bundle = get_arch(arch_id)
    ctx = make_context(multi_pod=False)
    rec = {"arch": arch_id, "shape": shape_name, "probe_layers": n_layers,
           "ok": False}
    try:
        with dist.use(ctx):
            # monkey-layer: build the cell with an n_layers override
            emb = "full" if embedding == "default" else embedding
            orig = bundle.make_config

            def patched(variant="full", **kw):
                kw.pop("embedding", None)
                kw["n_layers"] = n_layers
                kw["scan_layers"] = False   # unrolled: exact per-layer cost
                # NOTE: q_chunk stays at the production value — the chunk
                # scan's body holds no collectives (attention is local per
                # head shard), so only its einsum FLOPs are undercounted
                # (≤ ~20% of the compute term; see EXPERIMENTS.md §Method).
                return orig(variant, embedding=emb, **kw)

            object.__setattr__(bundle, "make_config", patched)
            try:
                cell = build_lm_cell(arch_id, shape_name, ctx, emb)
            finally:
                object.__setattr__(bundle, "make_config", orig)
            lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings
                              ).lower(*cell.arg_shapes)
            compiled = lowered.compile()
            cost = dryrun.cost_dict(compiled)
            colls = dryrun.parse_collectives(compiled.as_text())
            rec.update(ok=True, flops=cost.get("flops"),
                       bytes_accessed=cost.get("bytes accessed"),
                       collectives=colls,
                       collective_wire_bytes=dryrun.wire_bytes(colls))
    except BaseException as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def corrected_terms(arch_id: str, shape_name: str,
                    embedding: str = "default",
                    mesh: str = "single") -> Optional[dict]:
    """Roofline terms with the scan-body correction where applicable.

    ``mesh`` selects which dry-run artifact set to read ("single" or
    "multi" — the committed 2×16×16 sweep); the scan-body probe correction
    compiles single-pod probes, so it only applies to mesh="single".
    """
    from repro.configs import get_arch
    bundle = get_arch(arch_id)
    key = f"{arch_id}__{shape_name}__{mesh}__{embedding}".replace("/", "_")
    full = _load(key)
    if full is None or not full.get("ok") or full.get("skipped"):
        return None

    flops = full.get("flops") or 0.0
    byts = full.get("bytes_accessed") or 0.0
    wire = full.get("collective_wire_bytes") or 0.0

    emb_cost = None
    if bundle.kind == "recsys":
        # the substrate's own cost model (params / HBM bytes / flops per
        # step) — read from the backend, not recomputed here
        from repro.nn.embedding_backends import get_backend
        emb_name = {"default": "robe", "full2d": "full"}.get(embedding,
                                                             embedding)
        spec = bundle.make_config("full",
                                  embedding=emb_name).embedding_spec()
        shp = bundle.shapes[shape_name]
        b = shp.get("batch") or shp.get("n_candidates") or 0
        emb_cost = get_backend(spec.kind).cost(spec, b)

    corr = None
    if bundle.kind == "lm" and mesh == "single":
        cfg = bundle.make_config("full")
        fk = cfg.first_k_dense
        k = fk + 2
        p1 = run_probe(arch_id, shape_name, k, embedding)
        p2 = run_probe(arch_id, shape_name, k + 1, embedding)
        if p1.get("ok") and p2.get("ok"):
            def extrap(f1, f2):
                d = (f2 or 0.0) - (f1 or 0.0)
                return (f2 or 0.0) + (cfg.n_layers - (k + 1)) * d
            flops = extrap(p1.get("flops"), p2.get("flops"))
            byts = extrap(p1.get("bytes_accessed"), p2.get("bytes_accessed"))
            wire = extrap(p1.get("collective_wire_bytes"),
                          p2.get("collective_wire_bytes"))
            corr = {"probe_k": k,
                    "delta_flops": (p2.get("flops") or 0)
                    - (p1.get("flops") or 0)}

    t_compute = flops / PEAK_FLOPS
    t_memory = byts / HBM_BW
    t_coll = wire / LINK_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    model_flops = full.get("model_flops_per_step") or 0.0
    n_dev = full.get("n_devices", 256)
    hlo_flops_global = flops * n_dev
    return {
        "cell": f"{arch_id}/{shape_name}[{embedding}]",
        "flops_dev": flops, "bytes_dev": byts, "wire_dev": wire,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": model_flops,
        "useful_ratio": (model_flops / hlo_flops_global
                         if hlo_flops_global else None),
        "roofline_fraction": (t_compute / max(t_compute, t_memory, t_coll)
                              if max(t_compute, t_memory, t_coll) > 0
                              else None),
        "mem_args_gb": full["memory"]["argument_bytes"] / 1e9,
        "mem_temp_gb": full["memory"]["temp_bytes"] / 1e9,
        "scan_corrected": corr is not None,
        "embedding_cost": emb_cost,
        "note": full.get("note", ""),
    }


LEVERS = {
    "compute": "raise MXU utilization: larger per-device tiles / fewer "
               "recompute passes (remat policy) / fuse elementwise chains",
    "memory": "cut HBM traffic: bf16 activations end-to-end, fuse "
              "gather+reduce (Pallas robe_lookup), chunk the CE/logits",
    "collective": "cut wire bytes: reduce-scatter instead of all-reduce, "
                  "overlap dispatch all_to_alls with expert compute, "
                  "shrink MoE capacity factor / quantize exchanged grads",
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", default=os.path.join(OUT_DIR,
                                                    "roofline.json"))
    args = ap.parse_args()
    from repro.configs import all_arch_ids, get_arch

    rows = []
    for arch in all_arch_ids():
        bundle = get_arch(arch)
        for shape in bundle.shapes:
            embs = ["default"] + (["full", "hashed", "tt"]
                                  if bundle.kind == "recsys" else [])
            for e in embs:
                r = corrected_terms(arch, shape, e)
                if r is None:
                    key = f"{arch}__{shape}__single__{e}".replace("/", "_")
                    raw = _load(key)
                    if raw and raw.get("skipped"):
                        rows.append({"cell": f"{arch}/{shape}[{e}]",
                                     "skipped": raw["skipped"]})
                    continue
                r["lever"] = LEVERS[r["dominant"]]
                rows.append(r)
                print(f"{r['cell']:55s} C={r['t_compute_s']*1e3:9.2f}ms "
                      f"M={r['t_memory_s']*1e3:9.2f}ms "
                      f"N={r['t_collective_s']*1e3:9.2f}ms "
                      f"dom={r['dominant']:10s} "
                      f"useful={r['useful_ratio'] or 0:.2f}", flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(args.write, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {args.write} ({len(rows)} rows)")


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
