from repro.launch.hostdev import ensure_host_devices
ensure_host_devices()
# The two lines above MUST run before any jax import: jax freezes the
# device count at first initialization, and the production-mesh dry-run
# needs 512 placeholder host devices (REPRO_SIM_DEVICES overrides).
# Only entrypoints do this — tests and benchmarks see the real device.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. builds the cell (full-size config, ShapeDtypeStruct inputs, explicit
     in/out shardings — launch/cells.py),
  3. ``jit(...).lower(**specs).compile()`` — success proves the sharding
     config is coherent end-to-end (no allocation anywhere),
  4. prints ``compiled.memory_analysis()`` (fits-in-HBM evidence) and
     ``compiled.cost_analysis()`` (FLOPs/bytes for §Roofline),
  5. parses the post-optimization HLO for collective wire bytes,
  6. writes one JSON artifact per cell under --out for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --mesh single --out results/dryrun
"""
import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.launch import cells as cells_lib
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES, shape_applicable
from repro.roofline import analysis as roofline
from repro.roofline.hlo_parser import analyze_hlo


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             par_overrides=None, verbose: bool = True) -> dict:
    multi = mesh_kind == "multi"
    chips = 512 if multi else 256
    mesh = make_production_mesh(multi_pod=multi)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": chips, "status": "ok",
           "par_overrides": par_overrides or {}}
    t0 = time.time()
    try:
        cell = cells_lib.build_cell(arch, shape_name, mesh,
                                    par_overrides=par_overrides)
        with jax.set_mesh(mesh):
            lowered = cell.lower()
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        mem_stats = {}
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            mem_stats[field] = getattr(mem, field, None)
        if verbose:
            print(f"  memory_analysis: {mem_stats}")
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        cost = dict(cost) if cost else {}
        xla_flops = float(cost.get("flops", 0.0) or 0.0)
        xla_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)

        # Loop-trip-count-aware analysis (cost_analysis counts while
        # bodies once — useless under scanned layers; see hlo_parser).
        hlo = compiled.as_text()
        h = analyze_hlo(hlo, chips)
        flops = h["flops"]
        bytes_accessed = h["hbm_bytes"]
        csum = h["collectives"]
        if verbose:
            print(f"  hlo analysis: flops={flops:.3e} "
                  f"bytes={bytes_accessed:.3e} "
                  f"(xla one-iteration: flops={xla_flops:.3e})")

        cfg = cell.cfg
        shape = cell.shape
        mflops = roofline.model_flops(cfg, shape)
        # Memory term: compulsory-traffic model (the CPU HLO's fusion
        # granularity overstates TPU HBM traffic ~10×; the HLO surface
        # count is recorded alongside as the pessimistic bound).
        model_axis = 16
        wsh = chips if cell.par.fsdp else model_axis
        analytic = roofline.analytic_hbm_bytes(
            cfg, shape, chips, weight_shards=wsh,
            kv_cache_int8=cell.par.kv_cache_int8)
        terms = roofline.roofline_terms(
            flops_per_chip=flops,
            bytes_per_chip=analytic["total"],
            wire_bytes_per_chip=csum["total_wire_bytes"],
            chips=chips, mflops=mflops)

        rec.update(
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            kind=cell.kind,
            memory=mem_stats,
            flops_per_chip=flops,
            bytes_per_chip=analytic["total"],
            bytes_breakdown=analytic,
            hlo_surface_bytes_per_chip=bytes_accessed,
            xla_cost={"flops": xla_flops, "bytes": xla_bytes},
            collectives=csum,
            roofline=terms,
            hlo_bytes=len(hlo),
        )
        if verbose:
            print(f"  collectives: {csum['n_ops']} ops, "
                  f"{csum['total_wire_bytes']:.3e} wire B/chip")
            print(f"  roofline: compute={terms['t_compute_s']:.4f}s "
                  f"memory={terms['t_memory_s']:.4f}s "
                  f"collective={terms['t_collective_s']:.4f}s "
                  f"dominant={terms['dominant']} "
                  f"fraction={terms['roofline_fraction']:.3f}")
    except Exception as e:  # noqa: BLE001 — recorded, the matrix must finish
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"  ERROR {type(e).__name__}: {e}")
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--par", nargs="*", default=[],
                    help="ParallelConfig overrides, key=value")
    args = ap.parse_args()

    par_overrides = {}
    for kv in args.par:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            par_overrides[k] = v == "True"
        elif v.isdigit():
            par_overrides[k] = int(v)
        else:
            par_overrides[k] = v

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    if args.all:
        archs = list(configs.ARCHS)
        shapes = list(SHAPES)
    else:
        archs = [args.arch] if args.arch else list(configs.ARCHS)
        shapes = [args.shape] if args.shape else list(SHAPES)

    results = []
    for arch in archs:
        cfg = configs.get_config(arch)
        for shape_name in shapes:
            shape = SHAPES[shape_name]
            if not shape_applicable(cfg, shape):
                print(f"[SKIP] {arch} × {shape_name}: long_500k needs "
                      f"sub-quadratic attention")
                results.append({"arch": arch, "shape": shape_name,
                                "status": "skip",
                                "reason": "full-attention arch"})
                continue
            for mesh_kind in meshes:
                fname = os.path.join(
                    args.out, f"{args.tag}_{arch}_{shape_name}_{mesh_kind}.json")
                if args.skip_existing and os.path.exists(fname):
                    print(f"[CACHED] {arch} × {shape_name} × {mesh_kind}")
                    continue
                print(f"[RUN] {arch} × {shape_name} × {mesh_kind}"
                      + (f" par={par_overrides}" if par_overrides else ""))
                rec = run_cell(arch, shape_name, mesh_kind,
                               par_overrides=par_overrides or None)
                results.append(rec)
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"  -> {fname} ({rec['status']}, {rec['total_s']}s)")

    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_err = sum(1 for r in results if r.get("status") == "error")
    n_skip = sum(1 for r in results if r.get("status") == "skip")
    print(f"\ndry-run complete: {n_ok} ok, {n_err} error, {n_skip} skip")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
