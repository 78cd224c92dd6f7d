import sys

from repro.launch.hostdev import ensure_host_devices

# The 512-device host platform is for the collective profiler only; the
# serve-stats mode runs real tiny engines and must keep the default
# single device.  (Either way this must precede jax import — see
# launch/hostdev.py; REPRO_SIM_DEVICES overrides the count.)
_SERVE_STATS = len(sys.argv) > 1 and sys.argv[1] == "serve-stats"
if not _SERVE_STATS:
    ensure_host_devices()

"""Per-op collective profile of one dry-run cell: the §Perf 'profiler'.

Prints the top collectives by loop-trip-multiplied wire bytes, with
shapes — the evidence the hypothesis loop needs.

  PYTHONPATH=src python scripts/profile_cell.py qwen3-32b prefill_32k \\
      single [key=value par overrides...]

A second mode surfaces the paged serve engine's device-resident tick
stats (occupied pages, pool utilization, shared-prefix hits — harvested
in sync(), zero per-tick transfers):

  PYTHONPATH=src python scripts/profile_cell.py serve-stats \\
      [page_size=8 num_pages=24 ticks=12]

With ``--cells N`` (ISSUE 10) serve-stats runs the data-parallel
CellRouter over N cells instead: per-group shared-prefix request waves
are routed by affinity + least-loaded page budget, and the report shows
per-cell occupancy/utilization/shared-prefix hits plus the fleet
aggregate (one stacked harvest for all cells):

  PYTHONPATH=src python scripts/profile_cell.py serve-stats --cells 3
"""
import json
from collections import defaultdict


def parse_overrides(args):
    out = {}
    args = list(args)
    while "--cells" in args:                  # --cells N == cells=N
        i = args.index("--cells")
        out["cells"] = int(args[i + 1])
        del args[i:i + 2]
    for a in args:
        k, v = a.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        elif v.isdigit():
            v = int(v)
        out[k] = v
    return out


def serve_stats(overrides):
    """Run a tiny paged engine for a few ticks and print the per-tick
    stats table sync() harvested — the observability surface of the
    paged KV cache (pool occupancy is what replaces per-slot capacity
    as the admission currency)."""
    import jax
    from repro.models import build_model
    from repro.models.config import ModelConfig, ParallelConfig
    from repro.serve import BatchedEngine, Request, ServeConfig

    page_size = overrides.get("page_size", 8)
    ticks = overrides.get("ticks", 12)
    cfg = ModelConfig(name="tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init_params(jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_slots=4, max_seq_len=64, eos_id=-1,
                       page_size=page_size,
                       num_pages=overrides.get("num_pages", 24))
    if overrides.get("cells", 1) > 1:
        serve_stats_fleet(model, params, scfg, overrides, ticks)
        return
    eng = BatchedEngine(model, params, scfg)

    shared = list(range(2, 2 + 2 * page_size))   # common "system prompt"
    reqs = [Request(rid=i, prompt=shared + [20 + i, 21 + i],
                    max_new_tokens=6) for i in range(4)]
    eng.admit(reqs)
    for _ in range(ticks):
        eng.step()
    eng.sync()

    print(f"serve-stats page_size={page_size} num_pages={eng.num_pages} "
          f"slots={scfg.batch_slots} ticks={eng.tick_count}")
    hdr = ("tick", "live_slots", "frontier_pages", "pool_occupied",
           "pool_util", "shared_hits")
    print(f"{hdr[0]:>5s} {hdr[1]:>10s} {hdr[2]:>14s} {hdr[3]:>13s} "
          f"{hdr[4]:>9s} {hdr[5]:>11s}")
    for row in eng.tick_stats:
        print(f"{row['tick']:5d} {row['live_slots']:10d} "
              f"{row['frontier_pages']:14d} "
              f"{row['pool_occupied_pages']:13d} "
              f"{row['pool_utilization']:9.2f} "
              f"{row['shared_prefix_hits']:11d}")


def serve_stats_fleet(model, params, scfg, overrides, ticks):
    """--cells N: the same tiny workload scaled out over a CellRouter.

    One wave of 4 requests per cell, each wave sharing its own 2-page
    prompt prefix: the wave's opener lands by least-loaded page budget,
    the followers ride prefix affinity onto the opener's cell — so the
    per-cell ``shared_hits`` column is the routing policy made visible.
    Ticks run with zero per-tick transfers; ONE stacked harvest in
    ``sync()`` drains the whole fleet."""
    from repro.serve import Request
    from repro.serve.router import make_cells

    n_cells = overrides["cells"]
    router = make_cells(model, params, scfg, n_cells)
    ps = scfg.page_size
    reqs, rid = [], 0
    for g in range(n_cells):
        shared = [2 + g * ps * 2 + i for i in range(2 * ps)]
        for j in range(4):
            reqs.append(Request(rid=rid, prompt=shared + [20 + j, 30 + g],
                                max_new_tokens=6))
            rid += 1
    admitted = router.admit(reqs)
    for _ in range(ticks):
        router.step()
    router.sync()

    print(f"serve-stats cells={n_cells} page_size={ps} "
          f"num_pages/cell={router.cells[0].num_pages} "
          f"slots/cell={scfg.batch_slots} ticks={router.tick_count} "
          f"admitted={admitted}/{len(reqs)}")
    hdr = ("cell", "ticks", "live", "slots", "occ_pages", "pool_util",
           "shared_hits")
    print(f"{hdr[0]:>4s} {hdr[1]:>5s} {hdr[2]:>4s} {hdr[3]:>5s} "
          f"{hdr[4]:>9s} {hdr[5]:>9s} {hdr[6]:>11s}")
    rows = router.cell_stats()
    for r in rows:
        print(f"{r['cell']:4d} {r['ticks']:5d} {r['live_slots']:4d} "
              f"{r['slots']:5d} {r['occupied_pages']:9d} "
              f"{r['utilization']:9.2f} {r['shared_prefix_hits']:11d}")
    occ = sum(r["occupied_pages"] for r in rows)
    cap = sum(c.num_pages for c in router.cells)
    hits = sum(r["shared_prefix_hits"] for r in rows)
    live = sum(r["live_slots"] for r in rows)
    print(f" agg {router.tick_count:5d} {live:4d} "
          f"{sum(r['slots'] for r in rows):5d} {occ:9d} "
          f"{occ / max(cap, 1):9.2f} {hits:11d}")


def main():
    if _SERVE_STATS:
        serve_stats(parse_overrides(sys.argv[2:]))
        return

    from repro.launch import cells as cells_lib
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.hlo_parser import HloModule

    arch, shape, mesh_kind = sys.argv[1:4]
    overrides = parse_overrides(sys.argv[4:])
    multi = mesh_kind == "multi"
    chips = 512 if multi else 256
    mesh = make_production_mesh(multi_pod=multi)
    cell = cells_lib.build_cell(arch, shape, mesh,
                                par_overrides=overrides or None)
    with jax.set_mesh(mesh):
        compiled = cell.lower().compile()
    mod = HloModule(compiled.as_text(), chips)
    entry = mod.entry or next(iter(mod.comps))
    recs = mod.comp_collectives(entry)

    # aggregate identical (op, shape, group) records
    agg = defaultdict(lambda: {"count": 0, "wire": 0.0})
    for r in recs:
        k = (r["op"], r["shape"], r["group_size"])
        agg[k]["count"] += r["count"]
        agg[k]["wire"] += r["wire_bytes"]
    top = sorted(agg.items(), key=lambda kv: -kv[1]["wire"])[:25]
    total = sum(v["wire"] for v in agg.values())
    print(f"cell {arch} x {shape} x {mesh_kind} overrides={overrides}")
    print(f"total wire {total / 1e9:.1f} GB/chip, "
          f"{int(sum(v['count'] for v in agg.values()))} ops")
    print(f"{'op':18s} {'shape':34s} {'grp':>4s} {'count':>7s} "
          f"{'wire GB':>9s} {'%':>5s}")
    for (op, shape_s, g), v in top:
        print(f"{op:18s} {shape_s:34s} {g:4d} {v['count']:7.0f} "
              f"{v['wire'] / 1e9:9.2f} {100 * v['wire'] / total:5.1f}")
    print(f"flops/chip {mod.comp_flops(entry):.3e}")


if __name__ == "__main__":
    main()
