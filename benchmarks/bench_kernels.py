"""Kernel × ISA-mode benchmark matrix -> BENCH_kernels.json.

Times every Pallas kernel under every primitive budget it supports
(abstract / abstract+shuffle / native / library) and pairs each wall-clock
with the kernel's *modeled* scratch traffic from ``structural_cost`` — so
the output shows both the outcome (time) and the §VII.C mechanism
(scratchpad round-trips the shuffle budget eliminates).  This file seeds
the repo's performance trajectory: re-run it after kernel changes and
diff the JSON.

  PYTHONPATH=src python benchmarks/bench_kernels.py            # full
  PYTHONPATH=src python benchmarks/bench_kernels.py --quick    # CI smoke
  PYTHONPATH=src python benchmarks/bench_kernels.py --out path.json
  PYTHONPATH=src python benchmarks/bench_kernels.py --compare OLD.json

``--compare`` is the regression gate: it diffs this run against a prior
JSON and exits non-zero when a (kernel, mode) row disappeared, when the
*modeled* structural cost regressed at the old row's recorded shape
(scratch/HBM bytes are backend-independent, so this check is meaningful
even when the sizings differ — it is how CI's --quick run gates against
the committed full-size baseline), or — when both runs share a backend +
sizing — when a median slowed past ``--threshold``.

Off-TPU the kernels run in Pallas interpret mode (see
``repro.kernels.ops.default_interpret``): absolute times are then
emulation times and only the *structure* columns are hardware-meaningful;
on a real TPU backend the same harness times compiled Mosaic kernels.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys

import jax
import jax.numpy as jnp

try:  # `python -m benchmarks.bench_kernels` (repo root on sys.path)
    from benchmarks.common import fmt_table, time_fn
except ModuleNotFoundError:  # `python benchmarks/bench_kernels.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import fmt_table, time_fn
from repro.core.registry import REGISTRY, ExecutionPolicy
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.kernels.fused import quantize_weight
from repro.models.attention import quantize_kv

KEY = jax.random.PRNGKey(0)


def _cases(quick: bool):
    """(kernel, case, run, shape) table for both sizings.

    The mode axis of the matrix is NOT listed here: it is enumerated from
    the lowering registry (each kernel's registered variants), so a newly
    registered variant shows up in the matrix without touching this file —
    and gemm's lack of a shuffle row falls out of its registration rather
    than a hardcoded mode list.  ``case`` labels the shape regime: "seq"
    (train/prefill-shaped, the historical rows) or "decode" (rows = the
    decode batch, sq = 1 against a cache — the shapes the ISSUE 5
    layout planner made fusion-legal on the serve tick)."""
    ks = jax.random.split(KEY, 8)
    if quick:
        n_red, rows_rms, d_rms = 1 << 15, 64, 256
        n_hist, bins = 1 << 14, 256
        b, h, s, hd, blk = 1, 2, 256, 64, 128
        m = k = n = 256
        b_dec, b_att, s_att = 8, 4, 256
        s_ssd, s_ssd2, p_ssd, p_ssd2, n_ssd, n_ssd2 = 256, 128, 32, 16, 32, 32
        b_ssdd, p_ssdd, n_ssdd = 8, 32, 32
        warmup, iters = 1, 3
    else:
        n_red, rows_rms, d_rms = 1 << 21, 1024, 1024
        n_hist, bins = 1 << 18, 256
        b, h, s, hd, blk = 1, 4, 1024, 64, 256
        m = k = n = 1024
        # decode rows: full decode batch for the rowwise fused ops; the
        # decode attention row runs a trimmed (batch, cache) — interpret
        # mode pays per grid cell, and the structural columns (what the
        # gate pins) are computed from the recorded shape either way
        b_dec, b_att, s_att = 128, 16, 512
        # the two canonical ssd tuning buckets (core/tuning.py): a long
        # prefill bucket and a short one that fits in a single chunk
        s_ssd, s_ssd2, p_ssd, p_ssd2, n_ssd, n_ssd2 = 1024, 256, 64, 64, 128, 64
        # the canonical ssd_decode tuning bucket: a full serve batch of
        # [N,P] states ticking one token (core/tuning.py ssd_decode rows)
        b_ssdd, p_ssdd, n_ssdd = 16, 64, 128
        warmup, iters = 2, 5

    n_proj = d_rms                       # norm -> square projection
    f_ff = d_rms                         # swiglu: [wi|wg] is [d, 2d]
    n_wo = h * hd                        # wo: square over the head concat
    # fresh streams for the fused cases (fold_in: the eight pre-existing
    # streams below keep their values and stay independent of these)
    kp, kr, kw, kwo = jax.random.split(jax.random.fold_in(KEY, 1), 4)
    w_cat = jax.random.normal(kw, (d_rms, 2 * f_ff), jnp.float32)
    w_o = jax.random.normal(kwo, (h * hd, n_wo), jnp.float32)
    x_red = jax.random.normal(ks[0], (n_red,), jnp.float32)
    x_rms = jax.random.normal(ks[1], (rows_rms, d_rms), jnp.float32)
    w_rms = jax.random.normal(ks[2], (d_rms,), jnp.float32) + 1.0
    p_rms = jax.random.normal(kp, (d_rms, n_proj), jnp.float32)
    r_rms = jax.random.normal(kr, (rows_rms, d_rms), jnp.float32)
    v_hist = jax.random.randint(ks[3], (n_hist,), 0, bins, jnp.int32)
    q = jax.random.normal(ks[4], (b, h, s, hd), jnp.float32)
    kk = jax.random.normal(ks[5], (b, h, s, hd), jnp.float32)
    vv = jax.random.normal(ks[6], (b, h, s, hd), jnp.float32)
    a_g = jax.random.normal(ks[7], (m, k), jnp.float32)
    b_g = jax.random.normal(ks[0], (k, n), jnp.float32)

    # decode-shaped streams: rows = decode batch, one query against a
    # skv-long cache with per-slot frontiers (the serve-tick shapes the
    # persisted [wq|wk|wv]/[wi|wg] layouts made fusion-legal)
    kd = jax.random.split(jax.random.fold_in(KEY, 2), 6)
    x_dec = jax.random.normal(kd[0], (b_dec, d_rms), jnp.float32)
    r_dec = jax.random.normal(kd[1], (b_dec, d_rms), jnp.float32)
    q_dec = jax.random.normal(kd[2], (b_att, h, 1, hd), jnp.float32)
    k_dec = jax.random.normal(kd[3], (b_att, h, s_att, hd), jnp.float32)
    v_dec = jax.random.normal(kd[4], (b_att, h, s_att, hd), jnp.float32)
    pos_dec = jax.random.randint(kd[5], (b_att,), 0, s_att, jnp.int32)

    # paged decode streams (ISSUE 6): same op through a page pool + block
    # table at the same (b_att, s_att) capacity, but with half-occupied
    # frontiers and sentinel dead entries — the recorded shape carries the
    # host-computed occupancy so the structural columns (and --compare's
    # recompute) account HBM by *occupied* pages, not capacity
    page = 128
    maxp = s_att // page
    occ = max(maxp // 2, 1)              # occupied pages per slot
    n_pool = b_att * maxp
    kpg = jax.random.split(jax.random.fold_in(KEY, 3), 2)
    k_pg = jax.random.normal(kpg[0], (n_pool, h, page, hd), jnp.float32)
    v_pg = jax.random.normal(kpg[1], (n_pool, h, page, hd), jnp.float32)
    tbl_ids = jnp.arange(n_pool, dtype=jnp.int32).reshape(b_att, maxp)
    tbl_pg = jnp.where(jnp.arange(maxp)[None, :] < occ, tbl_ids, n_pool)
    pos_pg = jnp.full((b_att,), occ * page - 1, jnp.int32)
    pages_occ = b_att * occ

    # quantized streams (ISSUE 7): the same decode/paged shapes through
    # the registered _q8 twins — int8 weights + per-channel scales (and,
    # on the paged row, int8 KV pages + per-token scale strips) so the
    # matrix records the weight/kv-stream cut next to the f32 rows it
    # undercuts.  Weights are quantized once here: the timed region sees
    # the serving steady state (dequantize-in-VMEM), not the one-time
    # quantization.
    # ssd streams (ISSUE 8): one fused chunked scan per (seq, p, n)
    # tuning bucket — h heads over g groups, dt positive via softplus,
    # A negative (decaying state), B/C scaled down so the chunk-boundary
    # state stays O(1) across the scan
    h_ssd, g_ssd = 4, 1
    kss = jax.random.split(jax.random.fold_in(KEY, 4), 5)
    x_ssd = jax.random.normal(kss[0], (1, s_ssd, h_ssd, p_ssd), jnp.float32)
    dt_ssd = jax.nn.softplus(jax.random.normal(
        kss[1], (1, s_ssd, h_ssd), jnp.float32))
    a_ssd = -jnp.exp(jax.random.normal(kss[2], (h_ssd,), jnp.float32) * 0.5)
    b_ssd = jax.random.normal(kss[3], (1, s_ssd, g_ssd, n_ssd),
                              jnp.float32) * 0.3
    c_ssd = jax.random.normal(kss[4], (1, s_ssd, g_ssd, n_ssd),
                              jnp.float32) * 0.3
    x_ssd2, dt_ssd2 = x_ssd[:, :s_ssd2, :, :p_ssd2], dt_ssd[:, :s_ssd2]
    b_ssd2, c_ssd2 = b_ssd[:, :s_ssd2, :, :n_ssd2], c_ssd[:, :s_ssd2, :, :n_ssd2]

    # ssd decode stream (ISSUE 9): one serve-batch tick of the batched
    # recurrence — b_ssdd resident [N,P] states, one token's x/dt/B/C
    ksd = jax.random.split(jax.random.fold_in(KEY, 5), 5)
    st_ssdd = jax.random.normal(
        ksd[0], (b_ssdd, g_ssd, h_ssd // g_ssd, n_ssdd, p_ssdd),
        jnp.float32) * 0.5
    x_ssdd = jax.random.normal(ksd[1], (b_ssdd, h_ssd, p_ssdd), jnp.float32)
    dt_ssdd = jax.nn.softplus(jax.random.normal(
        ksd[2], (b_ssdd, h_ssd), jnp.float32))
    a_ssdd = -jnp.exp(jax.random.normal(ksd[3], (h_ssd,), jnp.float32)
                      * 0.5)
    bc_ssdd = jax.random.normal(ksd[4], (2, b_ssdd, g_ssd, n_ssdd),
                                jnp.float32) * 0.3

    p_q, p_s = quantize_weight(p_rms)
    wc_q, wc_s = quantize_weight(w_cat)
    wo_q, wo_s = quantize_weight(w_o)
    k_pgq, k_pgs = quantize_kv(k_pg)
    v_pgq, v_pgs = quantize_kv(v_pg)

    def _q8_pol(mode):
        return ExecutionPolicy(mode=mode, precision="int8")

    cases = [
        ("reduction", "seq",
         lambda mode: ops.reduce_sum(x_red, mode=mode),
         dict(n=n_red)),
        ("rmsnorm", "seq",
         lambda mode: ops.rmsnorm(x_rms, w_rms, mode=mode),
         dict(rows=rows_rms, d=d_rms)),
        ("histogram", "seq",
         lambda mode: ops.histogram(v_hist, bins, mode=mode),
         dict(n=n_hist, num_bins=bins)),
        ("flash_attention", "seq",
         lambda mode: ops.flash_attention(q, kk, vv, causal=True,
                                          mode=mode, block_q=blk,
                                          block_kv=blk),
         dict(b=b, h=h, sq=s, skv=s, d=hd, causal=True,
              block_q=blk, block_kv=blk)),
        ("gemm", "seq",
         lambda mode: ops.matmul(a_g, b_g, mode=mode),
         dict(m=m, n=n, k=k)),
        # the fused multi-op lowerings: HBM traffic is the treatment here
        ("rmsnorm_matmul", "seq",
         lambda mode: ops.fused_rmsnorm_matmul(x_rms, w_rms, p_rms,
                                               mode=mode),
         dict(rows=rows_rms, d=d_rms, n=n_proj)),
        ("add_rmsnorm", "seq",
         lambda mode: ops.fused_add_rmsnorm(x_rms, r_rms, w_rms,
                                            mode=mode),
         dict(rows=rows_rms, d=d_rms)),
        ("rmsnorm_swiglu", "seq",
         lambda mode: ops.fused_rmsnorm_swiglu(x_rms, w_rms, w_cat,
                                               mode=mode),
         dict(rows=rows_rms, d=d_rms, f=f_ff)),
        ("flash_attention_matmul", "seq",
         lambda mode: ops.fused_flash_attention_matmul(
             q, kk, vv, w_o, causal=True, mode=mode, block_q=blk,
             block_kv=blk),
         dict(b=b, h=h, sq=s, skv=s, d=hd, n=n_wo, causal=True,
              block_q=blk, block_kv=blk)),
        # decode-shaped fused rows (ISSUE 5): the same registered ops at
        # the serve tick's shapes — structural columns pin the per-token
        # activation-round-trip saving at zero weight-traffic overhead
        ("rmsnorm_matmul", "decode",
         lambda mode: ops.fused_rmsnorm_matmul(x_dec, w_rms, p_rms,
                                               mode=mode),
         dict(rows=b_dec, d=d_rms, n=n_proj)),
        ("add_rmsnorm", "decode",
         lambda mode: ops.fused_add_rmsnorm(x_dec, r_dec, w_rms,
                                            mode=mode),
         dict(rows=b_dec, d=d_rms)),
        ("rmsnorm_swiglu", "decode",
         lambda mode: ops.fused_rmsnorm_swiglu(x_dec, w_rms, w_cat,
                                               mode=mode),
         dict(rows=b_dec, d=d_rms, f=f_ff)),
        ("flash_attention_matmul", "decode",
         lambda mode: ops.fused_flash_attention_matmul(
             q_dec, k_dec, v_dec, w_o, mode=mode, block_kv=blk,
             pos=pos_dec),
         dict(b=b_att, h=h, sq=1, skv=s_att, d=hd, n=n_wo, causal=False,
              block_kv=blk)),
        # paged decode (ISSUE 6): block-table gather, dead-entry skip;
        # hbm_bytes scales with pages_occupied rather than max_len —
        # compare() gates this row's hbm below the dense decode row's
        ("flash_attention_matmul", "decode_paged",
         lambda mode: ops.fused_flash_attention_matmul(
             q_dec, k_pg, v_pg, w_o, mode=mode, pos=pos_pg,
             block_tables=tbl_pg),
         dict(b=b_att, h=h, sq=1, skv=maxp * page, d=hd, n=n_wo,
              causal=False, block_kv=page, page_size=page,
              pages_occupied=pages_occ)),
        # fused chunked SSD scan (ISSUE 8): one grid, [N,P] state carried
        # in VMEM scratch across the sequential chunk axis — the rows
        # cover both canonical tuning buckets, and compare() gates each
        # mode's modeled hbm_bytes below the unfused six-dot pair's
        ("ssd_scan", "seq",
         lambda mode: ops.fused_ssd_scan(x_ssd, dt_ssd, a_ssd, b_ssd,
                                         c_ssd, mode=mode),
         dict(b=1, seq=s_ssd, h=h_ssd, p=p_ssd, g=g_ssd, n=n_ssd)),
        ("ssd_scan", "seq_short",
         lambda mode: ops.fused_ssd_scan(x_ssd2, dt_ssd2, a_ssd, b_ssd2,
                                         c_ssd2, mode=mode),
         dict(b=1, seq=s_ssd2, h=h_ssd, p=p_ssd2, g=g_ssd, n=n_ssd2)),
        ("ssd_decode", "decode",
         lambda mode: ops.fused_ssd_decode(st_ssdd, x_ssdd, dt_ssdd,
                                           a_ssdd, bc_ssdd[0], bc_ssdd[1],
                                           mode=mode),
         dict(b=b_ssdd, h=h_ssd, p=p_ssdd, g=g_ssd, n=n_ssdd)),
        # quantized decode rows (ISSUE 7): int8 weights dequantized in
        # VMEM — weight_stream_bytes must undercut the matching f32
        # decode row by >= 2x (compare() gates this); the paged row adds
        # int8 KV pages + scale strips, halving the kv stream as well
        ("rmsnorm_matmul_q8", "decode_q8",
         lambda mode: ops.fused_rmsnorm_matmul(
             x_dec, w_rms, p_q, w_scale=p_s, policy=_q8_pol(mode)),
         dict(rows=b_dec, d=d_rms, n=n_proj)),
        ("rmsnorm_swiglu_q8", "decode_q8",
         lambda mode: ops.fused_rmsnorm_swiglu(
             x_dec, w_rms, wc_q, w_scale=wc_s, policy=_q8_pol(mode)),
         dict(rows=b_dec, d=d_rms, f=f_ff)),
        ("flash_attention_matmul_q8", "decode_q8",
         lambda mode: ops.fused_flash_attention_matmul(
             q_dec, k_dec, v_dec, wo_q, pos=pos_dec, block_kv=blk,
             w_scale=wo_s, policy=_q8_pol(mode)),
         dict(b=b_att, h=h, sq=1, skv=s_att, d=hd, n=n_wo, causal=False,
              block_kv=blk)),
        ("flash_attention_matmul_q8", "decode_paged_q8",
         lambda mode: ops.fused_flash_attention_matmul(
             q_dec, k_pgq, v_pgq, wo_q, pos=pos_pg, block_tables=tbl_pg,
             w_scale=wo_s, k_scale=k_pgs, v_scale=v_pgs,
             policy=_q8_pol(mode)),
         dict(b=b_att, h=h, sq=1, skv=maxp * page, d=hd, n=n_wo,
              causal=False, block_kv=page, page_size=page,
              pages_occupied=pages_occ)),
        # mesh-shaped TP rows (ISSUE 10): the same decode-regime work
        # costed as its tensor-parallel twin over a 4-way model axis
        # (tp=4 rides in the recorded shape).  The structural columns
        # gain the collective_* keys (ring wire bytes, hops, the
        # hbm-equivalent toll) and a 1/4 weight stream; compare() gates
        # the declared term and the chip-side hbm cut against the
        # replicated base recomputed at the same geometry
        ("gemm_tp", "decode_tp",
         lambda mode: ops.run_op("gemm_tp", x_dec, p_rms, mode=mode),
         dict(m=b_dec, n=n_proj, k=d_rms, tp=4)),
        ("rmsnorm_matmul_tp", "decode_tp",
         lambda mode: ops.run_op("rmsnorm_matmul_tp", x_dec, w_rms,
                                 p_rms, mode=mode),
         dict(rows=b_dec, d=d_rms, n=n_proj, tp=4)),
        ("rmsnorm_swiglu_tp", "decode_tp",
         lambda mode: ops.run_op("rmsnorm_swiglu_tp", x_dec, w_rms,
                                 w_cat, mode=mode),
         dict(rows=b_dec, d=d_rms, f=f_ff, tp=4)),
        ("flash_attention_matmul_tp", "decode_tp",
         lambda mode: ops.run_op("flash_attention_matmul_tp", q_dec,
                                 k_dec, v_dec, w_o, causal=False,
                                 pos=pos_dec, block_kv=blk, mode=mode),
         dict(b=b_att, h=h, sq=1, skv=s_att, d=hd, n=n_wo, causal=False,
              block_kv=blk, tp=4)),
    ]
    return cases, warmup, iters


def run(quick: bool = False, out: str = "BENCH_kernels.json") -> dict:
    cases, warmup, iters = _cases(quick)
    rows = []
    for kernel, case, fn, shape in cases:
        for mode in REGISTRY.modes(kernel):
            timing = time_fn(lambda mode=mode, fn=fn: fn(mode),
                             warmup=warmup, iters=iters)
            cost = dict(REGISTRY.structural_cost(kernel, mode, **shape))
            rows.append({
                "kernel": kernel,
                "case": case,
                "mode": mode,
                "shape": shape,
                "median_s": timing["median_s"],
                "p25_s": timing["p25_s"],
                "p75_s": timing["p75_s"],
                "iters": timing["iters"],
                # the §VII.C mechanism columns (0 where not modeled)
                "scratch_bytes": cost.get("scratch_bytes_total", 0),
                "scratch_round_trips": cost.get(
                    "scratch_round_trips_per_block", 0),
                "lane_shuffles": cost.get("lane_shuffles_per_block", 0),
                "hbm_bytes": cost.get("hbm_bytes", 0),
                # the ISSUE 10 interconnect column (0 on chip-local rows)
                "collective_bytes": cost.get("collective_bytes", 0),
                "structural": cost,
            })
            print(f"[bench_kernels] {kernel:16s} {case:6s} {mode:17s} "
                  f"{timing['median_s'] * 1e3:9.2f} ms   "
                  f"scratch={cost.get('scratch_bytes_total', 0)}")

    result = {
        "meta": {
            "backend": jax.default_backend(),
            "interpret": ops.default_interpret(),
            "quick": quick,
            "jax": jax.__version__,
            "python": platform.python_version(),
            # the mode axis comes from registry enumeration, not a list
            "matrix": {op: list(REGISTRY.modes(op))
                       for op in REGISTRY.ops()},
        },
        "rows": rows,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)

    print()
    print(fmt_table(
        ["kernel", "case", "mode", "median_ms", "scratch_bytes",
         "round_trips", "shuffles", "coll_bytes"],
        [[r["kernel"], r["case"], r["mode"], f"{r['median_s'] * 1e3:.2f}",
          r["scratch_bytes"], r["scratch_round_trips"],
          r["lane_shuffles"], r["collective_bytes"]] for r in rows]))
    print(f"\n[bench_kernels] wrote {out} "
          f"({len(rows)} kernel×mode rows)")
    return result


def compare(old: dict, new: dict, threshold: float = 1.5) -> list:
    """Regression-diff two bench artifacts; returns failure strings.

    Three gates, strongest applicable wins:
    1. coverage — every old (kernel, mode) row must still exist in the
       new run's matrix (a dropped variant is a silent de-registration);
    2. structural — the new code's *modeled* cost, recomputed at the old
       row's recorded shape, must not exceed the old row's recorded
       scratch/HBM bytes (backend- and sizing-independent: this is the
       §VII.C currency, and the gate CI applies between its --quick run
       and the committed full-size baseline);
    3. timing — only when both runs share (backend, quick, interpret)
       and the row shapes match: new median must stay under
       ``threshold × old median``.
    Plus a cross-row invariant on the new run alone: every
    ``decode_paged`` row's modeled HBM must stay below its mode's dense
    ``decode`` row — the occupied-page traffic saving paging exists for.
    """
    failures = []
    new_matrix = new["meta"]["matrix"]
    meta_match = all(
        old.get("meta", {}).get(k) == new["meta"].get(k)
        for k in ("backend", "quick", "interpret"))
    # rows are keyed by (kernel, mode, case) so the decode-shaped fused
    # rows gate independently of the seq-shaped ones (pre-ISSUE-5
    # baselines carry no case field and default to "seq")
    new_rows = {(r["kernel"], r["mode"], r.get("case", "seq")): r
                for r in new["rows"]}
    new_cases = {(r["kernel"], r.get("case", "seq")) for r in new["rows"]}
    deltas = []
    for r in old["rows"]:
        kernel, mode = r["kernel"], r["mode"]
        case = r.get("case", "seq")
        if mode not in new_matrix.get(kernel, []):
            failures.append(f"{kernel}[{mode}]: variant disappeared from "
                            f"the registry matrix")
            continue
        if (kernel, case) not in new_cases:
            failures.append(f"{kernel} case {case!r}: shape regime "
                            f"disappeared from the benchmark matrix")
            continue
        shape = r.get("shape")
        if shape:
            cost = dict(REGISTRY.structural_cost(kernel, mode, **shape))
            for key, col in (("scratch_bytes_total", "scratch_bytes"),
                             ("hbm_bytes", "hbm_bytes")):
                if cost.get(key, 0) > r.get(col, 0):
                    failures.append(
                        f"{kernel}[{mode}] @ {shape}: modeled {col} "
                        f"regressed {r.get(col, 0)} -> {cost.get(key, 0)}")
        nr = new_rows.get((kernel, mode, case))
        if nr is None:
            continue
        if meta_match and shape and nr.get("shape") == shape:
            ratio = nr["median_s"] / max(r["median_s"], 1e-12)
            deltas.append([kernel, case, mode,
                           f"{r['median_s'] * 1e3:.2f}",
                           f"{nr['median_s'] * 1e3:.2f}", f"{ratio:.2f}x"])
            if ratio > threshold:
                failures.append(
                    f"{kernel}[{mode}] ({case}): median regressed "
                    f"{r['median_s'] * 1e3:.2f} -> "
                    f"{nr['median_s'] * 1e3:.2f} ms "
                    f"({ratio:.2f}x > {threshold}x)")
    # paged-vs-dense consistency gate (ISSUE 6): whenever both decode
    # regimes are present in the new run, the paged row's modeled HBM
    # must undercut the dense row's for the same mode — the block-table
    # walk only pays for occupied pages, and losing that saving is a
    # regression even when every row individually "improved"
    for (kernel, mode, case), nr in new_rows.items():
        if case != "decode_paged":
            continue
        dense = new_rows.get((kernel, mode, "decode"))
        if dense is None:
            continue
        if nr["hbm_bytes"] >= dense["hbm_bytes"]:
            failures.append(
                f"{kernel}[{mode}]: paged decode hbm_bytes "
                f"{nr['hbm_bytes']} not below dense decode "
                f"{dense['hbm_bytes']} — occupied-page saving lost")
    # fused-vs-pair gate (ISSUE 8): every fused row that models an
    # unfused pair must undercut it — a non-library mode whose fused
    # hbm_bytes reaches the pair's has lost the round-trip saving the
    # fusion exists for (the library row IS the pair, so it must match)
    for (kernel, mode, case), nr in new_rows.items():
        pair = nr["structural"].get("hbm_bytes_unfused_pair")
        if pair is None:
            continue
        if mode == "library":
            if nr["hbm_bytes"] != pair:
                failures.append(
                    f"{kernel}[library] ({case}): hbm_bytes "
                    f"{nr['hbm_bytes']} != unfused pair {pair} — the "
                    f"library row must BE the unfused pair")
        elif nr["hbm_bytes"] >= pair:
            failures.append(
                f"{kernel}[{mode}] ({case}): fused hbm_bytes "
                f"{nr['hbm_bytes']} not below unfused pair {pair} — "
                f"fusion saving lost")
    # quantized-vs-f32 stream gate (ISSUE 7): every ``_q8`` row's modeled
    # weight stream must stay at or below HALF its f32 twin's (same mode,
    # same shape regime) — the int8-weights-dequantized-in-VMEM saving
    # the variants exist for — and wherever both rows model a kv stream
    # (the paged regime), the int8-pages cut must hold at 2x as well.
    for (kernel, mode, case), nr in new_rows.items():
        if not kernel.endswith("_q8") or not case.endswith("_q8"):
            continue
        f32_row = new_rows.get((kernel[:-3], mode, case[:-3].rstrip("_")))
        if f32_row is None:
            continue
        st, f32_st = nr["structural"], f32_row["structural"]
        for col in ("weight_stream_bytes", "kv_stream_bytes"):
            if col not in st or col not in f32_st:
                continue
            if 2 * st[col] > f32_st[col]:
                failures.append(
                    f"{kernel}[{mode}] ({case}): modeled {col} "
                    f"{st[col]} exceeds 0.5x the f32 row's "
                    f"{f32_st[col]} — int8 stream saving lost")
    # collective-term gate (ISSUE 10): every mesh-shaped ``_tp`` row must
    # declare its interconnect term (kind + positive wire/hbm-equivalent
    # bytes at the recorded tp), and its chip-side hbm term must stay
    # below the replicated base recomputed at the same geometry — losing
    # either means "auto" can no longer see the TP-vs-replicated
    # crossover the twins exist for
    for (kernel, mode, case), nr in new_rows.items():
        if not kernel.endswith("_tp"):
            continue
        st = nr["structural"]
        if not st.get("collective") \
                or st.get("collective_bytes", 0) <= 0 \
                or st.get("collective_hbm_equiv_bytes", 0) <= 0:
            failures.append(
                f"{kernel}[{mode}] ({case}): mesh-shaped row declares "
                f"no collective term at tp={nr['shape'].get('tp')}")
            continue
        base_shape = {k: v for k, v in nr["shape"].items() if k != "tp"}
        base = dict(REGISTRY.structural_cost(kernel[:-3], mode,
                                             **base_shape))
        if nr["hbm_bytes"] >= base.get("hbm_bytes", 0):
            failures.append(
                f"{kernel}[{mode}] ({case}): sharded chip hbm "
                f"{nr['hbm_bytes']} not below the replicated base's "
                f"{base.get('hbm_bytes', 0)} — weight-shard saving lost")
    if deltas:
        print("\n[bench_kernels] timing deltas vs baseline:")
        print(fmt_table(["kernel", "case", "mode", "old_ms", "new_ms",
                         "ratio"], deltas))
    elif not meta_match:
        print("\n[bench_kernels] timing compare skipped (baseline meta "
              "differs: backend/sizing); structural gate still applied")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small sizes + few iters (CI smoke)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--compare", metavar="OLD.json", default=None,
                    help="regression-diff against a prior artifact; "
                    "exits non-zero past --threshold or on structural/"
                    "coverage regressions")
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="max allowed new/old median ratio (same-meta "
                    "runs only)")
    args = ap.parse_args()
    enable_compile_cache()
    result = run(quick=args.quick, out=args.out)
    if args.compare:
        with open(args.compare) as f:
            old = json.load(f)
        failures = compare(old, result, threshold=args.threshold)
        if failures:
            print(f"\n[bench_kernels] REGRESSIONS vs {args.compare}:")
            for fail in failures:
                print("  -", fail)
            raise SystemExit(1)
        print(f"\n[bench_kernels] compare vs {args.compare}: OK")


if __name__ == "__main__":
    main()
