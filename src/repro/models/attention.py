"""Attention implementations for training / prefill / decode.

Three execution paths:

1. ``chunked_causal`` — pure-jnp online-softmax (flash) attention, memory
   bounded by (chunk_q × chunk_kv) logits.  This is what the multi-pod
   dry-run lowers (compilable for any backend).  Baseline visits every
   (q-chunk, kv-chunk) pair and masks — O(S²) compute.
2. ``exact_causal`` (ParallelConfig.causal_folding) — python-unrolled
   q-chunks, each scanning only its causal kv prefix: exact triangle
   compute, ~2× FLOP reduction at long sequence.  A §Perf lever visible in
   ``cost_analysis``.
3. The Pallas flash kernel (kernels/attention.py) — the TPU-native path,
   numerically identical (tests assert so), selected on real TPU backends.

All paths implement GQA without materializing repeated KV heads: q is
viewed as [B, Hkv, G, S, D] and contracted against [B, Hkv, S, D].
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import paged_decode
from repro.parallel.sharding import ShardCtx, shard

NEG_INF = -1e30


def _pad_axis(x, axis: int, multiple: int):
    pad = (-x.shape[axis]) % multiple
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        x = jnp.pad(x, cfg)
    return x, x.shape[axis] // multiple


def _chunk_step(qc, kc, vc, carry, row_ids, col_ids, causal):
    """One online-softmax update.  qc: [B,Hkv,G,cq,D], kc/vc: [B,Hkv,ck,D]."""
    m_prev, l_prev, acc = carry
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qc.astype(jnp.float32),
                   kc.astype(jnp.float32))
    if causal:
        mask = col_ids[None, :] <= row_ids[:, None]        # (cq, ck)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_cur = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * corr + jnp.einsum("bkgqc,bkcd->bkgqd", p,
                                  vc.astype(jnp.float32))
    return m_cur, l_cur, acc


def chunked_attention(q, k, v, *, causal: bool = True,
                      kv_offset: int = 0, chunk_q: int = 512,
                      chunk_kv: int = 1024, exact_causal: bool = False,
                      scale: Optional[float] = None,
                      ctx: Optional[ShardCtx] = None):
    """q: [B,H,Sq,D]; k/v: [B,Hkv,Skv,D] -> [B,H,Sq,D]."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0
    g = h // hkv
    if scale is None:
        scale = d ** -0.5
    q = q * jnp.asarray(scale, q.dtype)

    chunk_q = min(chunk_q, sq)
    chunk_kv = min(chunk_kv, skv)
    qg = q.reshape(b, hkv, g, sq, d)
    qg, nq = _pad_axis(qg, 3, chunk_q)
    kp, nk = _pad_axis(k, 2, chunk_kv)
    vp, _ = _pad_axis(v, 2, chunk_kv)
    sqp, skvp = qg.shape[3], kp.shape[2]
    # padded kv columns must never win the softmax
    kv_valid = jnp.arange(skvp) < skv

    qs = jnp.moveaxis(qg.reshape(b, hkv, g, nq, chunk_q, d), 3, 0)
    ks = jnp.moveaxis(kp.reshape(b, hkv, nk, chunk_kv, d), 2, 0)
    vs = jnp.moveaxis(vp.reshape(b, hkv, nk, chunk_kv, d), 2, 0)
    col_base = jnp.arange(chunk_kv)
    row_base = jnp.arange(chunk_q)

    def init_carry():
        return (jnp.full((b, hkv, g, chunk_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, hkv, g, chunk_q, 1), jnp.float32),
                jnp.zeros((b, hkv, g, chunk_q, d), jnp.float32))

    def finish(carry):
        _, l, acc = carry
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l).astype(q.dtype)

    def kv_scan(qc, qi: int | jax.Array, n_kv_chunks: int):
        def body(carry, inp):
            ki, kc, vc = inp
            rows = qi * chunk_q + row_base + kv_offset
            cols = ki * chunk_kv + col_base
            cols_ok = cols < skv
            kc = jnp.where(cols_ok[None, None, :, None], kc, 0)
            rows_mask = jnp.where(cols_ok, cols, skv + sqp + kv_offset)
            carry = _chunk_step(qc, kc, vc, carry, rows, rows_mask,
                                causal=True)
            return carry, None
        xs = (jnp.arange(n_kv_chunks), ks[:n_kv_chunks], vs[:n_kv_chunks])
        carry, _ = jax.lax.scan(body, init_carry(), xs)
        return finish(carry)

    if exact_causal and causal and kv_offset == skv - sq:
        # §Perf path: unroll q-chunks in python; chunk i scans only its
        # causal prefix — exact-triangle FLOPs, visible in cost_analysis.
        outs = []
        off_chunks = kv_offset // chunk_kv
        for qi in range(nq):
            last_col = qi * chunk_q + chunk_q - 1 + kv_offset
            n_kv = min(nk, last_col // chunk_kv + 1)
            outs.append(kv_scan(qs[qi], qi, max(n_kv, 1)))
        out = jnp.stack(outs, axis=0)
    else:
        def q_body(_, qin):
            qi, qc = qin
            if causal:
                o = kv_scan(qc, qi, nk)
            else:
                def body(carry, inp):
                    ki, kc, vc = inp
                    cols = ki * chunk_kv + col_base
                    rows = jnp.full((chunk_q,), skvp + sqp, jnp.int32)
                    cols_m = jnp.where(cols < skv, cols, skvp + sqp + 1)
                    # non-causal: mask only padded kv columns
                    carry = _chunk_step(qc, kc, vc, carry, rows, cols_m,
                                        causal=True)
                    return carry, None
                carry, _ = jax.lax.scan(body, init_carry(),
                                        (jnp.arange(nk), ks, vs))
                o = finish(carry)
            return None, o
        _, out = jax.lax.scan(q_body, None, (jnp.arange(nq), qs))

    out = jnp.moveaxis(out, 0, 3)                    # [B,Hkv,G,nq,cq,D]
    out = out.reshape(b, hkv, g, sqp, d)[:, :, :, :sq]
    return out.reshape(b, h, sq, d)


def decode_attention(q, k_cache, v_cache, pos, *,
                     scale: Optional[float] = None,
                     ctx: Optional[ShardCtx] = None):
    """Single-token attention against a cache.

    q: [B,H,1,D]; caches: [B,Hkv,S,D]; pos: [B] int32 — number of valid
    cache entries per sequence (the new token sits at index pos).
    """
    b, h, _, d = q.shape
    _, hkv, s, _ = k_cache.shape
    g = h // hkv
    if scale is None:
        scale = d ** -0.5
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None] <= pos[:, None]            # [B,S]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h, 1, d).astype(q.dtype)


def update_cache(cache, new, pos):
    """Insert new [B,Hkv,1,D] at index pos [B] — one-hot masked write
    (GSPMD-friendly for seq-sharded caches; see DESIGN.md §4)."""
    b, hkv, s, d = cache.shape
    onehot = (jnp.arange(s)[None] == pos[:, None])         # [B,S]
    return jnp.where(onehot[:, None, :, None], new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# Paged KV cache: fixed-size pages + per-slot block tables (vLLM-style).
# Pages are token-major, [P, page_size, Hkv, D]: a token's row for every kv
# head is contiguous, so the one-token write is one scatter of [Hkv, D]
# rows (one pool per layer; the page index axis is shared across layers).
# Their int8 scales sit in [P, rows, lanes] pools (:func:`scale_page_shape`):
# the same values in the same order as [P, page_size, Hkv, 1], folded so
# that a page is whole (8, 128) tiles and the stored layout is the one the
# scatter and the flat carry take, whatever the page count.
# The Pallas paged kernel reads (page, head) tiles of head-major pages,
# [P, Hkv, page_size, D] with [P, Hkv, page_size, 1] scales, and writes
# them through ``token_major=False``.
# Block tables: [B, max_pages] int32 — entry j is the page holding tokens
# [j*page_size, (j+1)*page_size); entries past a slot's reservation hold
# the sentinel value ``P`` (out of range), so the scatter write drops and
# the gather clamp reads a page whose contents are masked anyway.  The
# serve engine owns allocation/refcounts (repro.serve.engine.PagePool);
# everything here is pure device math.
# ---------------------------------------------------------------------------


def scale_page_shape(page_size: int, num_kv_heads: int):
    """A token-major scale page's ``(rows, lanes)``: its ``page_size·Hkv``
    per-(token, head) scales in token-major order, in rows of 128 lanes
    where a token's scales never straddle two rows (one (8, 128) tile at
    page 128 and 8 kv heads), else in one row."""
    n = page_size * num_kv_heads
    lanes = 128 if n % 128 == 0 and 128 % num_kv_heads == 0 else n
    return n // lanes, lanes


def _table_rows(block_tables, pos, page_size: int):
    """The (page, row) each slot's logical index ``pos`` [B] resolves to
    through the table."""
    page_of = jnp.take_along_axis(
        block_tables, (pos // page_size)[:, None], axis=1)[:, 0]    # [B]
    return page_of, pos % page_size


def update_paged_cache(pages, new, block_tables, pos, *,
                       token_major: bool = True):
    """Insert new [B,Hkv,1,D] at logical index pos [B] through the table.

    The write resolves to page ``block_tables[b, pos[b] // page_size]`` at
    row ``pos[b] % page_size``.  A sentinel table entry (== num_pages, the
    engine's reset value for dead/reaped slots) makes the write **drop** —
    a freed slot whose ``pos`` keeps advancing inside the one-program tick
    can never touch a page that was handed to another request.  Indices
    past the table end clamp (jax gather semantics) onto the slot's own
    last entry, which the engine guarantees is never a shared page.
    """
    page_of, offset = _table_rows(block_tables, pos,
                                  pages.shape[1 if token_major else 2])
    rows = new[:, :, 0, :].astype(pages.dtype)                      # [B,Hkv,D]
    if token_major:
        # a pool of [P, page_size, Hkv·D] rows takes them flattened
        rows = rows.reshape(rows.shape[:1] + pages.shape[2:])
        return pages.at[page_of, offset].set(rows, mode="drop")
    return pages.at[page_of, :, offset].set(rows, mode="drop")


def pages_read(block_tables, pos, page_size: int, num_pages: int):
    """[B] pages the paged decode kernel reads for each slot at frontiers
    ``pos``: ``min(pos // page_size + 1, max_pages)`` (a finished slot's
    ``pos`` runs on past its table), 0 where the slot's first entry is
    the sentinel ``num_pages``."""
    n = jnp.minimum(pos // page_size + 1, block_tables.shape[1])
    return jnp.where(block_tables[:, 0] < num_pages, n, 0).astype(jnp.int32)


def gather_paged_kv(pages, block_tables, head_dim: Optional[int] = None):
    """[P,page_size,Hkv,D] + [B,max_pages] -> a [B,Hkv,S,D] logical strip
    (``[P,page_size,Hkv·D]`` pools, heads flattened into their rows, give
    ``head_dim``).

    Sentinel/dead entries clamp to the last real page; whatever they read
    sits past every consumer's ``pos`` frontier and is masked.  This is
    the library-row materialization — the Pallas decode kernels read the
    same pages in place without ever building the strip.
    """
    tbl = jnp.minimum(block_tables, pages.shape[0] - 1)
    strip = pages[tbl]                     # [B, max_pages, ps, Hkv, D]
    b, maxp, ps = strip.shape[:3]
    d = head_dim or strip.shape[-1]
    return strip.reshape(b, maxp * ps, -1, d).transpose(0, 2, 1, 3)


def gather_paged_scales(scale_pages, block_tables, num_kv_heads: int):
    """[P,rows,lanes] scales + [B,max_pages] -> a [B,Hkv,S,1] strip
    (the :func:`gather_paged_kv` of an int8 cache's scale pool)."""
    tbl = jnp.minimum(block_tables, scale_pages.shape[0] - 1)
    strip = scale_pages[tbl]               # [B, max_pages, rows, lanes]
    b = strip.shape[0]
    return strip.reshape(b, -1, num_kv_heads).transpose(0, 2, 1)[..., None]


def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           k_scale=None, v_scale=None,
                           scale: Optional[float] = None,
                           ctx: Optional[ShardCtx] = None,
                           interpret: Optional[bool] = None):
    """Single-token attention against a paged cache.

    q: [B,H,1,D]; pages: [P,page_size,Hkv,D] (or ``[P,page_size,Hkv·D]``);
    block_tables: [B,max_pages]; pos: [B].  Float pools on a TPU backend
    are read in place, live pages only, by the Pallas kernel
    (``kernels/paged_decode.py``; ``interpret`` as the policy sets it).
    Elsewhere this is the jnp reference, numerically identical to
    :func:`decode_attention` over the gathered strip — the masked-softmax
    math never sees page boundaries.
    Int8 pages come with their ``[P,rows,lanes]`` scale pools: the
    strips are gathered first and dequantized after, so a pool is never
    dequantized whole.
    """
    from repro.kernels import ops as kernel_ops
    if (k_scale is None and jnp.issubdtype(k_pages.dtype, jnp.floating)
            and kernel_ops._backend_probe() == "tpu"):
        return paged_decode.paged_decode_attention(
            q, k_pages, v_pages, block_tables, pos,
            scale=q.shape[-1] ** -0.5 if scale is None else scale,
            interpret=bool(interpret))
    k_cache = gather_paged_kv(k_pages, block_tables, q.shape[-1])
    v_cache = gather_paged_kv(v_pages, block_tables, q.shape[-1])
    if k_scale is not None:
        hkv = k_pages.shape[2]
        k_cache = dequantize_kv(
            k_cache, gather_paged_scales(k_scale, block_tables, hkv),
            q.dtype)
        v_cache = dequantize_kv(
            v_cache, gather_paged_scales(v_scale, block_tables, hkv),
            q.dtype)
    return decode_attention(q, k_cache, v_cache, pos, scale=scale, ctx=ctx)


# ---------------------------------------------------------------------------
# int8 KV cache (beyond-paper serving optimization; ParallelConfig flag)
# ---------------------------------------------------------------------------


def quantize_kv(x):
    """[B,Hkv,S,D] -> (int8 values, f32 scales [B,Hkv,S,1]).

    Per-(token, head) symmetric scaling: attention quality is far more
    sensitive to per-token dynamic range than per-tensor (K norms drift
    with position under RoPE)."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(m / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def update_cache_int8(cache_q, cache_scale, new, pos):
    """Quantized one-hot cache write: (int8 cache, scales, new bf16 slot)."""
    b, hkv, s, d = cache_q.shape
    q_new, s_new = quantize_kv(new)
    onehot = (jnp.arange(s)[None] == pos[:, None])          # [B,S]
    cache_q = jnp.where(onehot[:, None, :, None], q_new, cache_q)
    cache_scale = jnp.where(onehot[:, None, :, None], s_new, cache_scale)
    return cache_q, cache_scale


def update_paged_cache_int8(pages, scale_pages, new, block_tables, pos, *,
                            token_major: bool = True):
    """Quantized paged write (ISSUE 7): the int8 composition of
    :func:`update_paged_cache`.

    ``pages``: int8 ``[P,page_size,Hkv,D]`` with f32 ``scale_pages``
    ``[P,rows,lanes]`` (:func:`scale_page_shape`), or head-major
    ``[P,Hkv,page_size,D]`` with ``[P,Hkv,page_size,1]`` scales under
    ``token_major=False`` — per-token scales in pools of the *same*
    pages, so both writes resolve through the same table entry and the
    same sentinel/drop semantics (the value row and its scale can never
    land on different pages).  ``new`` arrives bf16/f32 and is quantized
    per-(token, head) here, at write time."""
    q_new, s_new = quantize_kv(new)
    pages = update_paged_cache(pages, q_new, block_tables, pos,
                               token_major=token_major)
    if not token_major:
        return pages, update_paged_cache(scale_pages, s_new, block_tables,
                                         pos, token_major=False)
    hkv, lanes = s_new.shape[1], scale_pages.shape[2]
    page_of, offset = _table_rows(block_tables, pos, pages.shape[1])
    # a token's Hkv scales are one window of one row: written in place,
    # where a scatter of single scales has the pool staged and copied back
    first = offset * hkv
    at = jnp.stack([page_of, first // lanes, first % lanes], axis=1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 2))
    scale_pages = jax.lax.scatter(
        scale_pages, at, s_new[:, :, 0, 0], dims,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    return pages, scale_pages
