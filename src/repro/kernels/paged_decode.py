"""Single-token attention read in place from token-major page pools.

The paged decode tick keeps its K and V pools in HBM as token-major pages:
a page of one layer holds ``page_size`` tokens, each token's row carrying
every kv head (``[N, page_size, Hkv, hd]``, or ``[N, page_size, Hkv·hd]``
where a head of 64 would half fill a tile).  This kernel reads, for each
slot, only the pages its frontier reached, straight from the pools, and
never builds the ``[B, Hkv, S, hd]`` capacity strip that
:func:`repro.models.attention.gather_paged_kv` materializes.

- One grid step covers every slot.  The slots' live pages form one stream
  (slot by slot, page by page), and a ring of ``PAGES_IN_FLIGHT`` page
  buffers keeps that many K and V pages in flight across slot boundaries:
  the DMA for page ``t + PAGES_IN_FLIGHT - 1`` starts before page ``t`` is
  computed.  Step overhead grows with the slots, not with slots × pages.
- Slot ``b`` reads ``min(pos[b] // page_size + 1, max_pages)`` pages
  (:func:`repro.models.attention.pages_read`); a slot whose first table
  entry is the sentinel reads none and returns zeros.  Entries are
  clamped into the pool, as the gather clamps them.
- A page is taken as rows of the pool's minor dim, ``[page_size·Hkv, hd]``
  or ``[page_size, Hkv·hd]`` (a bitcast of the pool's tiles), and GQA
  never slices a head out of a row.  Each query head's q is laid over a
  row's lanes in its own kv head's block (zeros elsewhere), so one
  ``[H, lanes] x [rows, lanes]ᵀ`` product gives every head's score
  against every row, and a mask keeps a head's own kv head; the PV
  product's own blocks are the outputs.  One code path covers hd 128 and
  64; the Hkv-fold extra products stay under the ridge (about 32 FLOP a
  byte at 8 kv heads).
- The arithmetic of :func:`repro.models.attention.decode_attention`: bf16
  products accumulated in f32, an online softmax across pages with f32
  max and sum, the PV product accumulated in f32, one cast at the end.
  The probabilities enter the PV product in the value dtype, as a TPU's
  default-precision f32 product rounds them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: exp() of a masked score is 0, never NaN
#: the depth of the page ring: K and V pages in flight while one computes
PAGES_IN_FLIGHT = 8


def _kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
            sems, stream, starts, acc_ref, m_ref, l_ref, *, scale: float,
            page_size: int, num_kv_heads: int):
    n_slots, n_heads, head_dim = q_ref.shape
    num_pages, rows, lanes = k_hbm.shape
    max_pages = tables_ref.shape[1]
    depth = k_buf.shape[0]
    group = n_heads // num_kv_heads
    per_row = lanes // head_dim                 # kv heads a row holds
    row_groups = num_kv_heads // per_row        # rows a token takes

    # the stream of live pages, slot by slot: stream[starts[b] + j] is
    # slot b's page j, clamped into the pool as the gather clamps it
    def fill(b, t):
        n = jnp.where(tables_ref[b, 0] < num_pages,
                      jnp.minimum(pos_ref[b] // page_size + 1, max_pages), 0)

        def put(j, t):
            stream[t] = jnp.minimum(tables_ref[b, j], num_pages - 1)
            return t + 1
        t = jax.lax.fori_loop(0, n, put, t)
        starts[b + 1] = t
        return t
    starts[0] = 0
    total = jax.lax.fori_loop(0, n_slots, fill, 0)

    def copies(t):
        buf, page = t % depth, stream[t]
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf],
                                      sems.at[1, buf]))

    def start(t):
        @pl.when(t < total)
        def _():
            for c in copies(t):
                c.start()

    for t in range(depth - 1):
        start(t)

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(jnp.int32, shape, dim)
    kv_head = iota((n_heads, 1), 0) // group
    # a page is ``rows`` rows of ``lanes``: row r holds token
    # r // row_groups, lane c its kv head (r % row_groups)·per_row
    # + c // head_dim.  A score counts where that kv head is the query
    # head's own and the token is at or before the frontier.
    own_row = iota((n_heads, rows), 1) % row_groups == kv_head // per_row
    token = iota((n_heads, rows), 1) // row_groups
    if per_row > 1:
        # q spread over a row's lanes: tile[d, c] = 1 where lane c holds
        # head dim d, and own_lane keeps the query head's own kv head
        tile = (iota((head_dim, lanes), 1) % head_dim
                == iota((head_dim, lanes), 0)).astype(k_buf.dtype)
        own_lane = iota((n_heads, lanes), 1) // head_dim \
            == kv_head % per_row

    def slot(b, carry):
        s0 = starts[b]
        p = pos_ref[b]
        q = q_ref[b].astype(k_buf.dtype)                        # [H, hd]
        if per_row > 1:
            q = jnp.dot(q, tile, preferred_element_type=jnp.float32)
            q = jnp.where(own_lane, q, 0.0).astype(k_buf.dtype)  # [H, C]
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def page(t, carry):
            for c in copies(t):
                c.wait()
            start(t + depth - 1)
            buf = t % depth
            s = jax.lax.dot_general(
                q, k_buf[buf], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [H, rows]
            live = own_row & (token + (t - s0) * page_size <= p)
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[...]
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            w = jnp.exp(s - m)
            corr = jnp.exp(m_prev - m)
            l_ref[...] = l_ref[...] * corr + jnp.sum(w, axis=1,
                                                     keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                w.astype(v_buf.dtype), v_buf[buf],
                preferred_element_type=jnp.float32)
            m_ref[...] = m
            return carry
        jax.lax.fori_loop(s0, starts[b + 1], page, 0)
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        if per_row > 1:
            # each head's own block, folded back to [H, hd]: one nonzero
            # term a column, so the product is exact
            o = jax.lax.dot_general(
                jnp.where(own_lane, o, 0.0).astype(k_buf.dtype), tile,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[b] = o.astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, n_slots, slot, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           scale: float, interpret: bool = False):
    """q [B,H,1,hd] against token-major pools ``[N,page_size,Hkv,hd]`` or
    ``[N,page_size,Hkv·hd]`` through ``block_tables`` [B,max_pages]
    (entries in ``[0, N]``, ``N`` the sentinel) at frontiers ``pos`` [B]
    -> [B,H,1,hd].  The pools stay in HBM; only the live pages move."""
    b, h, _, hd = q.shape
    n, ps = k_pages.shape[:2]
    hkv = k_pages.size // (n * ps * hd)
    # a page as rows of the pool's minor dim: [ps·Hkv, hd] or [ps, Hkv·hd],
    # either way the bytes of the pool's (8, 128) tiles in their order
    lanes = k_pages.shape[-1]
    k_pages, v_pages = (p.reshape(n, -1, lanes) for p in (k_pages, v_pages))
    if not interpret:
        # the pools stay where the tick keeps them: XLA would otherwise
        # stage a pool small enough into VMEM around the whole tick
        k_pages, v_pages = (pltpu.with_memory_space_constraint(p, pltpu.HBM)
                            for p in (k_pages, v_pages))
    maxp = block_tables.shape[1]
    depth = PAGES_IN_FLIGHT
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=ps,
                          num_kv_heads=hkv),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((depth,) + k_pages.shape[1:], k_pages.dtype),
                pltpu.VMEM((depth,) + v_pages.shape[1:], v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, depth)),
                pltpu.SMEM((b * maxp,), jnp.int32),
                pltpu.SMEM((b + 1,), jnp.int32),
                pltpu.VMEM((h, lanes), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="uisa_paged_decode_attention",
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(b, h, hd), k_pages, v_pages)
    return out.reshape(b, h, 1, hd)
