"""Batched serving engine with a host-sync-free decode tick.

The decode state is a fixed [B, ...] cache pytree; requests claim a slot,
prefill writes that slot's cache entries, and every engine tick advances
ALL active slots by one token — the standard fixed-shape continuous-
batching layout (vLLM-style slots; the cache seq dim is pre-sized to
``max_seq_len``).

With ``ServeConfig.page_size`` set, the cache strips become a **paged KV
cache**: a global pool of fixed-size pages plus device-resident per-slot
block tables (``models/transformer.py::init_paged_cache``).  Admission is
then by *page budget* — a request reserves exactly the pages its
``prompt + max_new_tokens`` frontier can reach, so short prompts stop
paying the ``max_seq_len`` capacity tax and the engine accepts work until
the pool is actually exhausted, not until slots are dense-full.  Reaping a
finished request releases its pages back to the pool (host-side refcounts
in :class:`PagePool`), and leading full prompt pages are **shared by
refcount** across requests with a common prefix — the frontier/tail page
is always freshly allocated, so the one page a slot writes during decode
is never aliased (copy-on-write without the copy).  Table rows of reaped
slots reset to the sentinel ``num_pages``: inside the tick their writes
drop and their gathers clamp onto masked data, which is what keeps the
tick ONE compiled program with zero host transfers under paging.

The tick is **one device program and zero host transfers**:
``last_tokens``, the slot-liveness mask, and the per-slot remaining-token
budget are device-resident, and the jitted tick fuses decode + greedy
argmax + EOS/length masking, donating the cache and state buffers so the
update happens in place.  Per-token results accumulate as device arrays in
a history buffer; :meth:`sync` drains them to the ``Request`` objects with
a single stacked transfer.  Host synchronization happens only at
*admission* boundaries (a new request needs a prefill and a slot decision)
— never inside the steady-state tick loop.  This is the serving-side
application of the paper's §VII.C lesson: round-trips off the fast path
compound directly into tail latency.

Per-slot prefill uses a single-sequence prefill jit and writes the result
into the batch cache at the slot index (dynamic_update_slice), so a new
request joins without recompiling or disturbing other slots.  A model with
recurrent state (``model.recurrent_state`` names its cache entries, e.g.
the Mamba layers' SSM and conv state) has that state written whole from
the prefill's final state into the slot, so a reused slot never starts
from its last occupant's state; its prompt pages are never shared, since
a page cannot resume a recurrence.  Admission is
batched: all admissible pending requests are prefilled, then their first
tokens cross to the host in one stacked transfer.

``serve_step`` (what the decode_32k / long_500k dry-run cells lower) is
exactly one engine tick: (params, tokens[B], cache) -> (logits, cache).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import pages_read
from repro.serve.spans import (ADMIT, ADMIT_FIRST_TOKENS, ADMIT_PREFILL,
                               ADMIT_REAP, ADMIT_SCATTER, ADMIT_STATE, STEP,
                               SYNC, SYNC_APPLY, SYNC_FETCH, span)

#: a prefill cache's KV strips, relaid out for token-major pages
_STRIPS = ("k", "v", "k_scale", "v_scale")

#: max ticks between harvest syncs once admissions have drained: bounds
#: how much masked decode work a fully-EOS'd batch can waste
_SYNC_STRIDE = 64


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int          # B — concurrent decode slots
    max_seq_len: int          # cache capacity per slot
    max_new_tokens: int = 64
    eos_id: int = 1
    greedy: bool = True
    # ---- paged KV cache (None = dense per-slot strips) ----
    page_size: Optional[int] = None    # tokens per KV page
    num_pages: Optional[int] = None    # pool size; None = dense-equivalent
    prefix_sharing: bool = True        # refcount-share full prompt pages
    # pool sized by a DEVICE-BYTE budget instead of a page count (used
    # when num_pages is None): capacity reflects the page footprint, so
    # an int8 KV cache fits ~4·hd/(hd+4)× more pages in the same bytes —
    # the quantization win expressed as admission capacity, not just
    # bandwidth (BatchedEngine.page_footprint_bytes)
    kv_pool_bytes: Optional[int] = None

    @property
    def paged(self) -> bool:
        return self.page_size is not None

    @property
    def max_pages_per_slot(self) -> int:
        assert self.page_size is not None
        return -(-self.max_seq_len // self.page_size)


class PagePool:
    """Host-side allocator for the global KV page pool.

    Pure bookkeeping — the pages themselves live on device inside the
    engine's cache pytree; this class only decides which page ids a
    request holds.  Every held page is refcounted: fresh pages start at
    1, prefix-shared pages gain a reference per sharer, and a page
    returns to the free list **only when its refcount reaches 0** (the
    invariant the conformance suite pins).

    Prefix sharing indexes *full* prompt pages by a chain hash (each
    page's hash folds in its predecessor's, so a hit guarantees the whole
    leading path matches, not just one page).  Only pages below a
    request's reservation tail are ever published or matched — the
    frontier page a slot writes during decode is always freshly
    allocated, so sharing never aliases a written page.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        # pop() takes from the end: keep ids ascending for determinism
        self._free = list(range(num_pages - 1, -1, -1))
        self.refcount: dict = {}
        self._prefix: dict = {}       # chain hash -> page id
        self._hash_of: dict = {}      # page id -> chain hash (cleanup)
        self.shared_hits = 0          # pages NOT allocated thanks to sharing

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def occupied_pages(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, have {len(self._free)}")
        ids = [self._free.pop() for _ in range(n)]
        for p in ids:
            self.refcount[p] = 1
        return ids

    def retain(self, page_id: int) -> None:
        assert self.refcount.get(page_id, 0) > 0, page_id
        self.refcount[page_id] += 1

    def release(self, page_id: int) -> None:
        rc = self.refcount[page_id] - 1
        if rc > 0:
            self.refcount[page_id] = rc
            return
        # refcount 0: ONLY now does the page return to the free list
        del self.refcount[page_id]
        h = self._hash_of.pop(page_id, None)
        if h is not None:
            self._prefix.pop(h, None)
        self._free.append(page_id)

    def lookup_prefix(self, chain_hash) -> Optional[int]:
        return self._prefix.get(chain_hash)

    def publish_prefix(self, chain_hash, page_id: int) -> None:
        if chain_hash not in self._prefix and page_id not in self._hash_of:
            self._prefix[chain_hash] = page_id
            self._hash_of[page_id] = chain_hash

    @staticmethod
    def prefix_hashes(prompt: List[int], page_size: int) -> List:
        """One chain hash per FULL page of prompt tokens."""
        out, h = [], hash(("uisa-kv-page-chain",))
        for i in range(len(prompt) // page_size):
            h = hash((h, tuple(prompt[i * page_size:(i + 1) * page_size])))
            out.append(h)
        return out


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 64
    # filled by the engine
    slot: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # admission verdict: the request's page reservation can never fit the
    # pool (reserve > num_pages), so it is marked done without a slot
    # instead of livelocking the run() loop (ISSUE 9)
    rejected: bool = False


class BatchedEngine:
    def __init__(self, model, params, cfg: ServeConfig, policy=None):
        # ``policy`` (an ExecutionPolicy) overrides the model's resolved
        # lowering policy for this engine's jitted prefill/tick programs —
        # resolved once here, at trace-ownership time, so the engine's
        # compiled programs and the policy can never disagree.
        if policy is not None:
            model = model.with_policy(policy)
        self.model = model
        self.policy = getattr(model, "policy", None)
        # The layout the model's policy planned (models/config.ParamLayout).
        # The decode tick's q/k/v and ln2→[wi|wg] fusions activate only
        # when ``params`` actually carries the concatenated tensors —
        # block_decode inspects the pytree, so serving legacy params under
        # a fusing policy degrades gracefully to the PR 4 tick instead of
        # paying a per-token weight-concat tax.
        self.param_layout = getattr(model, "param_layout", None)
        self.params = params
        self.cfg = cfg
        # cache entries of per-slot recurrent state (none for a pure
        # attention model): written whole at admission, never shared
        self._recurrent = tuple(getattr(model, "recurrent_state", ()))
        self._prefix_sharing = cfg.prefix_sharing and not self._recurrent
        b = cfg.batch_slots
        self._paged = cfg.paged
        if self._paged:
            self._max_pages = cfg.max_pages_per_slot
            # dense-equivalent pool by default; cfg.num_pages < B·maxp is
            # the page-budget admission regime (capacity by pages), and
            # cfg.kv_pool_bytes sizes the pool by device bytes — where an
            # int8 cache's smaller page footprint becomes extra capacity
            if cfg.num_pages is not None:
                self.num_pages = cfg.num_pages
            elif cfg.kv_pool_bytes is not None:
                self.num_pages = max(
                    cfg.kv_pool_bytes // self.page_footprint_bytes(), 1)
            else:
                self.num_pages = b * self._max_pages
            self.pool: Optional[PagePool] = PagePool(self.num_pages,
                                                     cfg.page_size)
            self._slot_pages: List[List[int]] = [[] for _ in range(b)]
            self.cache = model.init_paged_cache(
                b, self.num_pages, cfg.page_size, self._max_pages)
        else:
            self.pool = None
            self.cache = model.init_cache(b, cfg.max_seq_len)
        # per-tick device-resident stats vectors (paged mode), drained by
        # sync() into tick_stats rows alongside the token history
        self._stats_history: List[jax.Array] = []
        self.tick_stats: List[dict] = []
        self.slots: List[Optional[Request]] = [None] * b
        # ---- device-resident tick state (never read per tick) ----
        self.last_tokens = jnp.zeros((b,), jnp.int32)
        self.live = jnp.zeros((b,), jnp.bool_)
        self.remaining = jnp.zeros((b,), jnp.int32)
        self._history: List[jax.Array] = []   # [B] token vecs since sync
        self.tick_count = 0
        self.trace_count = 0                  # tick compilations (regression)
        self._prefill_one = jax.jit(self._prefill_one_impl)
        # Donate liveness/budget/cache so the update is in place on
        # backends that support donation (no-op warning on CPU).  The
        # token vector is NOT donated: each tick's output token array is
        # retained in self._history until sync(), and becomes the next
        # tick's input — donating it would delete a retained buffer.
        donate = (2, 3, 4) if jax.default_backend() in ("tpu", "gpu") \
            else ()
        self._tick = jax.jit(self._tick_impl, donate_argnums=donate)
        # admission's page write, one program per prompt length, with the
        # pools donated: written in place, where an eager scatter makes a
        # whole new pool (a pool-sized allocation every admission)
        self._write_pages = jax.jit(self._write_pages_impl,
                                    donate_argnums=(0,) if donate else ())
        # admission's write of a slot's recurrent state, donated likewise
        self._write_state = jax.jit(self._write_state_impl,
                                    donate_argnums=(0,) if donate else ())

    def page_footprint_bytes(self) -> int:
        """Device bytes one KV page costs across the layer stack: K + V
        pool blocks, plus the per-(token,head) f32 scale blocks when the
        cache is int8.  A token-position then costs ``hd + 4`` bytes per
        head per direction instead of ``4*hd`` — the 4·hd/(hd+4)
        capacity multiplier a fixed ``kv_pool_bytes`` budget realizes."""
        mcfg = self.model.cfg
        hkv, hd = mcfg.num_kv_heads, mcfg.resolved_head_dim
        ps = self.cfg.page_size
        if getattr(self.model.par, "kv_cache_int8", False):
            per_layer = 2 * hkv * ps * (hd + 4)
        else:
            per_layer = 2 * hkv * ps * hd * np.dtype(mcfg.dtype).itemsize
        return mcfg.attention_layers * per_layer

    # ---- slot management ----

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None or r.done:
                return i
        return None

    def _prefill_one_impl(self, params, tokens):
        """Single-sequence prefill -> (last_logits, cache_for_batch1).

        For token-major pages the strips leave the prefill's program in
        page order, ``[L,1,plen,Hkv,x]``: relaid out in the page write
        instead, they cost that program about a second more to compile,
        for every prompt length."""
        logits, cache1 = self.model.prefill(params, {"tokens": tokens})
        if self._paged and self.model.token_major_pages:
            cache1 = {name: a.swapaxes(2, 3) if name in _STRIPS else a
                      for name, a in cache1.items()}
        return logits, cache1

    def add_request(self, req: Request) -> bool:
        """Claim a slot and prefill it.  False if engine is full."""
        return self.admit([req]) == 1

    def admit(self, reqs: List[Request]) -> int:
        """Batched admission: prefill as many of ``reqs`` (in order) as
        there are free slots — and, under paging, free *pages* — then
        fetch all first tokens in ONE host transfer.  Returns how many
        requests were admitted."""
        with span(ADMIT):
            return self._admit(reqs)

    def _admit(self, reqs: List[Request]) -> int:
        with span(ADMIT_REAP):
            self.sync()                # make slot liveness current
            if self._paged:
                self._reap_done_pages()  # page budget current first
        staged = []                    # (req, slot, first_token_device)
        consumed = 0                   # prefix of reqs taken (staged+rejected)
        for req in reqs:
            if self._paged and self._page_reserve(req) > self.num_pages:
                # the reservation exceeds the pool's *total* — no amount
                # of draining ever admits this request; reject it here so
                # run() never spins on it (the ISSUE 9 livelock)
                req.rejected = True
                req.done = True
                consumed += 1
                continue
            slot = self._free_slot()
            if slot is None:
                break
            if self._paged:
                plan = self._plan_pages(req)
                if plan is None:
                    break              # pool exhausted: stop admitting
            # reap the finished occupant (exactly the slot we claim)
            req.slot = slot
            self.slots[slot] = req
            with span(ADMIT_PREFILL, rid=req.rid):
                toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
                logits, cache1 = self._prefill_one(self.params, toks)
                first = jnp.argmax(logits[0]).astype(jnp.int32)
            if self._paged:
                page_ids, n_shared = plan
                with span(ADMIT_SCATTER, rid=req.rid,
                          fresh_pages=len(page_ids) - n_shared,
                          shared_pages=n_shared):
                    self._write_slot_paged(slot, cache1, len(req.prompt),
                                           page_ids, n_shared)
                if self._recurrent:
                    with span(ADMIT_STATE, rid=req.rid):
                        self.cache.update(self._write_state(
                            {n: self.cache[n] for n in self._recurrent},
                            {n: cache1[n] for n in self._recurrent},
                            jnp.int32(slot)))
            else:
                with span(ADMIT_SCATTER, rid=req.rid):
                    self._write_slot(slot, cache1, len(req.prompt))
            staged.append((req, slot, first))
            consumed += 1
        if not staged:
            return consumed
        with span(ADMIT_FIRST_TOKENS):
            idx = jnp.asarray([s for _, s, _ in staged], jnp.int32)
            firsts_dev = jnp.stack([t for _, _, t in staged])
            budgets = jnp.asarray(
                [max(r.max_new_tokens - 1, 0) for r, _, _ in staged],
                jnp.int32)
            firsts = np.asarray(firsts_dev)      # the one admission sync
            alive = []
            for (req, _, _), tok in zip(staged, firsts):
                tok = int(tok)
                req.generated.append(tok)
                req.done = (tok == self.cfg.eos_id
                            or len(req.generated) >= req.max_new_tokens)
                alive.append(not req.done)
            self.last_tokens = self.last_tokens.at[idx].set(firsts_dev)
            self.live = self.live.at[idx].set(jnp.asarray(alive))
            self.remaining = self.remaining.at[idx].set(budgets)
        return consumed

    def _write_slot(self, slot: int, cache1, prompt_len: int):
        """Copy a batch-1 prefill cache into batch slot ``slot``."""
        def write(full, one):
            # leading layout: either [layers, B, ...] or [B(=slots), ...]
            if one.ndim >= 2 and full.shape[0] == one.shape[0] \
                    and full.ndim == one.ndim \
                    and full.shape[1] == len(self.slots):
                # [layers, B, ...]: pad seq dims up to capacity
                pad = [(0, 0)] * one.ndim
                for ax in range(2, one.ndim):
                    pad[ax] = (0, full.shape[ax] - one.shape[ax])
                one_p = jnp.pad(one, pad)
                idx = (0, slot) + (0,) * (one.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    full, one_p.astype(full.dtype)[:, :1], idx)
            # [B, ...]
            pad = [(0, 0)] * one.ndim
            for ax in range(1, one.ndim):
                pad[ax] = (0, full.shape[ax] - one.shape[ax])
            one_p = jnp.pad(one, pad)
            idx = (slot,) + (0,) * (one.ndim - 1)
            return jax.lax.dynamic_update_slice(
                full, one_p.astype(full.dtype)[:1], idx)

        self.cache = jax.tree.map(write, self.cache, cache1)

    # ---- paged slot management ----

    def _reap_done_pages(self) -> None:
        """Release every finished slot's pages and reset its table row to
        the sentinel.  Safe while the slot's ``pos`` keeps advancing in
        the tick: sentinel entries drop writes, so a page handed to the
        next request can never be touched by its previous owner."""
        for slot, req in enumerate(self.slots):
            if req is None or not req.done or not self._slot_pages[slot]:
                continue
            for p in self._slot_pages[slot]:
                self.pool.release(p)
            self._slot_pages[slot] = []
            self.cache["block_tables"] = \
                self.cache["block_tables"].at[slot].set(self.num_pages)

    def _page_reserve(self, req: Request) -> int:
        """Pages ``req``'s ``prompt + max_new_tokens - 1`` frontier can
        ever reach (the :meth:`_plan_pages` reservation size) — admission
        rejects outright when this exceeds the pool's total."""
        ps = self.cfg.page_size
        total = min(len(req.prompt) + max(req.max_new_tokens, 1) - 1,
                    self.cfg.max_seq_len)
        total = max(total, len(req.prompt))
        return -(-total // ps)

    def _plan_pages(self, req: Request):
        """Reserve the pages ``req`` can ever reach, sharing leading full
        prompt pages by refcount.  Returns ``(page_ids, n_shared)`` or
        None when the pool cannot cover the reservation (nothing is
        mutated on failure).

        The reservation covers ``prompt + max_new_tokens - 1`` token
        positions (the final sampled token is never written back), capped
        at ``max_seq_len`` — so the tick allocates nothing and admission
        is the only allocation boundary.  Sharing is capped at
        ``reserve - 1`` pages: the tail page is always exclusively owned,
        which is what makes decode writes alias-free by construction."""
        ps = self.cfg.page_size
        reserve = self._page_reserve(req)
        shared: List[int] = []
        hashes = (PagePool.prefix_hashes(req.prompt, ps)[:reserve - 1]
                  if self._prefix_sharing else [])
        for h in hashes:
            pid = self.pool.lookup_prefix(h)
            if pid is None:
                break
            shared.append(pid)
        if reserve - len(shared) > self.pool.free_pages:
            return None
        for pid in shared:
            self.pool.retain(pid)
        self.pool.shared_hits += len(shared)
        page_ids = shared + self.pool.alloc(reserve - len(shared))
        for h, pid in zip(hashes, page_ids):
            self.pool.publish_prefix(h, pid)
        return page_ids, len(shared)

    def _write_slot_paged(self, slot: int, cache1, prompt_len: int,
                          page_ids: List[int], n_shared: int) -> None:
        """Scatter a batch-1 prefill cache into the slot's reserved pages.

        Only the *fresh* prompt pages are written — shared prefix pages
        already hold identical bytes and are never rewritten (the
        refcount invariant backs the aliasing argument, this backs the
        data one).  Reserved-but-unreached generation pages keep stale
        pool contents; every read of them sits past the ``pos`` mask."""
        ps = self.cfg.page_size
        self._slot_pages[slot] = page_ids
        row = np.full((self._max_pages,), self.num_pages, np.int32)
        row[:len(page_ids)] = page_ids
        tables = self.cache["block_tables"].at[slot].set(jnp.asarray(row))
        pos = self.cache["pos"].at[slot].set(prompt_len)
        new_cache = dict(self.cache, block_tables=tables, pos=pos)
        n_prompt_pages = -(-prompt_len // ps)
        if n_shared < n_prompt_pages:
            # shared pages already hold these bytes: their ids go to the
            # sentinel, whose writes drop
            ids = np.asarray(page_ids[:n_prompt_pages], np.int32)
            ids[:n_shared] = self.num_pages
            names = [n for n in ("k_pages", "v_pages", "k_scale_pages",
                                 "v_scale_pages") if n in new_cache]
            strips = {"k_pages": "k", "v_pages": "v",
                      "k_scale_pages": "k_scale", "v_scale_pages": "v_scale"}
            new_cache.update(self._write_pages(
                {n: new_cache[n] for n in names},
                {n: cache1[strips[n]] for n in names}, jnp.asarray(ids)))
        self.cache = new_cache

    def _write_pages_impl(self, pools, strips, ids):
        """Write a batch-1 prefill's strips page by page into the pools at
        ``ids``, one id per prompt page; an id of ``num_pages`` drops its
        page.  Strips and pools are in the model's page layout: token-
        major strips ``[L,1,plen,Hkv,x]`` into ``[L,P,page_size,Hkv,hd]``
        pools (scales ``[L,P,rows,lanes]``), or head-major strips
        ``[L,1,Hkv,plen,x]`` into ``[L,P,Hkv,page_size,x]`` pools.  Int8
        caches' scale strips go through the same ids into the scale
        pools."""
        ps = self.cfg.page_size
        n = ids.shape[0]
        token_major = self.model.token_major_pages
        out = {}
        for name, pool in pools.items():
            strip = strips[name][:, 0]
            seq = 1 if token_major else 2
            pad = [(0, 0)] * strip.ndim
            pad[seq] = (0, n * ps - strip.shape[seq])
            strip = jnp.pad(strip, pad)
            if token_major:
                pages = strip.reshape((strip.shape[0], n) + pool.shape[2:])
            else:
                nl, hkv, _, x = strip.shape
                pages = strip.reshape(nl, hkv, n, ps, x).transpose(
                    0, 2, 1, 3, 4)
            out[name] = pool.at[:, ids].set(pages.astype(pool.dtype),
                                            mode="drop")
        return out

    def _write_state_impl(self, states, new, slot):
        """Write a batch-1 prefill's final recurrent state (``[L,1,...]``
        each) over slot ``slot`` of the ``[L,B,...]`` state arrays."""
        return {n: jax.lax.dynamic_update_slice_in_dim(
            a, new[n].astype(a.dtype), slot, axis=1)
            for n, a in states.items()}

    # ---- ticking ----

    def _tick_impl(self, params, tokens, live, remaining, cache):
        """Fused decode tick: decode + argmax + EOS/length masking.

        One compiled program; every input/output stays on device.  Dead
        slots keep their token frozen (the cache still advances, into
        masked positions — the fixed-shape batching contract)."""
        self.trace_count += 1            # python side effect: traces only
        if self._paged:
            read = pages_read(cache["block_tables"], cache["pos"],
                              self.cfg.page_size, self.num_pages)
        logits, cache = self.model.decode_step(params, tokens, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(live, nxt, tokens)
        remaining = jnp.where(live, remaining - 1, remaining)
        live = live & (nxt != self.cfg.eos_id) & (remaining > 0)
        if not self._paged:
            return nxt, live, remaining, cache
        # per-tick observability, computed inside the one program: live
        # slot count, pages actually reached by live frontiers, the live
        # slots' positions summed (each slot's KV length, its new token
        # included), and the pages one layer's attention reads across
        # slots (live or finished but not yet reaped).  A tiny device
        # vector appended to history — harvested by sync(), so the tick
        # stays transfer-free.
        # ceil, not floor+1: a frontier sitting exactly on a page boundary
        # (pos == k·ps) has written k pages, not k+1 (ISSUE 9 off-by-one)
        frontier = jnp.where(live, -(-cache["pos"] // self.cfg.page_size),
                             0)
        stats = jnp.stack([jnp.sum(live.astype(jnp.int32)),
                           jnp.sum(frontier).astype(jnp.int32),
                           jnp.sum(jnp.where(live, cache["pos"], 0)
                                   ).astype(jnp.int32),
                           jnp.sum(read)])
        return nxt, live, remaining, cache, stats

    def step(self) -> None:
        """One decode tick for all slots — zero host transfers.

        Emitted tokens land in the device-side history; call :meth:`sync`
        (or :meth:`run`, which does) to drain them into the requests."""
        with span(STEP):
            out = self._tick(self.params, self.last_tokens, self.live,
                             self.remaining, self.cache)
        if self._paged:
            nxt, self.live, self.remaining, self.cache, stats = out
            self._stats_history.append(stats)
        else:
            nxt, self.live, self.remaining, self.cache = out
        self.last_tokens = nxt
        self._history.append(nxt)
        self.tick_count += 1

    def tick_hlo_text(self) -> str:
        """The optimized HLO text of the tick program as compiled for the
        engine's current arguments (the compile cache serves it).  Its
        instruction names are the operation names of a device trace, and
        each instruction's ``metadata={op_name=...}`` carries the model's
        ``jax.named_scope`` path."""
        return self._tick.lower(self.params, self.last_tokens, self.live,
                                self.remaining, self.cache
                                ).compile().as_text()

    def _pending_harvest(self) -> dict:
        """The device half of :meth:`sync`: stack the token history (and
        the paged per-tick stats vectors) into device arrays and clear
        the buffers.  Nothing is transferred here — the caller fetches
        the returned dict (this engine's own :meth:`sync`, or a
        :class:`~repro.serve.router.CellRouter` stacking *every* cell's
        pending harvest into one ``device_get``)."""
        pending: dict = {}
        if self._history:
            pending["hist"] = jnp.stack(self._history)   # [T, B]
            self._history = []
        if self._stats_history:
            pending["stats"] = jnp.stack(self._stats_history)   # [T, 4]
            pending["stats_base"] = self.tick_count \
                - len(self._stats_history)
            self._stats_history = []
        return pending

    def _apply_harvest(self, harvest: dict) -> None:
        """The host half of :meth:`sync`: replay a fetched harvest into
        the Request objects and the tick_stats rows."""
        with span(SYNC_APPLY):
            hist = harvest.get("hist")
            if hist is not None:
                for t in range(hist.shape[0]):
                    for slot, req in enumerate(self.slots):
                        if req is None or req.done:
                            continue
                        tok = int(hist[t, slot])
                        req.generated.append(tok)
                        if tok == self.cfg.eos_id or \
                                len(req.generated) >= req.max_new_tokens:
                            req.done = True
            rows = harvest.get("stats")
            if rows is not None:
                base = int(harvest["stats_base"])
                for i in range(rows.shape[0]):
                    # device columns are per-tick; the pool columns are
                    # the host allocator's view at harvest time
                    # (admission-grain)
                    self.tick_stats.append({
                        "tick": base + i,
                        "live_slots": int(rows[i, 0]),
                        "frontier_pages": int(rows[i, 1]),
                        "live_positions": int(rows[i, 2]),
                        "kv_pages_read": int(rows[i, 3]),
                        "pool_occupied_pages": self.pool.occupied_pages,
                        "pool_utilization":
                            self.pool.occupied_pages / max(self.num_pages, 1),
                        "shared_prefix_hits": self.pool.shared_hits,
                    })

    def sync(self) -> None:
        """Drain the device-side token history into the Request objects
        with a single stacked device->host transfer (the paged per-tick
        stats vectors ride in the same fetch)."""
        with span(SYNC):
            pending = self._pending_harvest()
            if pending:
                with span(SYNC_FETCH):
                    fetched = jax.device_get(pending)
                self._apply_harvest(fetched)

    def run(self, requests: List[Request],
            max_ticks: int = 10_000) -> List[Request]:
        """Continuous batching: admit whenever a slot frees, tick until
        all requests finish.  Host syncs happen only at admission/harvest
        boundaries; between them the tick loop is transfer-free."""
        pending = list(requests)
        admitted: List[Request] = []
        while self.tick_count < max_ticks:
            n = 0
            if pending:
                n = self.admit(pending)       # syncs + reaps done slots
                admitted.extend(pending[:n])
                del pending[:n]
            else:
                self.sync()
            active = [r for r in self.slots if r is not None and not r.done]
            if not pending and not active:
                break
            if pending and not active and n == 0:
                # nothing running and nothing admissible: ticking cannot
                # free capacity, so spinning to max_ticks would livelock.
                # (Rejection above consumes never-admittable requests;
                # this guards the residual stuck-admission case.)
                break
            if pending:
                # full house: tick once, then re-check for freed slots
                self.step()
            else:
                # no admissions left: run a transfer-free stretch, capped
                # so EOS-finished batches don't burn unbounded masked
                # ticks before the next sync notices everyone is done
                bound = max(r.max_new_tokens - len(r.generated)
                            for r in active)
                bound = min(bound, _SYNC_STRIDE,
                            max_ticks - self.tick_count)
                for _ in range(max(1, bound)):
                    self.step()
        self.sync()
        return admitted
