"""Serve-path equivalence: the batched continuous-batching engine must
produce token-for-token what sequential single-request decoding produces,
including across mid-stream admissions and slot reuse — plus regression
tests pinning the host-sync-free tick (one compiled program, zero host
transfers inside the tick loop)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model
from repro.models.config import ModelConfig, ParallelConfig
from repro.core import REGISTRY
from repro.serve import BatchedEngine, Request, ServeConfig

KEY = jax.random.PRNGKey(0)
CACHE_LEN = 32


def tiny_model():
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, dtype="float32")
    return build_model(cfg, ParallelConfig(remat="none")), cfg


def sequential_decode(model, params, prompt, max_new, eos):
    """Hand-rolled prefill + one-at-a-time greedy decode: the ground truth
    the batched engine must reproduce (engine semantics: the prefill
    token counts toward max_new; stop on EOS or length)."""
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = model.prefill(params, {"tokens": toks})
    pad = CACHE_LEN - cache["k"].shape[3]
    # k and v, and an int8 cache's scales, padded along the sequence
    cache = {name: a if name == "pos" else
             jnp.pad(a, ((0, 0),) * 3 + ((0, pad), (0, 0)))
             for name, a in cache.items()}
    out = [int(jnp.argmax(logits[0]))]
    while out[-1] != eos and len(out) < max_new:
        lg, cache = model.decode_step(
            params, jnp.asarray([out[-1]], jnp.int32), cache)
        out.append(int(jnp.argmax(lg[0])))
    return out


@pytest.fixture(scope="module")
def model_and_params():
    model, cfg = tiny_model()
    return model, model.init_params(KEY), cfg


def _prompts(cfg, n, rng_key=KEY):
    keys = jax.random.split(rng_key, n)
    return [[int(t) for t in jax.random.randint(
        k, (3 + i % 3,), 2, cfg.vocab_size)] for i, k in enumerate(keys)]


class TestBatchedSequentialEquivalence:
    def test_oversubscribed_matches_sequential(self, model_and_params):
        """5 requests on 2 slots: admissions happen mid-stream as slots
        free; every request must still match its solo decode."""
        model, params, cfg = model_and_params
        prompts = _prompts(cfg, 5)
        max_news = [4, 7, 5, 6, 4]
        want = [sequential_decode(model, params, p, m, eos=-1)
                for p, m in zip(prompts, max_news)]

        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        reqs = [Request(rid=i, prompt=p, max_new_tokens=m)
                for i, (p, m) in enumerate(zip(prompts, max_news))]
        done = eng.run(reqs)
        assert len(done) == 5
        for r in done:
            assert r.generated == want[r.rid], r.rid

    def test_eos_termination_matches_sequential(self, model_and_params):
        """Pick a token the greedy path actually emits as EOS: batched
        early termination must match sequential early termination."""
        model, params, cfg = model_and_params
        prompts = _prompts(cfg, 3)
        probe = sequential_decode(model, params, prompts[0], 8, eos=-1)
        eos = probe[2]          # guaranteed to appear mid-stream
        want = [sequential_decode(model, params, p, 8, eos=eos)
                for p in prompts]
        assert len(want[0]) < 8  # the EOS path is actually exercised

        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=eos))
        done = eng.run([Request(rid=i, prompt=p, max_new_tokens=8)
                        for i, p in enumerate(prompts)])
        for r in done:
            assert r.generated == want[r.rid], r.rid

    def test_explicit_mid_stream_admission(self, model_and_params):
        """Admit a request onto a slot that another request just vacated,
        with ticks in between: the newcomer is unaffected by the slot's
        previous occupant."""
        model, params, cfg = model_and_params
        prompts = _prompts(cfg, 3)
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        r0 = Request(rid=0, prompt=prompts[0], max_new_tokens=3)
        r1 = Request(rid=1, prompt=prompts[1], max_new_tokens=8)
        assert eng.add_request(r0) and eng.add_request(r1)
        for _ in range(4):       # r0 finishes (3 tokens), r1 keeps going
            eng.step()
        r2 = Request(rid=2, prompt=prompts[2], max_new_tokens=5)
        assert eng.add_request(r2)      # reuses r0's slot
        assert r2.slot == r0.slot and r0.done
        for _ in range(8):
            eng.step()
        eng.sync()
        for req, m in ((r0, 3), (r1, 8), (r2, 5)):
            assert req.generated == sequential_decode(
                model, params, req.prompt, m, eos=-1), req.rid

    def test_slot_reaping_admits_into_reaped_slot(self, model_and_params):
        """Regression for the double-_free_slot bug: admission must claim
        exactly the slot it reaps, once per admission."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        prompts = _prompts(cfg, 4)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=3 + i)
                for i in range(4)]
        done = eng.run(reqs)
        assert len(done) == 4
        assert all(r.done and len(r.generated) == 3 + r.rid for r in done)
        # the two late requests took over the two early slots
        assert {reqs[2].slot, reqs[3].slot} == {reqs[0].slot, reqs[1].slot}


class TestFusionEquivalence:
    """ISSUE 4 satellite: PR 3's decode path was only ever tested with
    fusion off.  The fused decode tick (add_rmsnorm residual→ln2 in
    block_decode, rmsnorm_matmul final-norm→lm_head in _head) must emit
    token-for-token what the unfused engine emits."""

    def test_fused_decode_matches_unfused(self, model_and_params):
        model, params, cfg = model_and_params
        fused_model = build_model(cfg, ParallelConfig(
            remat="none", fuse_epilogues=True))
        assert fused_model.policy.fuses() and not model.policy.fuses()
        prompts = _prompts(cfg, 4)
        max_news = [4, 7, 5, 6]

        def run(m):
            eng = BatchedEngine(m, params, ServeConfig(
                batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
            return eng.run([Request(rid=i, prompt=p, max_new_tokens=mx)
                            for i, (p, mx) in enumerate(zip(prompts,
                                                            max_news))])

        want = {r.rid: r.generated for r in run(model)}
        got = run(fused_model)
        assert len(got) == 4
        for r in got:
            assert r.generated == want[r.rid], r.rid

    def test_all_three_fusions_on_concat_layout(self, model_and_params):
        """ISSUE 5: with the persisted [wq|wk|wv]/[wi|wg] layout and the
        Pallas decode attention epilogue, ALL THREE seq-path fusions
        (q/k/v prologue, flash->wo, ln2->swiglu) are live inside the
        decode tick — and the engine still emits token-for-token what the
        unfused legacy engine emits."""
        model, params, cfg = model_and_params
        full = build_model(cfg, ParallelConfig(
            remat="none", fuse_epilogues=True, use_pallas_attn=True))
        assert full.param_layout.attn_qkv and full.param_layout.mlp_swiglu
        # same seed, concatenated layout: identical weights, fused form
        concat_params = full.init_params(KEY)
        prompts = _prompts(cfg, 4)
        max_news = [4, 7, 5, 6]

        def run(m, p):
            eng = BatchedEngine(m, p, ServeConfig(
                batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
            done = eng.run([Request(rid=i, prompt=pr, max_new_tokens=mx)
                            for i, (pr, mx) in enumerate(zip(prompts,
                                                             max_news))])
            return done, eng

        want = {r.rid: r.generated for r in run(model, params)[0]}
        got, eng = run(full, concat_params)
        assert eng.param_layout.attn_qkv            # engine surfaces it
        assert eng.trace_count == 1                 # still ONE tick program
        assert len(got) == 4
        for r in got:
            assert r.generated == want[r.rid], r.rid

    def test_fused_tick_stays_one_compiled_program(self, model_and_params):
        """Fusion must not break the host-sync-free tick: still exactly
        one trace across admissions and slot reuse."""
        model, params, cfg = model_and_params
        fused_model = build_model(cfg, ParallelConfig(
            remat="none", fuse_epilogues=True))
        eng = BatchedEngine(fused_model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        prompts = _prompts(cfg, 5)
        eng.run([Request(rid=i, prompt=p, max_new_tokens=4 + i % 3)
                 for i, p in enumerate(prompts)])
        assert eng.tick_count > 4
        assert eng.trace_count == 1


class TestPagedEngine:
    """ISSUE 6 tentpole: the paged KV cache (page pool + per-slot block
    tables) must be invisible to correctness — identical tokens to the
    dense engine for identical request streams — while keeping the tick
    ONE compiled program with zero per-tick host transfers, admitting by
    page budget instead of slot-dense capacity, and freeing pages on
    reap."""

    PAGE = 8                      # CACHE_LEN=32 -> 4 pages per slot

    def _run(self, model, params, reqs, **cfg_kw):
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1, **cfg_kw))
        done = eng.run(reqs)
        return {r.rid: r.generated for r in done}, eng

    def _reqs(self, cfg, n=5, max_news=(4, 7, 5, 6, 4)):
        return [Request(rid=i, prompt=p, max_new_tokens=m)
                for i, (p, m) in enumerate(zip(_prompts(cfg, n),
                                               max_news))]

    def test_paged_matches_dense_tokens(self, model_and_params):
        """Oversubscribed (5 requests, 2 slots, mid-stream reaping):
        paged and dense engines emit identical token streams."""
        model, params, cfg = model_and_params
        want, _ = self._run(model, params, self._reqs(cfg))
        got, eng = self._run(model, params, self._reqs(cfg),
                             page_size=self.PAGE)
        assert len(got) == 5
        assert got == want
        assert eng.trace_count == 1          # still ONE tick program

    def test_paged_fused_pallas_matches_dense(self, model_and_params):
        """The paged decode shape of flash_attention_matmul (block-table
        gather + dead-block skip) inside the fully-fused Pallas tick:
        token-for-token against the unfused dense engine."""
        model, params, cfg = model_and_params
        full = build_model(cfg, ParallelConfig(
            remat="none", fuse_epilogues=True, use_pallas_attn=True))
        want, _ = self._run(model, params, self._reqs(cfg, 4,
                                                      (4, 7, 5, 6)))
        got, eng = self._run(full, full.init_params(KEY),
                             self._reqs(cfg, 4, (4, 7, 5, 6)),
                             page_size=self.PAGE)
        assert got == want
        assert eng.trace_count == 1

    def test_page_budget_admission_beats_dense_capacity(
            self, model_and_params):
        """ISSUE 6 satellite: short prompts must not pay the max_seq_len
        capacity tax.  A pool holding FEWER tokens than
        ``batch_slots × max_seq_len`` (dense-impossible) still admits
        every slot, because reservations follow actual request length."""
        model, params, cfg = model_and_params
        num_pages = 8                        # 64 tokens of pool capacity
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=4, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, num_pages=num_pages))
        assert num_pages * self.PAGE < 4 * CACHE_LEN   # < dense bytes
        prompts = _prompts(cfg, 4)
        reqs = [Request(rid=i, prompt=p[:3], max_new_tokens=4)
                for i, p in enumerate(prompts)]
        assert eng.admit(reqs) == 4          # all slots, tiny pool
        # capacity regression: the pool covers > batch_slots × avg_len
        # actual tokens, while a dense layout of the same byte budget
        # would hold only num_pages·page/max_len = 2 slots
        avg_len = sum(len(r.prompt) + r.max_new_tokens for r in reqs) / 4
        assert num_pages * self.PAGE > 4 * avg_len
        assert num_pages * self.PAGE // CACHE_LEN < 4
        done = eng.run([])
        for r in reqs:
            assert r.generated == sequential_decode(
                model, params, r.prompt, 4, eos=-1), r.rid

    def test_admission_stops_when_pool_exhausted(self, model_and_params):
        """Page budget is a real budget: with pages for only one
        reservation, the second request waits even though a slot is
        free — then admits once the first reaps and frees its pages."""
        model, params, cfg = model_and_params
        prompts = _prompts(cfg, 2)
        # each request reserves ceil((3..5 + 4 - 1)/8) = 1 page
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, num_pages=1))
        reqs = [Request(rid=i, prompt=p[:3], max_new_tokens=4)
                for i, p in enumerate(prompts)]
        assert eng.admit(reqs) == 1          # pool, not slots, is the gate
        assert eng.pool.free_pages == 0
        done = eng.run(reqs[1:])             # finishes r0, then admits r1
        assert reqs[0].done and reqs[1].done
        for r in reqs:
            assert r.generated == sequential_decode(
                model, params, r.prompt, 4, eos=-1), r.rid

    def test_prefix_sharing_refcounts_and_tokens(self, model_and_params):
        """Two requests with one common full prompt page share it by
        refcount; the tail/frontier page is never shared (copy-on-write
        never aliases), and output tokens match the non-sharing engine."""
        model, params, cfg = model_and_params
        prompt = _prompts(cfg, 1)[0] * 4     # >= 10 tokens: 1 full page
        assert len(prompt) > self.PAGE
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        r0 = Request(rid=0, prompt=list(prompt), max_new_tokens=5)
        r1 = Request(rid=1, prompt=list(prompt), max_new_tokens=5)
        assert eng.admit([r0, r1]) == 2
        head0, head1 = eng._slot_pages[0][0], eng._slot_pages[1][0]
        assert head0 == head1                        # shared prefix page
        assert eng.pool.refcount[head0] == 2
        assert (set(eng._slot_pages[0][1:])
                & set(eng._slot_pages[1][1:]) == set())   # tails disjoint
        assert eng.pool.shared_hits == 1
        eng.run([])
        assert r0.generated == r1.generated
        plain = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, prefix_sharing=False))
        solo = plain.run([Request(rid=0, prompt=list(prompt),
                                  max_new_tokens=5)])
        assert plain.pool.shared_hits == 0
        assert solo[0].generated == r0.generated

    def test_page_write_is_one_program_per_prompt_length(
            self, model_and_params):
        """Admission writes its fresh prompt pages with one compiled
        program per prompt length, however many pages it shares, and
        changes no other page."""
        model, params, cfg = model_and_params
        prompt = _prompts(cfg, 1)[0] * 4     # >= 10 tokens: 1 full page
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        assert eng.admit([Request(rid=0, prompt=list(prompt),
                                  max_new_tokens=5)]) == 1
        pools = {k: eng.cache[k] for k in ("k_pages", "v_pages")}
        r1 = Request(rid=1, prompt=list(prompt), max_new_tokens=5)
        assert eng.admit([r1]) == 1
        assert eng._slot_pages[1][0] == eng._slot_pages[0][0]
        fresh = eng._slot_pages[1][1:-(-len(prompt) // self.PAGE)]
        untouched = [p for p in range(eng.num_pages) if p not in fresh]
        for k, before in pools.items():
            after = eng.cache[k]
            assert bool(jnp.all(after[:, jnp.asarray(untouched)]
                                == before[:, jnp.asarray(untouched)]))
            assert not bool(jnp.all(after[:, jnp.asarray(fresh)]
                                    == before[:, jnp.asarray(fresh)]))
        assert eng._write_pages._cache_size() == 1
        eng.run([])
        short = Request(rid=2, prompt=list(prompt[:3]), max_new_tokens=3)
        eng.run([short])
        assert short.generated == sequential_decode(
            model, params, short.prompt, 3, eos=-1)
        assert eng._write_pages._cache_size() == 2

    def test_reap_frees_pages_and_slot_reuse_is_clean(
            self, model_and_params):
        """Pages release exactly at reap; a newcomer over a reaped slot
        reuses its pages without contamination from the previous
        occupant (sentinel table hygiene)."""
        model, params, cfg = model_and_params
        prompts = _prompts(cfg, 4)
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=3 + i)
                for i in range(4)]
        done = eng.run(reqs)
        assert len(done) == 4
        assert eng.pool.occupied_pages == sum(
            len(p) for p in eng._slot_pages)
        for r in done:
            assert r.generated == sequential_decode(
                model, params, r.prompt, 3 + r.rid, eos=-1), r.rid

    def test_paged_tick_loop_is_transfer_free(self, model_and_params):
        """Zero host transfers inside the paged tick loop — the block
        tables, page pools, and per-tick stats all stay on device."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        eng.add_request(Request(rid=0, prompt=[3, 5, 7],
                                max_new_tokens=50))
        eng.step()                       # compile outside the guard
        with jax.transfer_guard("disallow"):
            for _ in range(10):
                eng.step()
        eng.sync()
        assert len(eng.slots[0].generated) >= 11
        assert eng.trace_count == 1

    def test_tick_stats_harvested_in_sync(self, model_and_params):
        """ISSUE 6 satellite: per-tick stats ride the device history and
        drain in sync() — live slots, frontier pages, live positions,
        pages read, pool utilization, shared-prefix hits."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        eng.run(self._reqs(cfg))
        assert len(eng.tick_stats) == eng.tick_count
        first = eng.tick_stats[0]
        assert set(first) == {"tick", "live_slots", "frontier_pages",
                              "live_positions", "kv_pages_read",
                              "pool_occupied_pages", "pool_utilization",
                              "shared_prefix_hits"}
        assert first["live_slots"] == 2
        # both slots live: their KV lengths, the new token included
        assert first["live_positions"] == sum(
            len(r.prompt) + 1 for r in self._reqs(cfg)[:2])
        assert 0 < first["frontier_pages"] <= eng.num_pages
        assert 0.0 < first["pool_utilization"] <= 1.0

    def test_paged_kernel_tick_matches_gather_tick(self, model_and_params,
                                                   monkeypatch):
        """The tick as a TPU runs it: the backend probe answers "tpu", so
        the float token-major pools are read in place by the paged decode
        kernel (here in interpret mode).  Five requests on two slots, so
        slots are reaped and reused mid-run: the same greedy streams as
        the gather path, from one tick program."""
        model, params, cfg = model_and_params
        want, _ = self._run(model, params, self._reqs(cfg),
                            page_size=self.PAGE)
        from repro.kernels import ops, paged_decode
        calls = []
        kernel = paged_decode.paged_decode_attention

        def spy(*args, **kw):
            calls.append(kw["interpret"])
            return kernel(*args, **kw)
        monkeypatch.setattr(ops, "_backend_probe", lambda: "tpu")
        monkeypatch.setattr(paged_decode, "paged_decode_attention", spy)
        on_tpu = model.with_policy(dataclasses.replace(model.policy,
                                                       interpret=True))
        eng = BatchedEngine(on_tpu, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        reqs = self._reqs(cfg)
        got = {r.rid: r.generated for r in eng.run(reqs)}
        assert calls == [True]               # traced once, in the scan
        assert len(got) == 5 and got == want
        assert {r.slot for r in reqs} == {0, 1}      # slots reused
        assert eng.trace_count == 1

    def test_kv_pages_read_counts_from_pos_and_tables(self,
                                                      model_and_params):
        """``kv_pages_read`` is what the host computes from each tick's
        ``pos`` and tables: a slot with a real first entry reads
        ``min(pos // page + 1, max_pages)`` pages, a reaped one none.
        Finished slots left unreaped run past their tables to the cap,
        so the count reaches but never passes B·max_pages; reaped, they
        read nothing."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        want = []

        def tick():
            tbl = np.asarray(eng.cache["block_tables"])
            pos = np.asarray(eng.cache["pos"])
            want.append(sum(min(p // self.PAGE + 1, tbl.shape[1])
                            for t, p in zip(tbl, pos)
                            if t[0] < eng.num_pages))
            eng.step()
        pending = self._reqs(cfg)
        while pending:
            del pending[:eng.admit(pending)]
            tick()
        for _ in range(CACHE_LEN + 8):
            tick()
        assert eng.admit([]) == 0            # syncs, then reaps both
        tick()
        eng.sync()
        got = [r["kv_pages_read"] for r in eng.tick_stats]
        cap = 2 * eng._max_pages
        assert got == want
        assert all(0 <= g <= cap for g in got)
        assert got[-2:] == [cap, 0]

    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    def test_layer_offset_keeps_pages_apart(self, int8):
        """The pools ride the tick's layer scan flattened to [L·P, ...],
        each layer reaching its pages through the table offset by l·P.
        With a reaped slot whose pos keeps advancing, no layer writes a
        page that no request holds, and the live slot's tokens are the
        sequential ones."""
        _, cfg = tiny_model()
        cfg = dataclasses.replace(cfg, num_layers=3)
        model = build_model(cfg, ParallelConfig(remat="none",
                                                kv_cache_int8=int8))
        assert model.token_major_pages
        params = model.init_params(KEY)
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, num_pages=8))
        short, long_ = _prompts(cfg, 2)
        r0 = Request(rid=0, prompt=short[:3], max_new_tokens=2)
        r1 = Request(rid=1, prompt=long_, max_new_tokens=14)
        assert eng.admit([r0, r1]) == 2
        eng.step()
        assert eng.admit([]) == 0               # syncs, then reaps r0
        assert r0.done and eng._slot_pages[0] == []
        free = [p for p in range(eng.num_pages) if p not in eng.pool.refcount]
        assert 0 in free                          # r0's page, now free
        names = [n for n in eng.cache if n.endswith("pages")]
        before = {n: np.asarray(eng.cache[n]) for n in names}
        for _ in range(6):
            eng.step()
        eng.sync()
        for n in names:
            after = np.asarray(eng.cache[n])
            assert after.shape[0] == 3
            np.testing.assert_array_equal(after[:, free],
                                          before[n][:, free], err_msg=n)
        eng.run([])
        assert r1.generated == sequential_decode(model, params, r1.prompt,
                                                 14, eos=-1)

    def test_paged_hbm_cost_scales_with_occupied_pages(
            self, model_and_params):
        """Acceptance pin: the paged decode structural_cost.hbm_bytes
        follows occupied pages, NOT max_len.  Doubling capacity at fixed
        occupancy leaves traffic unchanged; doubling occupancy raises
        it; a quarter-occupied paged cache beats the dense decode shape
        that streams the whole strip."""
        del model_and_params
        base = dict(b=8, h=4, sq=1, d=64, n=256, causal=False)
        for mode in REGISTRY.modes("flash_attention_matmul"):
            paged = REGISTRY.structural_cost(
                "flash_attention_matmul", mode, skv=1024, page_size=128,
                pages_occupied=16, **base)
            grown = REGISTRY.structural_cost(
                "flash_attention_matmul", mode, skv=4096, page_size=128,
                pages_occupied=16, **base)
            double = REGISTRY.structural_cost(
                "flash_attention_matmul", mode, skv=1024, page_size=128,
                pages_occupied=32, **base)
            dense = REGISTRY.structural_cost(
                "flash_attention_matmul", mode, skv=1024, **base)
            assert paged["hbm_bytes"] == grown["hbm_bytes"], mode
            assert double["hbm_bytes"] > paged["hbm_bytes"], mode
            assert paged["hbm_bytes"] < dense["hbm_bytes"], mode
            assert paged["blocks_visited"] == 4 * 16, mode


class TestQuantizedEngine:
    """ISSUE 7 tentpole: the fully-quantized serve tick — int8 weights
    dequantized in VMEM through the _q8 registry twins, int8 KV pages +
    scale strips through the block tables — must emit the same tokens as
    the f32 engine on the tiny model, stay ONE compiled program with
    zero per-tick host transfers, and hold MORE pages than f32 inside
    the same byte budget (the capacity win quantization exists for)."""

    PAGE = 8

    def _quant_engine(self, cfg, **serve_kw):
        from repro.models import common
        model = build_model(cfg, ParallelConfig(
            remat="none", fuse_epilogues=True, use_pallas_attn=True,
            weight_precision="int8", kv_cache_int8=True))
        qparams = common.quantize_params(model.init_params(KEY))
        return model, qparams, BatchedEngine(model, qparams, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1, **serve_kw))

    def test_quantized_paged_matches_f32_tokens(self, model_and_params):
        """int8 weights + int8 KV pages against the unfused dense f32
        engine: identical greedy tokens for identical request streams
        (the tiny model's logit gaps dominate the declared int8
        tolerance), through one compiled tick program."""
        model, params, cfg = model_and_params
        prompts = _prompts(cfg, 4)
        max_news = [4, 7, 5, 6]
        reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=m)
                        for i, (p, m) in enumerate(zip(prompts, max_news))]
        want = {r.rid: r.generated
                for r in BatchedEngine(model, params, ServeConfig(
                    batch_slots=2, max_seq_len=CACHE_LEN,
                    eos_id=-1)).run(reqs())}
        _, _, eng = self._quant_engine(cfg, page_size=self.PAGE)
        got = {r.rid: r.generated for r in eng.run(reqs())}
        assert len(got) == 4
        assert got == want
        assert eng.trace_count == 1

    def test_quantized_tick_loop_is_transfer_free(self, model_and_params):
        """Quantization must not smuggle host work into the tick: scale
        pools, int8 pages, and block tables all live on device; steps
        run under a disallow-all transfer guard."""
        _, _, cfg = model_and_params
        _, _, eng = self._quant_engine(cfg, page_size=self.PAGE)
        eng.add_request(Request(rid=0, prompt=[3, 5, 7],
                                max_new_tokens=50))
        eng.step()                       # compile outside the guard
        with jax.transfer_guard("disallow"):
            for _ in range(10):
                eng.step()
        eng.sync()
        assert len(eng.slots[0].generated) >= 11
        assert eng.trace_count == 1

    def test_int8_pool_holds_more_pages_per_byte(self, model_and_params):
        """Capacity accounting follows the real footprint: at the same
        ``kv_pool_bytes`` budget the int8 engine sizes its pool
        4·hd/(hd+4)x larger than f32 (hd=16 -> 3.2x), because an int8
        page costs (hd+4) bytes per (token, head, direction) against
        f32's 4·hd."""
        model, params, cfg = model_and_params
        budget = 64 * 1024
        eng_f = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, kv_pool_bytes=budget))
        _, _, eng_q = self._quant_engine(cfg, page_size=self.PAGE,
                                         kv_pool_bytes=budget)
        hd = cfg.resolved_head_dim
        assert eng_q.page_footprint_bytes() * 4 * hd == \
            eng_f.page_footprint_bytes() * (hd + 4)
        assert eng_q.num_pages == budget // eng_q.page_footprint_bytes()
        assert eng_f.num_pages == budget // eng_f.page_footprint_bytes()
        assert eng_q.num_pages > eng_f.num_pages


class TestHostSyncFreeTick:
    def test_tick_compiles_exactly_once(self, model_and_params):
        """The fused tick must stay ONE compiled program across admissions,
        slot reuse, EOS exits, and hundreds of ticks."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        prompts = _prompts(cfg, 6)
        eng.run([Request(rid=i, prompt=p, max_new_tokens=4 + i % 3)
                 for i, p in enumerate(prompts)])
        assert eng.tick_count > 5
        assert eng.trace_count == 1

    def test_tick_loop_is_transfer_free(self, model_and_params):
        """Zero host transfers inside the tick loop: steps run under a
        disallow-all transfer guard (warmup outside the guard pays the
        one-time compile)."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        eng.add_request(Request(rid=0, prompt=[3, 5, 7],
                                max_new_tokens=50))
        eng.step()                       # compile outside the guard
        with jax.transfer_guard("disallow"):
            for _ in range(10):
                eng.step()
        eng.sync()
        assert len(eng.slots[0].generated) >= 11


class TestSSDDecodeServe:
    """ISSUE 9 tentpole: the mamba decode tick routed through the fused
    ``ssd_decode`` kernel must emit token-for-token what the jnp einsum
    trio emits, while the engine's tick stays ONE compiled program with
    zero per-tick host transfers."""

    def _cfg(self):
        from repro.models.config import SSMConfig
        return ModelConfig(name="t", family="ssm", num_layers=2,
                           d_model=64, num_heads=0, num_kv_heads=0, d_ff=0,
                           vocab_size=128, dtype="float32",
                           ssm=SSMConfig(state_dim=16, head_dim=16,
                                         chunk_size=8), subquadratic=True)

    def _run(self, model, params, prompts, max_news):
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        done = eng.run([Request(rid=i, prompt=p, max_new_tokens=m)
                        for i, (p, m) in enumerate(zip(prompts,
                                                       max_news))])
        return {r.rid: r.generated for r in done}, eng

    def test_fused_decode_tick_matches_jnp_recurrence(self):
        """Oversubscribed (4 requests, 2 slots, mid-stream admission):
        the fused-ssd-decode engine and the library engine emit identical
        token streams, and the fused tick compiles exactly once."""
        cfg = self._cfg()
        lib = build_model(cfg, ParallelConfig(remat="none"))
        fused = build_model(cfg, ParallelConfig(remat="none",
                                                fuse_epilogues=True))
        assert fused.policy.fuses() and not lib.policy.fuses()
        params = lib.init_params(KEY)
        prompts = _prompts(cfg, 4)
        max_news = [4, 7, 5, 6]
        want, _ = self._run(lib, params, prompts, max_news)
        got, eng = self._run(fused, params, prompts, max_news)
        assert len(got) == 4
        assert got == want
        assert eng.trace_count == 1          # still ONE tick program

    def test_fused_mamba_tick_is_transfer_free(self):
        """The [B,G,Hg,N,P] state never leaves the device between ticks:
        steps run under a disallow-all transfer guard with the fused
        recurrence inside the one compiled program."""
        cfg = self._cfg()
        fused = build_model(cfg, ParallelConfig(remat="none",
                                                fuse_epilogues=True))
        params = fused.init_params(KEY)
        eng = BatchedEngine(fused, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1))
        eng.add_request(Request(rid=0, prompt=[3, 5, 7],
                                max_new_tokens=30))
        eng.step()                       # compile outside the guard
        with jax.transfer_guard("disallow"):
            for _ in range(10):
                eng.step()
        eng.sync()
        assert len(eng.slots[0].generated) >= 11
        assert eng.trace_count == 1


class TestAdmissionBugfixes:
    """ISSUE 9 satellites: a never-admittable request is rejected instead
    of livelocking run(), and frontier_pages uses ceil semantics at page
    boundaries."""

    PAGE = 8

    def test_oversized_request_rejected_not_livelocked(
            self, model_and_params):
        """A request whose page reservation exceeds the pool's TOTAL is
        marked done/rejected at admission; the rest of the stream is
        served normally and run() returns promptly instead of burning
        masked ticks to max_ticks."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, num_pages=2))
        # reserve = ceil((3 + 20 - 1) / 8) = 3 > 2 total pages
        big = Request(rid=0, prompt=[3, 5, 7], max_new_tokens=20)
        ok = Request(rid=1, prompt=[2, 4, 6], max_new_tokens=4)
        done = eng.run([big, ok])
        assert big.rejected and big.done and big.generated == []
        assert big.slot is None              # never occupied a slot
        assert not ok.rejected and ok.done
        assert ok.generated == sequential_decode(model, params, ok.prompt,
                                                 4, eos=-1)
        assert {r.rid for r in done} == {0, 1}
        assert eng.tick_count < 100          # bounded by real work

    def test_all_unadmittable_returns_without_ticking(
            self, model_and_params):
        """The pure livelock case: nothing active, nothing admissible —
        run() must return immediately, not spin to max_ticks."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=2, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE, num_pages=1))
        big = Request(rid=0, prompt=[3, 5, 7], max_new_tokens=20)
        done = eng.run([big])
        assert big.rejected and big.done
        assert eng.tick_count == 0
        assert [r.rid for r in done] == [0]

    def test_frontier_pages_exact_on_page_boundary(self, model_and_params):
        """A frontier landing exactly on a page boundary (pos == k·ps)
        has written k pages — the stats row must say k, not k+1 (the
        pre-fix floor+1 overcount)."""
        model, params, cfg = model_and_params
        eng = BatchedEngine(model, params, ServeConfig(
            batch_slots=1, max_seq_len=CACHE_LEN, eos_id=-1,
            page_size=self.PAGE))
        prompt = _prompts(cfg, 1)[0] * 3     # 9 tokens
        eng.add_request(Request(rid=0, prompt=prompt[:7],
                                max_new_tokens=12))
        eng.step()                           # pos: 7 -> 8 == page_size
        eng.sync()
        assert eng.tick_stats[0]["frontier_pages"] == 1
