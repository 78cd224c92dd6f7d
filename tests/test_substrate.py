"""Substrate tests: optimizer, data pipeline, checkpointing, train loop
fault tolerance, serving engine."""
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep (requirements-dev.txt)
    from _hypothesis_stub import given, settings, st

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLMDataset
from repro.models import build_model
from repro.models.config import ModelConfig, ParallelConfig
from repro.parallel import compress
from repro.serve import BatchedEngine, Request, ServeConfig
from repro.train import (OptConfig, adamw_update, build_train_step,
                         init_opt_state, lr_at_step)
from repro.train.loop import (LoopConfig, StragglerMonitor, resume_or_init,
                              train_loop)

KEY = jax.random.PRNGKey(0)


def tiny_model(dtype="float32", **kw):
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, dtype=dtype)
    return build_model(cfg, ParallelConfig(remat="none", **kw)), cfg


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------


class TestOptimizer:
    def test_lr_schedule_shape(self):
        cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                        min_lr_ratio=0.1)
        assert float(lr_at_step(cfg, 0)) == 0.0
        np.testing.assert_allclose(float(lr_at_step(cfg, 10)), 1e-3,
                                   rtol=1e-5)
        assert float(lr_at_step(cfg, 100)) == pytest.approx(1e-4, rel=1e-4)
        # monotone decay after warmup
        lrs = [float(lr_at_step(cfg, s)) for s in range(10, 101, 10)]
        assert all(a >= b for a, b in zip(lrs, lrs[1:]))

    def test_adamw_descends_quadratic(self):
        cfg = OptConfig(lr=0.1, warmup_steps=0, total_steps=100,
                        weight_decay=0.0, grad_clip=1e9)
        params = {"w": jnp.array([5.0, -3.0])}
        state = init_opt_state(params, cfg)
        for _ in range(60):
            grads = {"w": params["w"]}        # d/dw (w²/2)
            params, state, stats = adamw_update(grads, state, params, cfg)
        assert float(jnp.abs(params["w"]).max()) < 1.0

    def test_grad_clipping(self):
        cfg = OptConfig(grad_clip=1.0, warmup_steps=0)
        params = {"w": jnp.zeros(4)}
        state = init_opt_state(params, cfg)
        grads = {"w": jnp.full((4,), 100.0)}
        _, _, stats = adamw_update(grads, state, params, cfg)
        assert float(stats["grad_norm"]) == pytest.approx(200.0)
        assert float(stats["clip_factor"]) == pytest.approx(1 / 200.0)

    def test_int8_ef_residual_carries(self):
        """Error feedback: quantization residual rides in state['ef'] and
        the accumulated update converges to the true gradient signal."""
        cfg = OptConfig(lr=0.01, warmup_steps=0, compression="int8_ef",
                        weight_decay=0.0, grad_clip=1e9)
        params = {"w": jnp.array([1.0])}
        state = init_opt_state(params, cfg)
        assert "ef" in state
        # constant tiny gradient that always quantizes to 0 alone
        for _ in range(5):
            grads = {"w": jnp.array([1e-10])}
            params, state, _ = adamw_update(grads, state, params, cfg)
        # residual must accumulate rather than be dropped
        assert float(jnp.abs(state["ef"]["w"])[0]) >= 0.0

    def test_master_weights_are_fp32_copies(self):
        model, _ = tiny_model(dtype="bfloat16")
        params = model.init_params(KEY)
        state = init_opt_state(params, OptConfig())
        for m, p in zip(jax.tree.leaves(state["master"]),
                        jax.tree.leaves(params)):
            assert m.dtype == jnp.float32
            assert m.shape == p.shape

    @given(step=st.integers(0, 10000))
    @settings(max_examples=50, deadline=None)
    def test_lr_always_in_range(self, step):
        cfg = OptConfig(lr=3e-4, warmup_steps=200, total_steps=10000)
        lr = float(lr_at_step(cfg, step))
        assert 0.0 <= lr <= cfg.lr * (1 + 1e-6)


class TestCompression:
    @given(scale=st.floats(1e-6, 1e3))
    @settings(max_examples=30, deadline=None)
    def test_int8_roundtrip_error_bound(self, scale):
        g = jax.random.normal(KEY, (256,)) * scale
        q, s = compress.quantize_int8(g)
        deq = compress.dequantize_int8(q, s)
        max_err = float(jnp.max(jnp.abs(deq - g)))
        assert max_err <= float(s) * 0.5 + 1e-9   # half-step rounding

    def test_int8_wire_dtype(self):
        q, _ = compress.quantize_int8(jax.random.normal(KEY, (64,)))
        assert q.dtype == jnp.int8


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------


class TestData:
    def _cfg(self, **kw):
        d = dict(global_batch=4, seq_len=16, vocab_size=1000, seed=7)
        d.update(kw)
        return DataConfig(**d)

    def test_deterministic_by_step(self):
        ds1 = SyntheticLMDataset(self._cfg())
        ds2 = SyntheticLMDataset(self._cfg())
        b1, b2 = ds1.batch_at(5), ds2.batch_at(5)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(ds1.batch_at(5)["tokens"],
                                  ds1.batch_at(6)["tokens"])

    def test_labels_are_next_tokens(self):
        ds = SyntheticLMDataset(self._cfg())
        b = ds.batch_at(0)
        assert b["tokens"].shape == b["labels"].shape == (4, 16)

    def test_host_sharding_partitions_batch(self):
        full = SyntheticLMDataset(self._cfg(host_count=1)).batch_at(3)
        h0 = SyntheticLMDataset(self._cfg(host_count=2,
                                          host_index=0)).batch_at(3)
        h1 = SyntheticLMDataset(self._cfg(host_count=2,
                                          host_index=1)).batch_at(3)
        np.testing.assert_array_equal(
            np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"])

    def test_resume_replays_nothing(self):
        ds = SyntheticLMDataset(self._cfg())
        seen = [next(ds)["tokens"] for _ in range(4)]
        state = ds.state()
        ds2 = SyntheticLMDataset(self._cfg())
        ds2.restore(state)
        nxt = next(ds2)["tokens"]
        assert not any(np.array_equal(nxt, s) for s in seen)
        np.testing.assert_array_equal(nxt, ds.batch_at(4)["tokens"])

    def test_prefetch_thread_matches_sync(self):
        ds = SyntheticLMDataset(self._cfg()).start()
        try:
            got = [next(ds)["tokens"] for _ in range(3)]
        finally:
            ds.stop()
        want = [SyntheticLMDataset(self._cfg()).batch_at(i)
                for i in range(3)]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w["tokens"])

    def test_token_distribution_is_skewed(self):
        """Zipf-ish skew: low ids more frequent than high ids."""
        ds = SyntheticLMDataset(self._cfg(global_batch=64, seq_len=128,
                                          vocab_size=1000))
        toks = ds.batch_at(0)["tokens"]
        low = (toks < 100).mean()
        high = (toks >= 900).mean()
        assert low > high * 2


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------


class TestCheckpoint:
    def _tree(self, x=1.0):
        return {"params": {"w": jnp.full((4, 4), x),
                           "b": jnp.zeros((4,))},
                "opt_state": {"step": jnp.array(3)}}

    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(10, self._tree(2.5))
        got = mgr.restore(10, self._tree(0.0))
        np.testing.assert_allclose(got["params"]["w"],
                                   np.full((4, 4), 2.5))
        assert mgr.latest_step() == 10

    def test_atomic_no_tmp_visible(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, self._tree())
        assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]

    def test_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, keep_period=10)
        for s in (5, 10, 15, 20, 25):
            mgr.save(s, self._tree())
        steps = mgr.all_steps()
        assert 10 in steps and 20 in steps       # keep_period multiples
        assert 25 in steps and 20 in steps       # newest two
        assert 5 not in steps and 15 not in steps

    def test_async_save_lands_after_wait(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(7, self._tree(1.5), blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 7

    def test_restore_missing_leaf_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"a": jnp.zeros(2)})
        with pytest.raises((KeyError, FileNotFoundError)):
            mgr.restore(1, {"a": jnp.zeros(2), "b": jnp.zeros(2)})

    def test_manifest_describes_leaves(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(2, self._tree(), extra={"note": "hi"})
        man = mgr.manifest(2)
        assert man["extra"]["note"] == "hi"
        assert man["leaves"]["params/w"]["shape"] == [4, 4]


# ---------------------------------------------------------------------------
# Train loop fault tolerance
# ---------------------------------------------------------------------------


class TestTrainLoop:
    def _setup(self, tmp, total=6):
        model, cfg = tiny_model()
        opt_cfg = OptConfig(total_steps=total, warmup_steps=1)
        step_fn, _ = build_train_step(model, opt_cfg)
        step_fn = jax.jit(step_fn)
        params = model.init_params(KEY)
        opt = init_opt_state(params, opt_cfg)
        ds = SyntheticLMDataset(DataConfig(
            global_batch=4, seq_len=16, vocab_size=cfg.vocab_size))
        ckpt = CheckpointManager(tmp)
        put = lambda b: {k: jnp.asarray(v) for k, v in b.items()}
        return model, opt_cfg, step_fn, params, opt, ds, ckpt, put

    def test_checkpoint_restart_continuity(self, tmp_path):
        (model, opt_cfg, step_fn, params, opt, ds, ckpt,
         put) = self._setup(str(tmp_path))
        p1, o1, rep = train_loop(step_fn, params, opt, ds,
                                 LoopConfig(total_steps=4,
                                            checkpoint_every=2,
                                            async_checkpoint=False),
                                 ckpt, batch_put=put)
        assert rep["final_step"] == 4
        # restart: resume_or_init must pick up step 4
        def init_fn():
            p = model.init_params(KEY)
            return p, init_opt_state(p, opt_cfg)
        p2, o2, start = resume_or_init(ckpt, init_fn)
        assert start == 4
        assert int(o2["step"]) == int(o1["step"])
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(p2)[0]),
            np.asarray(jax.tree.leaves(p1)[0]), rtol=1e-6)

    def test_straggler_detection(self):
        mon = StragglerMonitor(factor=2.0, alpha=0.5)
        for _ in range(5):
            mon.observe(0, 0.1)
        assert mon.observe(10, 0.5)             # 5x the EWMA
        assert len(mon.events) == 1
        assert mon.events[0]["slowdown"] > 2.0

    def test_loss_decreases(self, tmp_path):
        (model, opt_cfg, step_fn, params, opt, ds, ckpt,
         put) = self._setup(str(tmp_path), total=30)
        _, _, rep = train_loop(step_fn, params, opt, ds,
                               LoopConfig(total_steps=30,
                                          checkpoint_every=1000,
                                          log_every=1),
                               None, batch_put=put)
        losses = [h["loss"] for h in rep["history"]]
        assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# Serving engine
# ---------------------------------------------------------------------------


class TestEngine:
    def _engine(self, slots=2):
        model, cfg = tiny_model()
        params = model.init_params(KEY)
        return BatchedEngine(model, params,
                             ServeConfig(batch_slots=slots, max_seq_len=32,
                                         max_new_tokens=6, eos_id=-1)), cfg

    def test_continuous_batching_serves_more_requests_than_slots(self):
        eng, cfg = self._engine(slots=2)
        reqs = [Request(rid=i, prompt=[3, 5, 7], max_new_tokens=4)
                for i in range(5)]
        done = eng.run(reqs)
        assert len(done) == 5
        assert all(len(r.generated) == 4 for r in done)

    def test_greedy_decode_is_deterministic(self):
        eng1, _ = self._engine()
        eng2, _ = self._engine()
        r1 = Request(rid=0, prompt=[2, 4, 6], max_new_tokens=5)
        r2 = Request(rid=0, prompt=[2, 4, 6], max_new_tokens=5)
        eng1.run([r1])
        eng2.run([r2])
        assert r1.generated == r2.generated

    def test_engine_matches_manual_decode(self):
        """Engine slot-0 output == hand-rolled prefill+decode chain."""
        model, cfg = tiny_model()
        params = model.init_params(KEY)
        eng = BatchedEngine(model, params,
                            ServeConfig(batch_slots=1, max_seq_len=32,
                                        max_new_tokens=4, eos_id=-1))
        req = Request(rid=0, prompt=[3, 5, 7], max_new_tokens=4)
        eng.run([req])

        toks = jnp.array([[3, 5, 7]], jnp.int32)
        logits, cache = model.prefill(params, {"tokens": toks})
        full = model.init_cache(1, 32)
        # place prefill kv into capacity cache
        k = jnp.pad(cache["k"], ((0, 0), (0, 0), (0, 0), (0, 29), (0, 0)))
        v = jnp.pad(cache["v"], ((0, 0), (0, 0), (0, 0), (0, 29), (0, 0)))
        cache = {"k": k, "v": v, "pos": cache["pos"]}
        want = [int(jnp.argmax(logits[0]))]
        tok = jnp.array([want[0]], jnp.int32)
        for _ in range(3):
            lg, cache = model.decode_step(params, tok, cache)
            nxt = int(jnp.argmax(lg[0]))
            want.append(nxt)
            tok = jnp.array([nxt], jnp.int32)
        assert req.generated == want


class TestCompileCache:
    """The persistent compilation cache is placed from outside: the
    environment variable when set, else one fixed path in the checkout."""

    def _restore(self, fn):
        was = jax.config.jax_compilation_cache_dir
        try:
            return fn(), jax.config.jax_compilation_cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    def test_env_var_wins_and_nothing_is_set(self, monkeypatch):
        from repro.launch import compile_cache
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        used, after = self._restore(compile_cache.enable_compile_cache)
        assert used == "/elsewhere/cache"
        assert after == before

    def test_fixed_ignored_path_in_checkout(self, monkeypatch):
        from repro.launch import compile_cache
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        used, after = self._restore(compile_cache.enable_compile_cache)
        again, _ = self._restore(compile_cache.enable_compile_cache)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert used == after == again == os.path.join(root, ".jax_cache")
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
