"""Fused-epilogue lowerings (ISSUE 3 tentpole).

Pins: numerical equivalence of the fused ops to their unfused pairs
(same tolerance discipline as test_registry.py), the exact one-activation
-round-trip HBM saving in the registered structural costs, auto selection
across dialects, the declared (warned + recorded) fallbacks, policy-gated
model routing, and the fused rows in the committed bench artifact.
"""
import json
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ExecutionPolicy, IsaMode, LoweringFallbackWarning,
                        REGISTRY, TARGET, UISA_UNIVERSAL10)
from repro.kernels import ops, ref
from repro.kernels.fused import FUSED_OPS

KEY = jax.random.PRNGKey(11)
ALL_MODES = ("abstract", "abstract+shuffle", "native", "library")


def _inputs(rows=33, d=200, n=96):
    ka, kb, kc, kd = jax.random.split(KEY, 4)
    x = jax.random.normal(ka, (rows, d), jnp.float32)
    w = jax.random.normal(kb, (d,), jnp.float32) + 1.0
    p = jax.random.normal(kc, (d, n), jnp.float32)
    r = jax.random.normal(kd, (rows, d), jnp.float32)
    return x, w, p, r


# ---------------------------------------------------------------------------
# Numerical equivalence to the unfused pair
# ---------------------------------------------------------------------------


class TestNumerics:
    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_rmsnorm_matmul_matches_unfused_pair(self, mode):
        x, w, p, _ = _inputs()
        want = jnp.einsum("rd,dn->rn", ref.rmsnorm(x, w), p)
        got = ops.fused_rmsnorm_matmul(x, w, p, mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_add_rmsnorm_matches_unfused_pair(self, mode):
        x, w, _, r = _inputs()
        want_s = x + r
        want_h = ref.rmsnorm(want_s, w)
        h, s = ops.fused_add_rmsnorm(x, r, w, mode=mode)
        np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                                   rtol=1e-4, atol=1e-4)

    def test_leading_batch_dims(self):
        x, w, p, _ = _inputs(rows=6, d=128, n=64)
        x3 = x.reshape(2, 3, 128)
        got = ops.fused_rmsnorm_matmul(x3, w, p, mode="native")
        assert got.shape == (2, 3, 64)
        want = jnp.einsum("bsd,dn->bsn", ref.rmsnorm(x3, w), p)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", (True, False))
    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_flash_attention_matmul_matches_unfused_pair(self, mode,
                                                         causal):
        """True GQA (un-repeated [B,Hkv,S,D] cache, folded by the kernel's
        index maps) + ragged seq (padded kv must stay masked when the
        causal mask is off) + ragged wo width: the fused flash→wo output
        equals flash attention followed by the wo einsum."""
        ks = jax.random.split(KEY, 4)
        b, h, hkv, s, d, n = 2, 4, 2, 96, 32, 80
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
        wo = jax.random.normal(ks[3], (h * d, n), jnp.float32)
        o = ref.attention(q, jnp.repeat(k, h // hkv, axis=1),
                          jnp.repeat(v, h // hkv, axis=1), causal=causal)
        want = jnp.einsum("bsh,hn->bsn",
                          o.transpose(0, 2, 1, 3).reshape(b, s, h * d), wo)
        got = ops.fused_flash_attention_matmul(q, k, v, wo, causal=causal,
                                               mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("mode",
                             ("abstract", "abstract+shuffle", "native"))
    def test_flash_attention_noncausal_padded_kv_masked(self, mode):
        """Regression (found by review): with causal=False and skv not a
        block multiple, the zero-padded keys must not receive softmax
        weight — the causal mask that normally hides the pad is off."""
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 2, 96, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 96, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 96, 32), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=False, mode=mode)
        want = ref.attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_flash_attention_matmul_bf16_cross_head_accumulation(self):
        """The cross-head sum runs in an f32 VMEM scratch with ONE final
        output-dtype cast — bf16 outputs must match the unfused bf16
        pair without per-head rounding drift even with many heads."""
        ks = jax.random.split(KEY, 4)
        b, h, s, d, n = 1, 8, 64, 32, 64
        q = jax.random.normal(ks[0], (b, h, s, d)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, h, s, d)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, h, s, d)).astype(jnp.bfloat16)
        wo = jax.random.normal(ks[3], (h * d, n)).astype(jnp.bfloat16)
        want = ops.fused_flash_attention_matmul(q, k, v, wo, causal=True,
                                                mode="library")
        got = ops.fused_flash_attention_matmul(q, k, v, wo, causal=True,
                                               mode="native")
        assert got.dtype == jnp.bfloat16
        # bf16 carries ~8 mantissa bits: both routes round their inputs
        # and outputs to bf16, so the bound is bf16-relative
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-1)

    @pytest.mark.parametrize("mode", ALL_MODES)
    def test_rmsnorm_swiglu_matches_unfused_pair(self, mode):
        x, w, _, _ = _inputs()
        f = 96
        w_cat = jax.random.normal(jax.random.fold_in(KEY, 2),
                                  (x.shape[-1], 2 * f), jnp.float32)
        y = ref.rmsnorm(x, w)
        want = jax.nn.silu(y @ w_cat[:, f:]) * (y @ w_cat[:, :f])
        got = ops.fused_rmsnorm_swiglu(x, w, w_cat, mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# The acceptance criterion: exactly one activation round trip saved
# ---------------------------------------------------------------------------


class TestStructuralCost:
    @pytest.mark.parametrize("mode",
                             ("abstract", "abstract+shuffle", "native"))
    def test_rmsnorm_matmul_saves_exactly_one_round_trip(self, mode):
        rows, d, n = 1024, 1024, 512
        itemsize = 4
        fused = REGISTRY.structural_cost("rmsnorm_matmul", mode,
                                         rows=rows, d=d, n=n)
        norm = REGISTRY.structural_cost("rmsnorm", mode, rows=rows, d=d)
        proj = REGISTRY.structural_cost(
            "gemm", mode if mode != "abstract+shuffle" else "abstract",
            m=rows, n=n, k=d)
        unfused_sum = norm["hbm_bytes"] + proj["hbm_bytes"]
        round_trip = 2 * rows * d * itemsize     # write + read-back
        assert fused["hbm_bytes"] == unfused_sum - round_trip
        assert fused["hbm_bytes_saved"] == round_trip

    def test_library_row_is_the_unfused_pair(self):
        cost = REGISTRY.structural_cost("rmsnorm_matmul", "library",
                                        rows=256, d=256, n=256)
        assert cost["hbm_bytes_saved"] == 0
        assert cost["hbm_bytes"] == cost["hbm_bytes_unfused_pair"]

    @pytest.mark.parametrize("mode",
                             ("abstract", "abstract+shuffle", "native"))
    def test_add_rmsnorm_saves_the_readback_leg(self, mode):
        rows, d = 512, 1024
        cost = REGISTRY.structural_cost("add_rmsnorm", mode,
                                        rows=rows, d=d)
        # honest asymmetry: the write leg survives as the residual
        # stream's own output, only the norm's read-back disappears
        assert cost["hbm_bytes_saved"] == rows * d * 4
        assert cost["hbm_bytes"] == \
            cost["hbm_bytes_unfused_pair"] - rows * d * 4

    @pytest.mark.parametrize("mode",
                             ("abstract", "abstract+shuffle", "native"))
    def test_flash_attention_matmul_saves_one_activation_round_trip(
            self, mode):
        """The acceptance pin: hbm delta == exactly one [B,S,H,D] trip."""
        shape = dict(b=2, h=8, sq=1024, skv=1024, d=64, n=512, causal=True)
        cost = REGISTRY.structural_cost("flash_attention_matmul", mode,
                                        **shape)
        round_trip = 2 * 2 * 1024 * 8 * 64 * 4     # write + read-back
        assert cost["hbm_bytes_saved"] == round_trip
        assert cost["hbm_bytes"] == \
            cost["hbm_bytes_unfused_pair"] - round_trip
        att = REGISTRY.structural_cost(
            "flash_attention", mode, b=2, h=8, sq=1024, skv=1024, d=64,
            causal=True)
        proj = REGISTRY.structural_cost(
            "gemm", mode if mode != "abstract+shuffle" else "abstract",
            m=2 * 1024, n=512, k=8 * 64)
        assert cost["hbm_bytes_unfused_pair"] == \
            att["hbm_bytes"] + proj["hbm_bytes"]

    @pytest.mark.parametrize("mode",
                             ("abstract", "abstract+shuffle", "native"))
    def test_rmsnorm_swiglu_saves_exactly_one_round_trip(self, mode):
        rows, d, f = 1024, 1024, 512
        cost = REGISTRY.structural_cost("rmsnorm_swiglu", mode,
                                        rows=rows, d=d, f=f)
        norm = REGISTRY.structural_cost("rmsnorm", mode, rows=rows, d=d)
        proj = REGISTRY.structural_cost(
            "gemm", mode if mode != "abstract+shuffle" else "abstract",
            m=rows, n=2 * f, k=d)
        round_trip = 2 * rows * d * 4              # write + read-back
        assert cost["hbm_bytes_saved"] == round_trip
        assert cost["hbm_bytes"] == \
            norm["hbm_bytes"] + proj["hbm_bytes"] - round_trip

    def test_new_fused_library_rows_are_the_unfused_pairs(self):
        for op, shape in (
                ("flash_attention_matmul",
                 dict(b=1, h=2, sq=256, skv=256, d=64, n=128, causal=True)),
                ("rmsnorm_swiglu", dict(rows=256, d=256, f=256))):
            cost = REGISTRY.structural_cost(op, "library", **shape)
            assert cost["hbm_bytes_saved"] == 0
            assert cost["hbm_bytes"] == cost["hbm_bytes_unfused_pair"]

    def test_shuffle_variant_structurally_cheapest(self):
        """The §VII.C ordering holds for the fused ops too: zero scratch
        for the shuffle moment tree, round-trips for the abstract one."""
        shape = dict(rows=1024, d=1024, n=512)
        ab = REGISTRY.structural_cost("rmsnorm_matmul", "abstract", **shape)
        sh = REGISTRY.structural_cost("rmsnorm_matmul", "abstract+shuffle",
                                      **shape)
        assert ab["scratch_bytes_total"] > 0
        assert sh["scratch_bytes_total"] == 0
        assert sh["lane_shuffles_per_block"] > 0


# ---------------------------------------------------------------------------
# Auto selection + declared fallbacks
# ---------------------------------------------------------------------------


class TestDispatch:
    def test_auto_picks_shuffle_on_target(self):
        pol = ExecutionPolicy(mode="auto", dialect=TARGET.name)
        for op in FUSED_OPS:
            low = REGISTRY.select(op, pol, shape=ops.PROBE_SHAPES[op])
            assert low.mode is IsaMode.ABSTRACT_SHUFFLE, (op, low.mode)

    def test_auto_degrades_to_scratch_tree_without_shuffle(self):
        pol = ExecutionPolicy(mode="auto", dialect=UISA_UNIVERSAL10.name)
        for op in FUSED_OPS:
            low = REGISTRY.select(op, pol, shape=ops.PROBE_SHAPES[op])
            assert low.mode is IsaMode.ABSTRACT, (op, low.mode)

    def test_new_ops_declare_both_fallbacks(self):
        for op in ("flash_attention_matmul", "rmsnorm_swiglu"):
            fb = REGISTRY.fallback_for(op, IsaMode.ABSTRACT_SHUFFLE)
            assert fb is not None and fb.to is IsaMode.ABSTRACT
            fb = REGISTRY.fallback_for(op, IsaMode.NATIVE)
            assert fb is not None and fb.to is IsaMode.LIBRARY

    def test_shuffle_request_falls_back_declared(self):
        """abstract+shuffle on a no-shuffle dialect: warned + recorded,
        lands on the fused scratch-tree variant (never silent)."""
        x, w, p, _ = _inputs()
        n0 = len(REGISTRY.fallback_events)
        pol = ExecutionPolicy(mode="abstract+shuffle",
                              dialect=UISA_UNIVERSAL10.name)
        with pytest.warns(LoweringFallbackWarning):
            got = ops.fused_rmsnorm_matmul(x, w, p, policy=pol)
        ev = REGISTRY.fallback_events[n0]
        assert (ev.op, ev.requested, ev.used) == \
            ("rmsnorm_matmul", "abstract+shuffle", "abstract")
        want = jnp.einsum("rd,dn->rn", ref.rmsnorm(x, w), p)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_native_request_falls_back_to_unfused_pair(self):
        """native on a foreign dialect: the declared escape is the
        library row, which IS the unfused jnp pair."""
        x, _, _, r = _inputs()
        w = jnp.ones((x.shape[-1],), jnp.float32)
        n0 = len(REGISTRY.fallback_events)
        pol = ExecutionPolicy(mode="native", dialect="nvidia-ada-sm89")
        with pytest.warns(LoweringFallbackWarning):
            h, s = ops.fused_add_rmsnorm(x, r, w, policy=pol)
        ev = REGISTRY.fallback_events[n0]
        assert (ev.op, ev.requested, ev.used) == \
            ("add_rmsnorm", "native", "library")
        np.testing.assert_allclose(np.asarray(s), np.asarray(x + r),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Policy-gated model routing
# ---------------------------------------------------------------------------


def _tiny_model(**par_kw):
    from repro.models.config import ModelConfig, ParallelConfig
    from repro.models.transformer import TransformerLM
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=128,
                      qk_norm=True, dtype="float32")
    return TransformerLM(cfg, ParallelConfig(remat="none", **par_kw))


class TestModelRouting:
    def test_forced_fusion_selects_a_fused_lowering(self):
        """fuse_epilogues=True under the default (library-norm) policy
        must dispatch a fused Pallas variant through the kernel view —
        not silently land on the library row (the unfused pair)."""
        pol = _tiny_model(fuse_epilogues=True).policy
        assert pol.fuses()
        low = REGISTRY.select("rmsnorm_matmul", pol.kernel(),
                              shape=ops.PROBE_SHAPES["rmsnorm_matmul"])
        assert low.mode is not IsaMode.LIBRARY, low.mode

    def test_fuse_gate_default_follows_auto(self):
        assert _tiny_model().policy.fuses() is False
        assert _tiny_model(isa_mode="auto").policy.fuses() is True
        assert _tiny_model(isa_mode="auto",
                           fuse_epilogues=False).policy.fuses() is False
        assert _tiny_model(fuse_epilogues=True).policy.fuses() is True

    def test_fused_model_matches_reference(self):
        batch = {"tokens": jnp.arange(32).reshape(2, 16) % 128,
                 "labels": jnp.arange(32).reshape(2, 16) % 128}
        ref_model = _tiny_model()
        params = ref_model.init_params(jax.random.PRNGKey(0))
        want, _ = ref_model.loss_fn(params, batch)
        for kw in (dict(isa_mode="auto"), dict(fuse_epilogues=True),
                   # the flash→wo fused epilogue path (attn_seq)
                   dict(fuse_epilogues=True, use_pallas_attn=True),
                   dict(isa_mode="abstract", fuse_epilogues=True)):
            model = _tiny_model(**kw)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", LoweringFallbackWarning)
                got, _ = model.loss_fn(params, batch)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_fused_decode_matches_reference(self):
        batch = {"tokens": jnp.arange(16).reshape(2, 8) % 128}
        ref_model = _tiny_model()
        params = ref_model.init_params(jax.random.PRNGKey(1))
        logits_ref, cache_ref = ref_model.prefill(params, batch)
        step_ref, _ = ref_model.decode_step(
            params, jnp.argmax(logits_ref, -1).astype(jnp.int32), cache_ref)
        fused = _tiny_model(fuse_epilogues=True)
        logits_f, cache_f = fused.prefill(params, batch)
        np.testing.assert_allclose(np.asarray(logits_f),
                                   np.asarray(logits_ref),
                                   rtol=1e-3, atol=1e-3)
        step_f, _ = fused.decode_step(
            params, jnp.argmax(logits_f, -1).astype(jnp.int32), cache_f)
        np.testing.assert_allclose(np.asarray(step_f),
                                   np.asarray(step_ref),
                                   rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# The committed bench artifact carries the fused rows + the gate is green
# ---------------------------------------------------------------------------


class TestBenchArtifact:
    def test_fused_rows_present_and_gate_green(self):
        root = pathlib.Path(__file__).resolve().parent.parent
        data = json.loads((root / "BENCH_kernels.json").read_text())
        by_kernel = {}
        for row in data["rows"]:
            by_kernel.setdefault(row["kernel"], set()).add(row["mode"])
        assert {"abstract", "abstract+shuffle", "native", "library"} <= \
            by_kernel.get("rmsnorm_matmul", set())
        assert {"abstract", "abstract+shuffle", "native", "library"} <= \
            by_kernel.get("add_rmsnorm", set())
        # the --compare gate against itself (coverage + structural
        # recompute at the committed shapes) must be green
        import sys
        sys.path.insert(0, str(root))
        try:
            from benchmarks.bench_kernels import compare
        finally:
            sys.path.pop(0)
        assert compare(data, data) == []


# ---------------------------------------------------------------------------
# Norm-prologue tiles: fitted to the problem and to the chip's VMEM
# ---------------------------------------------------------------------------


class TestRowKernelTiles:
    """granite-8b's hot spots (d_model 4096; qkv 6144, [wi|wg] 2 x 14336
    and head 49152 columns; prefill, decode and head row counts)."""

    SHAPES = [(2048, 6144, 1), (16, 6144, 1), (1, 49152, 1),
              (2048, 14336, 2), (16, 14336, 2)]

    @pytest.mark.parametrize("rows,n,n_weights", SHAPES)
    @pytest.mark.parametrize("itemsize", (2, 4))
    def test_no_padding_past_the_lanes(self, rows, n, n_weights, itemsize):
        from repro.kernels.fused import row_kernel_tiles
        bm, bn, _ = row_kernel_tiles(1152, 1152, rows, 4096, n, itemsize,
                                     n_weights)
        assert bm % 128 == 0 and bn % 128 == 0
        assert -(-rows // 128) * 128 % bm == 0
        assert n % bn == 0

    @pytest.mark.parametrize("rows,n,n_weights", SHAPES)
    def test_one_stage_keeps_eq1_occupancy(self, rows, n, n_weights):
        """Double-buffered, one stage fits twice in the target's VMEM:
        half of it stays free for the kernel's f32 temporaries."""
        from repro.kernels.fused import row_kernel_tiles
        _, _, vmem = row_kernel_tiles(1024, 1024, rows, 4096, n, 2,
                                      n_weights)
        assert TARGET.buffer_occupancy(vmem // 2, 2) >= 2

    def test_vmem_limit_raised_only_past_the_default(self):
        from repro.core.pipeline import DEFAULT_SCOPED_VMEM, compiler_params
        assert compiler_params(None, DEFAULT_SCOPED_VMEM) is None
        raised = compiler_params(None, DEFAULT_SCOPED_VMEM + 1)
        assert raised.vmem_limit_bytes == TARGET.scratchpad_bytes
        assert raised.dimension_semantics is None
        native = compiler_params(("parallel",))
        assert native.dimension_semantics == ("parallel",)
        assert native.vmem_limit_bytes is None

    @pytest.mark.parametrize("f", (256, 200))
    def test_swiglu_reads_both_halves_of_one_operand(self, f):
        """wi and wg are two column windows of the one [wi|wg] operand;
        a width that does not divide the tile is padded per half."""
        x, w, _, _ = _inputs(rows=24, d=128)
        wc = jax.random.normal(KEY, (128, 2 * f), jnp.float32)
        got = ops.fused_rmsnorm_swiglu(x[:, :128], w[:128], wc,
                                       mode="native")
        want = ops.fused_rmsnorm_swiglu(x[:, :128], w[:128], wc,
                                        mode="library")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
