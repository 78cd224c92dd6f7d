"""The granite hybrid against its plain reference (CPU, one period of ten
layers at tiny widths, ``data/tiny-hybrid.json``), the faults the cell's
check must catch, the hybrid step's counts, and the hybrid readers.

Tolerances.  The tiny configuration is float32 end to end, so the
program and the reference differ only in the order of float32 sums: the
chunked dual form of the scan against the per-step recurrence, paged
against whole-sequence attention.  That is about 1e-6 of the largest
logit; ``TOL`` allows 1e-4 of it.  Leaving out any one part of the
mathematics moves the logits by a hundred times that or more
(``test_leaving_out_a_part_fails``), so the comparison tells them apart.
"""
import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, roofline, roofline_hybrid, spec
from bench.tests.tiny import DATA, make_tree
from bench.weights import make_params

REF = spec.load_reference("granite_hybrid")
SEED = 2**35 + 3
#: of the largest reference logit (module docstring)
TOL = 1e-4
CHUNK = 16          # the tiny config's mamba_chunk_size: prompts of 2 chunks
PAGE = 16


def tiny_cfg():
    with open(os.path.join(DATA, "tiny-hybrid.json")) as f:
        return json.load(f)


def build(cfg, **changes):
    from repro.models import build_model
    mcfg = dataclasses.replace(harness.model_config(cfg), **changes)
    return build_model(mcfg, harness.parallel_config())


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    model = build(cfg)
    return cfg, model, make_params(model, SEED)


def prompts(n, length=2 * CHUNK, shared=0, seed=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(2, 512, shared).tolist()
    return [head + rng.integers(2, 512, length - shared + 3 * i).tolist()
            for i in range(n)]


def served_logits(model, params, batches, ticks):
    """Drive the engine's paged cache through the model's decode step.

    ``batches`` is a list of prompt lists: the first batch is admitted
    into an engine of that many slots; before each later batch, the
    first slots' requests are finished and the batch admitted into the
    freed slots.  Each batch then decodes ``ticks`` greedy steps, every
    admitted request's logits kept.  Returns ``[(prompt, fed tokens,
    logits)]``, logits ``[1 + steps, vocab]`` from position
    ``len(prompt) - 1``."""
    from repro.serve import BatchedEngine, Request, ServeConfig
    engine = BatchedEngine(model, params, ServeConfig(
        batch_slots=len(batches[0]), max_seq_len=128, eos_id=-1,
        page_size=PAGE))
    step = jax.jit(model.decode_step)
    out, live = [], {}
    for b, batch in enumerate(batches):
        for r in list(live.values())[:len(batch)] if b else []:
            r.done = True
        reqs = [Request(rid=100 * b + i, prompt=p, max_new_tokens=1000)
                for i, p in enumerate(batch)]
        assert engine.admit(reqs) == len(reqs)
        for r in reqs:
            first, _ = model.prefill(params, {"tokens": jnp.asarray(
                [r.prompt])})
            rec = (r.prompt, [r.generated[0]], [first[0]])
            out.append(rec)
            live[r.slot] = r
            r.rec = rec
        for _ in range(ticks):
            logits, engine.cache = step(params, engine.last_tokens,
                                        engine.cache)
            engine.last_tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            for slot, r in live.items():
                if not r.done:
                    r.rec[2].append(logits[slot])
                    r.rec[1].append(int(engine.last_tokens[slot]))
    return engine, out


def worst(params, cfg, served):
    """The widest gap between served and reference logits, over the
    largest reference logit, across requests."""
    ratio = 0.0
    for prompt, fed, logits in served:
        got = jnp.stack(logits)
        pos = list(range(len(prompt) - 1, len(prompt) - 1 + len(got)))
        ref = REF.logits(params, cfg, prompt + fed[:len(got) - 1], pos)
        ratio = max(ratio, float(jnp.max(jnp.abs(got - ref))
                                 / jnp.max(jnp.abs(ref))))
    return ratio


def test_config_files_state_the_programs_sizes():
    """The reference reads the configuration file; the program its own
    entry.  They must describe one model."""
    from repro import configs
    for cfg, arch in ((tiny_cfg(), "granite-4.0-h-micro-reduced"),
                      (spec.load_config("granite-4.0-h-micro"),
                       "granite-4.0-h-micro")):
        m = configs.get_config(arch)
        s = m.ssm
        assert cfg["program_arch"] == arch
        assert tuple(cfg["layer_types"]) == m.layer_types
        assert len(m.layer_types) == m.num_layers == cfg["num_hidden_layers"]
        assert (cfg["mamba_d_state"], cfg["mamba_d_head"],
                cfg["mamba_expand"], cfg["mamba_n_groups"],
                cfg["mamba_d_conv"], cfg["mamba_chunk_size"]) == (
            s.state_dim, s.head_dim, s.expand, s.n_groups, s.conv_width,
            s.chunk_size)
        assert cfg["mamba_n_heads"] * s.head_dim == s.expand * m.d_model
        assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
                cfg["logits_scaling"], cfg["attention_multiplier"]) == (
            m.embedding_multiplier, m.residual_multiplier, m.logits_scaling,
            m.attention_multiplier)
        assert cfg["shared_intermediate_size"] == m.d_ff
        assert cfg["position_embedding_type"] == "nope"
        assert m.pos_emb == "none" and m.tie_embeddings
        assert cfg["mamba_conv_bias"] and cfg["ssm_state_dtype"] == "float32"


def test_reference_matches_prefill_logits(tiny):
    cfg, model, params = tiny
    prompt = prompts(1)[0]
    got, _ = model.prefill(params, {"tokens": jnp.asarray([prompt])})
    ref = REF.logits(params, cfg, prompt, [len(prompt) - 1])
    assert float(jnp.max(jnp.abs(got - ref))) < TOL * float(
        jnp.max(jnp.abs(ref)))


def test_paged_decode_matches_the_reference(tiny):
    """Prefill, then paged decode through several steps, with a slot
    freed and reused mid-run: the reused slot's request starts from its
    own prefill's state, not its last occupant's."""
    cfg, model, params = tiny
    engine, served = served_logits(
        model, params, [prompts(2, seed=1), prompts(1, seed=2)], ticks=6)
    assert engine.model.kv_layers == 1 and engine.model.ssm_layers == 9
    assert engine.cache["k_pages"].shape[0] == 1
    assert engine.cache["ssm_state"].shape[:2] == (9, 2)
    assert len(served) == 3
    assert worst(params, cfg, served) < TOL


def test_common_prefix_shares_nothing(tiny):
    """Two prompts with two full pages in common: no page is shared (a
    page cannot resume a recurrence), and each serves its own state."""
    cfg, model, params = tiny
    engine, served = served_logits(
        model, params, [prompts(2, shared=2 * PAGE, seed=3)], ticks=4)
    assert engine.pool.shared_hits == 0
    assert worst(params, cfg, served) < TOL


def test_engine_serves_the_reference_tokens(tiny):
    """The engine's own tick, continuous batching over 2 slots with 4
    requests (each slot reused) and a shared document."""
    cfg, model, params = tiny
    from repro.serve import BatchedEngine, Request, ServeConfig
    engine = BatchedEngine(model, params, ServeConfig(
        batch_slots=2, max_seq_len=96, eos_id=-1, page_size=PAGE))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6 + 4 * i)
            for i, p in enumerate(prompts(4, shared=PAGE, seed=4))]
    engine.run(reqs)
    assert engine.pool.shared_hits == 0
    assert [r.slot for r in reqs] == [0, 1, 0, 1]
    for r in reqs:
        g = check.gaps(REF, params, cfg, r.prompt, r.generated)
        assert len(g) == r.max_new_tokens
        assert float(g.max()) < 1e-4


def _no_gate(y, z, weight, eps, n_groups, policy=None):
    from repro.models import common
    return common.rmsnorm(y.astype(jnp.float32), weight, eps)


def _no_norm(y, z, weight, eps, n_groups, policy=None):
    return y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)) \
        * weight.astype(jnp.float32)


def _zeroed(params, name):
    mamba = dict(params["mamba"])
    mamba[name] = jnp.zeros_like(mamba[name])
    return dict(params, mamba=mamba)


#: the program with one part of the mathematics left out
LEFT_OUT = {
    "embedding_multiplier": dict(cfg={"embedding_multiplier": None}),
    "residual_multiplier": dict(cfg={"residual_multiplier": 1.0}),
    "logits_scaling": dict(cfg={"logits_scaling": 1.0}),
    "attention_multiplier": dict(cfg={"attention_multiplier": None}),
    "conv_bias": dict(params="conv_b"),
    "D": dict(params="D"),
    "gate": dict(norm=_no_gate),
    "gated_norm": dict(norm=_no_norm),
}


@pytest.mark.parametrize("part", list(LEFT_OUT))
def test_leaving_out_a_part_fails(tiny, monkeypatch, part):
    cfg, _, params = tiny
    how = LEFT_OUT[part]
    model = build(cfg, **how.get("cfg", {}))
    served_params = _zeroed(params, how["params"]) if "params" in how \
        else params
    if "norm" in how:
        from repro.models import ssd
        monkeypatch.setattr(ssd, "gated_rmsnorm", how["norm"])
    _, served = served_logits(model, served_params, [prompts(2, seed=5)],
                              ticks=3)
    assert worst(params, cfg, served) > 100 * TOL


#: granite-4.0-h-micro's depth, pattern, multipliers and bfloat16 weights
#: at a width the CPU runs in seconds
NARROW = {"hidden_size": 128, "intermediate_size": 512,
          "shared_intermediate_size": 512, "num_attention_heads": 2,
          "num_key_value_heads": 1, "vocab_size": 4096, "mamba_n_heads": 4,
          "mamba_chunk_size": 32}
#: the widest logit error against the reference, over the logits' spread,
#: averaged over three prompts: 0.0064 with the float32 stream, 0.0139
#: with a bfloat16 one; the limit sits 1.5 times from each
STREAM_TOL = 0.0094


@pytest.mark.parametrize("stream", ["float32", "bfloat16"])
def test_residual_stream_precision(monkeypatch, stream):
    """The residual stream is carried in float32.  Over 40 layers, each
    x0.22 branch rounded into a x12 stream, a bfloat16 stream makes the
    logits' error against the reference two to three times as wide.  The
    cell's sampled ``logit_gap`` does not see it (11 chip runs of a
    bfloat16-stream build read 0 to 0.0215, all under the limit, which the
    float32 program's own widest reading sets), so this comparison is what
    holds the stream to float32."""
    from repro.configs.granite_4p0_h_micro import CONFIG
    from repro.models import build_model, granite_hybrid
    cfg = dict(spec.load_config("granite-4.0-h-micro"), **NARROW)
    model = build_model(dataclasses.replace(
        CONFIG, d_model=128, d_ff=512, num_heads=2, num_kv_heads=1,
        vocab_size=4096, ssm=dataclasses.replace(CONFIG.ssm, chunk_size=32)
    ), harness.parallel_config())
    params = make_params(model, SEED)
    embed = granite_hybrid.GraniteHybridLM._embed
    monkeypatch.setattr(granite_hybrid.GraniteHybridLM, "_embed",
                        lambda self, p, t: embed(self, p, t).astype(stream))
    prefill = jax.jit(model.prefill)
    rng = np.random.default_rng(6)
    errors = []
    for _ in range(3):
        prompt = rng.integers(2, 4096, 2 * 32).tolist()
        got, _ = prefill(params, {"tokens": jnp.asarray([prompt])})
        ref = REF.logits(params, cfg, prompt, [len(prompt) - 1])
        errors.append(float(jnp.max(jnp.abs(got - ref))
                            / (jnp.max(ref) - jnp.min(ref))))
    assert (np.mean(errors) < STREAM_TOL) == (stream == "float32"), errors


# ---------------------------------------------------------------------------
# the cell's check, on a tiny hybrid cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    repo, root = make_tree(str(tmp_path_factory.mktemp("bench")))
    shutil.copy(os.path.join(DATA, "tiny-hybrid.json"),
                os.path.join(root, "configs", "tiny-hybrid.json"))
    bench = spec.load_benchmark(repo)
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": "bench/configs/tiny-hybrid.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-hybrid.chat",
                               "config": "tiny-hybrid",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return repo, root


@pytest.fixture(autouse=True)
def _no_checkout_cache(monkeypatch, tmp_path):
    # keep the tests' compiled programs out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def run(tree):
    repo, root = tree
    return harness.run_cell("tiny-hybrid.chat", SEED, 1.0, False,
                            require_tpu=False, repo=repo, root=root)


def _state_dropped(orig):
    """The tick's new recurrent state is dropped: each tick starts from
    the state admission wrote."""
    def tick(self, params, tokens, live, remaining, cache):
        out = orig(self, params, tokens, live, remaining, cache)
        kept = dict(out[3], ssm_state=cache["ssm_state"],
                    conv_state=cache["conv_state"])
        return out[:3] + (kept,) + out[4:]
    return "_tick_impl", tick


def _state_not_reset(orig):
    """Admission adds the prefill's state to what the slot held, so a
    reused slot carries its last occupant's state."""
    def write(self, states, new, slot):
        return {n: a.at[:, slot].add(new[n][:, 0].astype(a.dtype))
                for n, a in states.items()}
    return "_write_state_impl", write


def test_sound_hybrid_run_is_correct(tree):
    out = run(tree)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 4


@pytest.mark.parametrize("fault", [_state_dropped, _state_not_reset])
def test_broken_state_is_not_correct(tree, monkeypatch, fault):
    from repro.serve import BatchedEngine
    name = fault(None)[0]
    monkeypatch.setattr(BatchedEngine, name,
                        fault(getattr(BatchedEngine, name))[1])
    out = run(tree)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


# ---------------------------------------------------------------------------
# the hybrid step's counts
# ---------------------------------------------------------------------------

#: a hand-sized hybrid: 2 Mamba layers and 1 attention layer
HAND = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16,
        "shared_intermediate_size": 16, "vocab_size": 32,
        "layer_types": ["mamba", "attention", "mamba"], "mamba_expand": 2,
        "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 3,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_conv_bias": True,
        "mamba_chunk_size": 2, "torch_dtype": "bfloat16",
        "embedding_dtype": "float32", "ssm_state_dtype": "float32",
        "tie_word_embeddings": True}


def test_hand_count():
    # Mamba mixer: in 8 x (16 + 22 + 4), conv 4 x 22 + 22, dt_bias, A_log
    # and D 3 x 4, gated norm 16, out 16 x 8
    assert roofline_hybrid.mamba_params(HAND) == 336 + 110 + 12 + 16 + 128
    # attention 8x8 + 2 x 8x4 + 8x8; MLP 3 x 8 x 16
    assert roofline_hybrid.attention_params(HAND) == 192
    assert roofline_hybrid.mlp_params(HAND) == 384
    blocks = 2 * 602 + 192 + 3 * (384 + 16)
    assert roofline_hybrid.block_params(HAND) == blocks
    # bf16 blocks and final norm, the tied f32 head once
    weights = (blocks + 8) * 2 + 8 * 32 * 4
    assert roofline_hybrid.weight_bytes(HAND) == weights
    # per slot and Mamba layer: state 4x4x3 f32, conv 3 taps x 22 bf16
    state = 2 * (48 * 4 + 66 * 2)
    assert roofline_hybrid.state_bytes_per_slot(HAND) == state
    assert roofline_hybrid.kv_bytes_per_token(HAND) == 2 * 4 * 2
    flops, nbytes = roofline_hybrid.decode_step(HAND, 3, 20)
    assert nbytes == weights + 2 * 3 * state + 20 * 16 + 3 * 8 * 4
    recur = 2 * (5 * 48 + 2 * 4 * 22)
    assert flops == 2 * (blocks + 256) * 3 + 4 * 2 * 4 * 20 + 3 * recur
    # a 3-token prefill: chunks of 2 and 1 positions
    ssd = 2 * ((2 * 4 * 3 + 4 * (2 * 4 * 4 + 4 * 2 * 3 * 4))
               + (2 * 1 * 3 + 4 * (2 * 1 * 4 + 4 * 1 * 3 * 4)))
    assert roofline_hybrid.ssd_flops(HAND, 3) == ssd
    flops, nbytes = roofline_hybrid.prefill(HAND, 3)
    assert flops == 2 * blocks * 3 + 4 * 2 * 4 * 6 + ssd + 2 * 256
    assert nbytes == weights + 3 * 16 + state


def test_state_bytes_are_the_configured_sizes():
    cfg = spec.load_config("granite-4.0-h-micro")
    ssm = 64 * 64 * 128 * 4
    conv = 3 * (4096 + 2 * 128) * 2
    assert roofline_hybrid.state_bytes_per_slot(cfg) == 36 * (ssm + conv)
    assert roofline_hybrid.kv_bytes_per_token(cfg) == 4 * 2 * 8 * 64 * 2
    # the program's own state arrays, for 32 slots
    model = build(cfg)
    shapes = jax.eval_shape(lambda: model.init_paged_cache(32, 8, 128, 24))
    held = sum(shapes[n].size * shapes[n].dtype.itemsize
               for n in model.recurrent_state)
    assert held == 32 * roofline_hybrid.state_bytes_per_slot(cfg)


def test_tied_head_counted_once():
    untied = dict(HAND, tie_word_embeddings=False)
    blocks = roofline_hybrid.block_params(HAND)
    assert roofline_hybrid.weight_bytes(untied) == (blocks + 8 + 256) * 2
    assert roofline_hybrid.weight_bytes(HAND) - (blocks + 8) * 2 == 256 * 4


def test_the_full_model_is_about_three_billion():
    cfg = spec.load_config("granite-4.0-h-micro")
    blocks = roofline_hybrid.block_params(cfg)
    assert 2.9e9 < blocks < 3.1e9
    assert roofline_hybrid.head_params(cfg) == 2048 * 100352


# ---------------------------------------------------------------------------
# the hybrid readers
# ---------------------------------------------------------------------------

PEAK = roofline.PEAKS["TPU v5 lite"]


def _ctx(trace, tick_stats=(), prefill_lens=()):
    return harness.LayerCtx(
        cfg=spec.load_config("granite-4.0-h-micro"), records=[],
        window_s=1.0, give_up=1.0, admit_calls=[],
        prefill_lens=list(prefill_lens), tick_work=[],
        tick_stats=list(tick_stats), shared_hits=0, prompt_pages=0,
        trace=trace, peak=PEAK)


TRACE = {"modules": {"tick": {"device_s": 0.5, "count": 20},
                     "prefill": {"device_s": 0.25, "count": 3}},
         "tick_scopes": {"seconds": {"ssm": 0.2, "mlp": 0.1}, "ticks": 20},
         "busy_s": 0.8, "window_s": 1.0}
ROWS = [{"tick": i, "live_slots": 32, "live_positions": 32 * 700}
        for i in range(20)]


def test_hybrid_decode_roofline_reads_tick_stats():
    cfg = spec.load_config("granite-4.0-h-micro")
    reader = spec.load_metric("hybrid_decode_hbm_roofline")
    assert reader.UNIT == "%"
    one = roofline.roofline_s(*roofline_hybrid.decode_step(cfg, 32, 22400),
                              PEAK)
    assert reader.read(_ctx(TRACE, ROWS)) == pytest.approx(
        100 * 20 * one / 0.5)
    # bound by bytes: weights, 32 slots' state read and written, live KV
    assert 0 < reader.read(_ctx(TRACE, ROWS)) < 100


def test_hybrid_prefill_mfu_reads_prefill_lengths():
    cfg = spec.load_config("granite-4.0-h-micro")
    reader = spec.load_metric("hybrid_prefill_mfu")
    flops = sum(roofline_hybrid.prefill(cfg, n)[0] for n in (512, 1024))
    assert reader.read(_ctx(TRACE, prefill_lens=(512, 1024))) == \
        pytest.approx(100 * flops / (0.25 * PEAK["flops"]))


def test_decode_ssm_ms_reads_the_ssm_scope():
    reader = spec.load_metric("decode_ssm_ms")
    assert reader.UNIT == "ms"
    assert reader.read(_ctx(TRACE)) == pytest.approx(10.0)


@pytest.mark.parametrize("name", ["hybrid_decode_hbm_roofline",
                                  "hybrid_prefill_mfu", "decode_ssm_ms"])
def test_hybrid_readers_find_nothing_without_their_keys(name):
    reader = spec.load_metric(name)
    assert reader.read(_ctx(None, ROWS, (512,))) is None
    # a dense run's tick stats carry no live_positions
    bare = {"modules": {}, "busy_s": 0.8, "window_s": 1.0}
    assert reader.read(_ctx(bare, [{"tick": 0, "live_slots": 3}])) is None
