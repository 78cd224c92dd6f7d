#!/usr/bin/env python3
"""Smoke run of the paged serving path on one TPU chip, at full model width.

  python3 chip_smoke.py              # one chip: both policy phases
  python3 chip_smoke.py --chips 4    # four chips: the TP-sharded prefill

One chip.  granite-8b (``configs/granite_8b.py``) at its published widths
(d_model 4096, 32 heads / 8 KV heads, head_dim 128, d_ff 14336, vocab
49152, bf16) with depth cut from 36 to 16 layers: about 7 GB of bf16
block weights plus 1.2 GB of embedding and head, so the weights and the
KV page pool fill most of the chip's 16 GB.  Weights are random, drawn
from ``--seed``.  Phases, all in this one process:

- ``xla`` and ``auto``: a :class:`BatchedEngine` with a paged cache
  (page size 128, 16 slots) serves seeded requests of three prompt
  lengths, over-subscribing the slots, under the default policy (XLA)
  and under ``isa_mode="auto", use_pallas_attn=True`` (the Pallas
  kernels), on the same parameters.  The first pass, one request per
  prompt length, compiles the programs; the second is timed.
- ``compare``: jitted ``model.prefill`` and ``model.decode_step`` of
  both policies on the same prompts and the same paged cache.  Their
  logits must agree within ``LOGIT_TOL``; the ``auto`` programs must hold
  their ``tpu_custom_call`` kernels, and the registry must record no
  fallback.

Four chips (``--chips 4``): only a TP-sharded prefill of the same cut
config over a ``("data", "model") = (1, 4)`` mesh, against the one-device
prefill of the same parameters.

Times printed here are from this smoke run (compile included where
labelled), not benchmark numbers.  Without a TPU the script exits
non-zero and prints no result line.  The last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: layers kept of granite-8b's 36 (widths untouched)
DEPTH = 16
SLOTS = 16
PAGE_SIZE = 128
PROMPT_LENS = (256, 1024, 2048)
REQUESTS_PER_LEN = 6          # 18 requests over 16 slots: slots are reused
NEW_TOKENS = 32
#: device bytes kept free beside weights and pool: prefill activations
#: (a 2048-token prompt) and the compiled programs' temporaries
HEADROOM_BYTES = 2 << 30

#: Logit agreement bound, as a share of the reference's largest |logit|.
#: Both policies compute in bf16 with f32 accumulation, but they round at
#: different points (the kernels keep the normalized row, the softmax and
#: the per-head accumulator in f32; XLA rounds each op's output to bf16)
#: and sum in a different order.  Each rounding is worth up to 2^-9 of
#: the value; compounded through 16 residual layers that is a few percent
#: of the logit scale.  A wrong kernel (mask, page gather, head mapping,
#: tiling) moves the logits by their own size, far past this bound.
LOGIT_TOL = 0.1


@dataclasses.dataclass(frozen=True)
class Smoke:
    """The sizes of one smoke run (the defaults are the chip's)."""

    depth: int = DEPTH
    slots: int = SLOTS
    page_size: int = PAGE_SIZE
    prompt_lens: tuple = PROMPT_LENS
    requests_per_len: int = REQUESTS_PER_LEN
    new_tokens: int = NEW_TOKENS
    headroom_bytes: int = HEADROOM_BYTES
    seed: int = 0

    @property
    def max_seq(self) -> int:
        return max(self.prompt_lens) + self.new_tokens


def log(msg: str) -> None:
    print(msg, flush=True)


def granite(depth: int):
    from repro.configs.granite_8b import CONFIG
    return dataclasses.replace(CONFIG, name=f"granite-8b-{depth}l",
                               num_layers=depth)


def policies():
    """The two phases' parallel configs: default (XLA) and Pallas."""
    from repro.models.config import ParallelConfig
    return {"xla": ParallelConfig(remat="none"),
            "auto": ParallelConfig(remat="none", isa_mode="auto",
                                   use_pallas_attn=True)}


def prompts(s: Smoke, vocab: int, per_len: int):
    import numpy as np
    rng = np.random.default_rng(s.seed)
    return [rng.integers(2, vocab, n).tolist()
            for _ in range(per_len) for n in s.prompt_lens]


def mem(device, key: str = "peak_bytes_in_use") -> int:
    """One of the device's memory counters (``memory_stats()``)."""
    return device.memory_stats()[key]


def pool_pages(s: Smoke, cfg, device) -> int:
    """Pages that fit in what the weights leave.  Every admission copies
    a whole K or V pool outside jit (``_write_slot_paged``), so the pool
    is sized to fit twice; capped at the slots' dense equivalent."""
    free = (mem(device, "bytes_limit") - mem(device, "bytes_in_use")
            - s.headroom_bytes)
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    page = (cfg.num_layers * 2 * cfg.num_kv_heads * s.page_size
            * cfg.resolved_head_dim * itemsize)
    dense = s.slots * -(-s.max_seq // s.page_size)
    pages = min(free // (2 * page), dense)
    if pages < -(-s.max_seq // s.page_size):
        raise RuntimeError(f"no room for one request's pages: {free} "
                           f"bytes free after the weights")
    return int(pages)


def serve_phase(name, model, params, s: Smoke, num_pages: int, device):
    """Warm up (compile) then serve the seeded requests; returns stats."""
    import jax
    from repro.serve import BatchedEngine, Request, ServeConfig
    cfg = ServeConfig(batch_slots=s.slots, max_seq_len=s.max_seq,
                      max_new_tokens=s.new_tokens, eos_id=-1,
                      page_size=s.page_size, num_pages=num_pages)
    engine = BatchedEngine(model, params, cfg)
    vocab = model.cfg.vocab_size
    t0 = time.perf_counter()
    warm = [Request(rid=i, prompt=p, max_new_tokens=2)
            for i, p in enumerate(prompts(s, vocab, 1))]
    engine.run(warm)
    jax.block_until_ready(engine.cache)
    compile_s = time.perf_counter() - t0
    reqs = [Request(rid=i, prompt=p, max_new_tokens=s.new_tokens)
            for i, p in enumerate(prompts(s, vocab, s.requests_per_len))]
    t0 = time.perf_counter()
    done = engine.run(reqs)
    jax.block_until_ready(engine.cache)
    run_s = time.perf_counter() - t0
    tokens = sum(len(r.generated) for r in done)
    if len(done) != len(reqs) or tokens != len(reqs) * s.new_tokens:
        raise RuntimeError(f"{name}: {len(done)}/{len(reqs)} requests, "
                           f"{tokens} tokens")
    out = {"phase": name, "warmup_compile_s": round(compile_s, 3),
           "run_s": round(run_s, 3), "requests": len(done),
           "tokens": tokens, "ticks": engine.tick_count,
           "tick_traces": engine.trace_count,
           "peak_bytes_in_use": mem(device)}
    log(f"[smoke] {json.dumps(out)}")
    return out


def kernels_in(compiled_text: str):
    return sorted(set(re.findall(r"uisa_[a-z0-9_]+", compiled_text)))


def compile_checked(fn, *args, pallas: bool):
    """AOT-compile ``fn``; a Pallas program must hold its kernels as
    ``tpu_custom_call``s (interpret mode would lower them to plain HLO)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    text = compiled.as_text()
    kernels = kernels_in(text)
    if pallas and ("tpu_custom_call" not in text or not kernels):
        raise RuntimeError(f"{fn.__name__}: no uisa tpu_custom_call in the "
                           f"compiled auto program")
    return compiled, secs, kernels


def rel_diff(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def pages_for(model, cache):
    """A paged cache with token-major pages, as the XLA engine writes
    them, in ``model``'s page layout: the Pallas kernel's pages are
    head-major, ``[L, P, Hkv, page_size, x]``."""
    if model.token_major_pages:
        return cache
    return {name: a.swapaxes(2, 3) if name.endswith("pages") else a
            for name, a in cache.items()}


def compare_phase(models, params, s: Smoke, device):
    """Logits of both policies on the same prompts and the same cache."""
    import jax.numpy as jnp
    from repro.serve import BatchedEngine, Request, ServeConfig
    found = set()
    worst = 0.0
    for prompt in prompts(s, models["xla"].cfg.vocab_size, 1):
        toks = jnp.asarray(prompt, jnp.int32)[None, :]
        logits = {}
        for name, model in models.items():
            exe, secs, kernels = compile_checked(
                model.prefill, params, {"tokens": toks},
                pallas=name == "auto")
            found.update(kernels)
            logits[name] = exe(params, {"tokens": toks})[0]
            log(f"[smoke] compare prefill len={len(prompt)} {name}: "
                f"compile {secs:.3f}s kernels {kernels}")
        d = rel_diff(logits["auto"], logits["xla"])
        worst = max(worst, d)
        log(f"[smoke] prefill len={len(prompt)} max|dlogit|/max|logit| "
            f"= {d:.6f}")
    # a paged cache written by the XLA engine's admission, read by both
    ref = BatchedEngine(models["xla"], params, ServeConfig(
        batch_slots=len(s.prompt_lens), max_seq_len=s.max_seq,
        max_new_tokens=s.new_tokens, eos_id=-1, page_size=s.page_size))
    ref.admit([Request(rid=i, prompt=p, max_new_tokens=s.new_tokens)
               for i, p in enumerate(prompts(s, ref.model.cfg.vocab_size,
                                             1))])
    logits = {}
    for name, model in models.items():
        cache = pages_for(model, ref.cache)
        exe, secs, kernels = compile_checked(
            model.decode_step, params, ref.last_tokens, cache,
            pallas=name == "auto")
        found.update(kernels)
        logits[name] = exe(params, ref.last_tokens, cache)[0]
        log(f"[smoke] compare decode_step {name}: compile {secs:.3f}s "
            f"kernels {kernels}")
    d = rel_diff(logits["auto"], logits["xla"])
    worst = max(worst, d)
    log(f"[smoke] paged decode_step max|dlogit|/max|logit| = {d:.6f}")
    log(f"[smoke] uisa kernels in the compiled auto programs: "
        f"{sorted(found)}")
    log(f"[smoke] largest logit difference {worst:.6f} "
        f"(tolerance {LOGIT_TOL}); peak_bytes_in_use {mem(device)}")
    if not worst <= LOGIT_TOL:
        raise RuntimeError(f"logits differ by {worst} > {LOGIT_TOL}")
    return worst


def one_chip(s: Smoke, device) -> None:
    import jax
    from repro.core.registry import REGISTRY
    from repro.models import build_model
    cfg = granite(s.depth)
    pars = policies()
    models = {name: build_model(cfg, par) for name, par in pars.items()}
    # the auto model plans the fused layout ([wq|wk|wv], [wi|wg]); the
    # XLA policy reads the same tensors through views, so both phases
    # serve the same parameters.  Jitted so the f32 draws never exist
    # at full size beside their bf16 casts.
    t0 = time.perf_counter()
    params = jax.jit(models["auto"].init_params)(jax.random.PRNGKey(s.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[smoke] {cfg.name}: d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}; {n_params} parameters, init "
        f"{time.perf_counter() - t0:.3f}s; bytes_in_use "
        f"{mem(device, 'bytes_in_use')}")
    num_pages = pool_pages(s, cfg, device)
    log(f"[smoke] page pool: {num_pages} pages of {s.page_size} tokens "
        f"for {s.slots} slots (max_seq {s.max_seq})")
    for name in ("xla", "auto"):
        serve_phase(name, models[name], params, s, num_pages, device)
        gc.collect()
    compare_phase(models, params, s, device)
    events = list(REGISTRY.fallback_events)
    log(f"[smoke] registry fallback events: {len(events)}")
    if events:
        raise RuntimeError(f"registry fallbacks: {events}")


def four_chips(s: Smoke) -> None:
    """TP-sharded prefill over a (1, 4) mesh against one device."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_ctx, make_mesh
    from repro.models import build_model
    from repro.parallel.sharding import sanitize_tree, tree_shardings
    cfg = granite(s.depth)
    par = policies()["xla"]
    ref_model = build_model(cfg, par)
    params = jax.jit(ref_model.init_params)(jax.random.PRNGKey(s.seed))
    toks = jnp.asarray(prompts(s, cfg.vocab_size, 1)[-1], jnp.int32)[None]
    t0 = time.perf_counter()
    ref = jax.jit(ref_model.prefill)(params, {"tokens": toks})[0]
    ref.block_until_ready()
    log(f"[smoke] one-device prefill len={toks.shape[1]}: "
        f"{time.perf_counter() - t0:.3f}s (compile included)")
    mesh = make_mesh((1, 4), ("data", "model"))
    ctx = make_ctx(mesh, par, cfg)
    model = build_model(cfg, par, ctx)
    shardings = sanitize_tree(tree_shardings(ctx, model.param_specs()),
                              params)
    sharded = jax.device_put(params, shardings)
    del params
    for path, leaf in jax.tree_util.tree_leaves_with_path(sharded):
        log(f"[smoke] {jax.tree_util.keystr(path)} {leaf.shape} "
            f"{leaf.sharding.spec} devices "
            f"{sorted(d.id for d in leaf.devices())}")
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        out = jax.jit(model.prefill)(sharded, {"tokens": toks})[0]
        out.block_until_ready()
    log(f"[smoke] TP prefill over {dict(mesh.shape)}: "
        f"{time.perf_counter() - t0:.3f}s (compile included)")
    d = rel_diff(out, ref)
    log(f"[smoke] sharded vs one-device max|dlogit|/max|logit| = {d:.6f} "
        f"(tolerance {LOGIT_TOL})")
    for dev in jax.devices():
        log(f"[smoke] device {dev.id} peak_bytes_in_use {mem(dev)}")
    if not d <= LOGIT_TOL:
        raise RuntimeError(f"sharded logits differ by {d} > {LOGIT_TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {device.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"[smoke] device {device.platform} {device.device_kind} x"
        f"{len(devices)}; jax {jax.__version__}; compile cache {cache_dir}")
    s = Smoke(seed=args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(s)
    else:
        one_chip(s, device)
    log(f"[smoke] total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
