"""Serving example: continuous batching over more requests than slots.

Loads a reduced assigned architecture (default zamba2 hybrid — the
SSM+attention cache is the interesting one) and pushes a request stream
through the BatchedEngine: admissions, per-tick decode, slot reuse.

  PYTHONPATH=src python examples/serve_batch.py [--arch mamba2-2.7b]
"""
import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.models import build_model
from repro.models.config import ParallelConfig
from repro.serve import BatchedEngine, Request, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-1.2b")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = configs.get_reduced(args.arch)
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init_params(jax.random.PRNGKey(0))
    print(f"[serve_batch] {cfg.name} ({cfg.family}), "
          f"{args.slots} slots, {args.requests} requests")

    engine = BatchedEngine(model, params, ServeConfig(
        batch_slots=args.slots, max_seq_len=64,
        max_new_tokens=args.max_new, eos_id=-1))

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size, 10).tolist(),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.generated) for r in done)
    print(f"[serve_batch] {len(done)} requests -> {tokens} tokens "
          f"in {dt:.2f}s ({tokens / dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind}, compile included)")
    for r in done:
        print(f"  rid={r.rid}: {r.generated}")
    assert len(done) == args.requests
    assert all(len(r.generated) == args.max_new for r in done)
    print("[serve_batch] OK — continuous batching over-subscribed "
          f"{args.requests} reqs onto {args.slots} slots")


if __name__ == "__main__":
    main()
