"""Serving driver: batched engine with continuous batching.

  PYTHONPATH=src python -m repro.launch.serve \\
      --arch granite-8b --slots 4 --requests 10 --max-new 16

The reduced config is the default; ``--no-reduced`` serves the published
widths.
"""
import argparse

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.config import ParallelConfig
from repro.serve import BatchedEngine, Request, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    par = ParallelConfig(remat="none")
    model = build_model(cfg, par)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    serve_cfg = ServeConfig(batch_slots=args.slots,
                            max_seq_len=args.max_seq,
                            max_new_tokens=args.max_new)
    engine = BatchedEngine(model, params, serve_cfg)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        2, cfg.vocab_size, args.prompt_len).tolist(),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    done = engine.run(reqs)
    total_tokens = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens")
    for r in done[:3]:
        print(f"  rid={r.rid} generated={r.generated[:8]}...")


if __name__ == "__main__":
    main()
