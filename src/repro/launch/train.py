"""Training driver.

Runs real training on whatever devices exist (CPU here; the same code
path drives a TPU slice — the mesh shape is the only difference).  For
container-scale runs use a reduced config + small mesh:

  PYTHONPATH=src python -m repro.launch.train \\
      --arch granite-8b --reduced --steps 50 --batch 8 --seq 128 \\
      --mesh 1x1 --ckpt-dir /tmp/ckpt

Fault-tolerance paths exercised: checkpoint/restart (rerun the same
command — it resumes), preemption (SIGTERM → drain + save), straggler
logging, elastic restart (change --mesh between runs; restore reshards).
"""
import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_ctx, make_mesh
from repro.models import build_model
from repro.models.config import ParallelConfig
from repro.parallel.sharding import tree_shardings
from repro.train import OptConfig, build_train_step, init_opt_state
from repro.train.loop import (LoopConfig, PreemptionGuard, resume_or_init,
                              train_loop)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=("none", "full", "dots"))
    ap.add_argument("--compression", default="none",
                    choices=("none", "bf16", "int8_ef"))
    ap.add_argument("--mesh", default="1x1",
                    help="DATAxMODEL, e.g. 1x1, 2x2 (needs devices)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", default=None, help="write JSON report here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    par = ParallelConfig(remat=args.remat, grad_accum=args.grad_accum,
                         grad_compression=args.compression)

    dshape = tuple(int(x) for x in args.mesh.split("x"))
    n_dev = dshape[0] * dshape[1]
    mesh = make_mesh(dshape, ("data", "model")) if n_dev > 1 else None
    ctx = make_ctx(mesh, par, cfg)
    model = build_model(cfg, par, ctx)

    opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(args.steps // 20, 1),
                        compression=args.compression)
    step_fn, shardings = build_train_step(model, opt_cfg, ctx)
    if mesh is not None:
        param_sh = tree_shardings(ctx, model.param_specs())
        opt_sh = {"step": ctx.sharding(()), "m": param_sh, "v": param_sh,
                  "master": param_sh}
        if opt_cfg.compression == "int8_ef":
            opt_sh["ef"] = param_sh
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1),
                          out_shardings=(param_sh, opt_sh, None))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    data_cfg = DataConfig(
        global_batch=args.batch, seq_len=args.seq,
        vocab_size=cfg.vocab_size, seed=args.seed, family=cfg.family,
        num_frames=cfg.encdec.num_frames if cfg.encdec else 0,
        num_patches=cfg.vlm.num_patches if cfg.vlm else 0,
        d_model=cfg.d_model)
    dataset = SyntheticLMDataset(data_cfg).start()

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    def init_fn():
        params = model.init_params(jax.random.PRNGKey(args.seed))
        return params, init_opt_state(params, opt_cfg)

    restore_sh = None
    if mesh is not None:
        param_sh = tree_shardings(ctx, model.param_specs())
        restore_sh = {"params": param_sh,
                      "opt_state": {"step": ctx.sharding(()),
                                    "m": param_sh, "v": param_sh,
                                    "master": param_sh}}
    params, opt_state, start = resume_or_init(ckpt, init_fn, restore_sh)
    if start:
        print(f"[train] resumed from checkpoint at step {start}")

    def batch_put(batch):
        # VLM reduced: trim tokens so patches + tokens fit model seq plan
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def sink(step, rec):
        print(f"[step {step:5d}] loss={rec['loss']:.4f} "
              f"lr={rec.get('lr', 0):.2e} "
              f"gnorm={rec.get('grad_norm', 0):.3f} "
              f"dt={rec['step_time_s'] * 1e3:.0f}ms"
              + (" STRAGGLER" if rec.get("straggler") else ""))

    guard = PreemptionGuard()
    loop_cfg = LoopConfig(total_steps=args.steps,
                          checkpoint_every=args.ckpt_every,
                          log_every=args.log_every)
    layout = getattr(model, "param_layout", None)
    params, opt_state, report = train_loop(
        step_fn, params, opt_state, dataset, loop_cfg, ckpt,
        start_step=start, metrics_sink=sink, preemption=guard,
        batch_put=batch_put,
        save_extra={"param_layout": dataclasses.asdict(layout)}
        if layout is not None else None)
    dataset.stop()
    print(f"[train] done at step {report['final_step']} "
          f"(preempted={report['preempted']}, "
          f"stragglers={len(report['straggler_events'])})")
    if args.report:
        os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
