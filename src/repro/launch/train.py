"""End-to-end training driver.

Runs a REAL training loop (default: a reduced config that fits this CPU
container; pass --full to compile the production config on a real TPU
slice).  Demonstrates the whole substrate: sharded params/optimizer,
microbatched step, deterministic resumable data, async checkpoints,
restart-on-failure, straggler accounting.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 100 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_arch, smoke_config
from repro.data.tokens import TokenPipeline
from repro.distributed import sharding, shardctx
from repro.launch.mesh import TPU_PERF_FLAGS, make_production_mesh
from repro.models import model_zoo
from repro.train import checkpoint as ckpt_lib
from repro.train.optimizer import AdamW
from repro.train.trainer import Trainer, TrainState, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="production config + mesh (TPU slice required)")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override smoke width (e.g. ~100M model)")
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (restart demo)")
    args = ap.parse_args()

    if args.full:
        os.environ.setdefault("LIBTPU_INIT_ARGS", TPU_PERF_FLAGS)
        cfg = get_arch(args.arch)
        mesh = make_production_mesh()
    else:
        over = {}
        if args.d_model:
            over.update(d_model=args.d_model,
                        head_dim=max(args.d_model // 8, 16), n_heads=8,
                        n_kv_heads=4,
                        d_ff=0 if get_arch(args.arch).d_ff == 0
                        else args.d_model * 4,
                        vocab_size=8192)
        if args.n_layers:
            period = get_arch(args.arch).layer_period
            over["n_layers"] = max(period, args.n_layers // period * period)
        cfg = smoke_config(args.arch, **over)
        n_dev = jax.device_count()
        mesh = jax.make_mesh((n_dev, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

    bundle = model_zoo.build(cfg)
    opt = AdamW(lr=args.lr, warmup_steps=20, total_steps=args.steps,
                state_dtype=cfg.opt_state_dtype)
    step_fn = make_train_step(bundle.loss_fn, opt,
                              num_microbatches=args.microbatches)
    pipe = TokenPipeline(cfg.vocab_size, args.batch, args.seq)

    params_abs = model_zoo.abstract_params(cfg)
    pshard = sharding.param_shardings(mesh, params_abs)

    with shardctx.use_mesh(mesh):
        jit_step = jax.jit(step_fn, donate_argnums=(0,))

        def init():
            params = bundle.init_params(jax.random.PRNGKey(0))
            params = jax.device_put(params, pshard)
            return TrainState(params, opt.init(params))

        def batch_for_step(step):
            b = pipe.batch_for_step(step)
            out = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.encdec is not None:
                frames = pipe.frames_for_step(step, cfg.d_model)
                out = {"frames": jnp.asarray(frames, cfg.jdtype),
                       "tokens": out["tokens"][:, : args.seq // 4],
                       "labels": out["labels"][:, : args.seq // 4]}
            return out

        trainer = Trainer(jit_step, batch_for_step, init(),
                          ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every,
                          failure_at_step=args.fail_at)
        resumed = trainer.maybe_restore()
        print(f"arch={cfg.name} params={cfg.total_params()/1e6:.1f}M "
              f"devices={mesh.size} resumed={resumed} step={trainer.step}")
        try:
            metrics = trainer.run(args.steps - trainer.step)
        except RuntimeError as e:
            print(f"FAILURE: {e}; restarting from last checkpoint...")
            trainer.maybe_restore()
            metrics = trainer.run(args.steps - trainer.step)
        ckpt_lib.wait()
        print(f"done: {metrics} straggler_events={trainer.straggler_events}")


if __name__ == "__main__":
    main()
