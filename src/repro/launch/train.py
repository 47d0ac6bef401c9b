"""Training driver: config-driven, checkpointed, fault-tolerant.

Single-host usage (CPU example, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real multi-host TPU fleet the same driver runs per host (jax
distributed init is a no-op on CPU); the mesh comes from launch.mesh and
data sharding from DataConfig(num_hosts, host_id). Fault tolerance:
periodic async checkpoints, preemption-triggered sync save, straggler
logging, resume-from-LATEST.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager, latest_step
from repro.configs import get_spec, reduced_model
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, DataPipeline
from repro.models import model_zoo as zoo
from repro.models import params as params_lib
from repro.models import steps as steps_lib
from repro.models.sharding import make_rules
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import PreemptionGuard, StragglerDetector


def build_trainer(arch: str, *, reduced: bool, seq: int, batch: int,
                  steps: int, mesh=None, data_path=None, seed=0,
                  lr: float = 3e-4):
    spec = get_spec(arch)
    cfg = reduced_model(spec.model) if reduced else spec.model
    par = spec.parallelism if mesh is not None else \
        spec.parallelism.replace(remat="none", fsdp=False,
                                 sequence_parallel=False)
    shape = ShapeConfig("train", "train", seq, batch)
    rules = make_rules(mesh, cfg, par)
    opt_cfg = OptimizerConfig(lr=lr, total_steps=steps,
                              warmup_steps=max(10, steps // 20),
                              moment_dtype=par.moment_dtype)
    train_step = steps_lib.make_train_step(cfg, rules, par, opt_cfg)
    data = DataPipeline(cfg, shape, DataConfig(
        source="file" if data_path else "synthetic", path=data_path,
        seed=seed))
    return cfg, par, shape, rules, train_step, data, opt_cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--data", default="", help="text file (byte tokenizer)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg, par, shape, rules, train_step, data, opt_cfg = build_trainer(
        args.arch, reduced=args.reduced, seq=args.seq, batch=args.batch,
        steps=args.steps, data_path=args.data or None, seed=args.seed,
        lr=args.lr)

    n_params = zoo.param_count(cfg)
    print(f"arch={args.arch} reduced={args.reduced} params={n_params:,} "
          f"seq={args.seq} batch={args.batch}")

    template = zoo.param_template(cfg)
    params = params_lib.initialize(template, jax.random.PRNGKey(args.seed))
    opt_state = adamw_init(params, opt_cfg)
    start_step = 0

    ckpt = CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval) \
        if args.ckpt_dir else None
    if ckpt and args.resume and latest_step(args.ckpt_dir) is not None:
        tree = {"params": params, "opt": opt_state}
        tree, start_step = ckpt.restore_latest(tree)
        params, opt_state = tree["params"], tree["opt"]
        print(f"resumed from step {start_step}")

    jit_step = jax.jit(train_step, donate_argnums=(0, 1))
    detector = StragglerDetector(hosts=[0])
    losses = []
    t_last = time.time()
    with PreemptionGuard() as guard:
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in data.batch_at(step).items()}
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                losses.append((step, loss))
                dt = time.time() - t_last
                t_last = time.time()
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)",
                      flush=True)
            if ckpt:
                ckpt.maybe_save(step + 1, {"params": params, "opt": opt_state},
                                force=guard.requested)
            if guard.requested:
                print("preemption requested: checkpoint saved, exiting")
                break
    if ckpt:
        ckpt.wait()
    if len(losses) >= 2:
        print(f"loss {losses[0][1]:.4f} -> {losses[-1][1]:.4f} "
              f"({'improved' if losses[-1][1] < losses[0][1] else 'NOT improved'})")
    data.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
