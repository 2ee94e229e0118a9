"""End-to-end training driver with fault tolerance.

Features (deliverable b's end-to-end example uses this on CPU; the same
driver lowers unchanged onto the production meshes):

* auto-resume: restores the latest committed checkpoint (params, opt
  state, step) — the data pipeline is step-indexed so replay is exact;
* atomic checkpoints every ``--ckpt-every`` steps (+ final);
* straggler watchdog: per-step wall-times tracked against a rolling
  median; slow steps are flagged (on a real pod this feeds the
  reschedule/elastic controller — here it logs and records);
* elastic restore: ``--mesh debug`` restores checkpoints written on any
  other device count (tests/test_distributed.py exercises 1 -> 8 devices);
* NaN sentry: a non-finite loss aborts before the checkpoint can be
  poisoned (restart resumes from the last good step).

Usage (CPU, ~100M model, a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
      --steps 300 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke
from repro.data.pipeline import DataConfig, synth_batch
from repro.distributed import checkpoint as ckpt
from repro.distributed import sharding as shd
from repro.launch.mesh import make_debug_mesh, make_production_mesh, \
    rules_for
from repro.models import transformer as tf
from repro.optim.optimizers import (OptimizerConfig, cosine_schedule,
                                    make_optimizer)
from repro.train.train_step import make_train_step


@dataclasses.dataclass
class Watchdog:
    """Flags steps slower than ``threshold`` x rolling median."""

    threshold: float = 2.0
    window: int = 32
    times: list = dataclasses.field(default_factory=list)
    flagged: list = dataclasses.field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        hist = self.times[-self.window:]
        slow = bool(hist) and len(hist) >= 8 and \
            dt > self.threshold * statistics.median(hist)
        self.times.append(dt)
        if slow:
            self.flagged.append((step, dt))
        return slow


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced ~100M-class)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--mesh", choices=["none", "debug", "single", "multi"],
                    default="none")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke(cfg, d_model=256, n_super=2, vocab=2048)
        cfg = dataclasses.replace(cfg, remat=False)
    if args.seq and cfg.ssm is not None and args.seq % cfg.ssm.chunk:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm,
                                         chunk=min(cfg.ssm.chunk,
                                                   args.seq)))

    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    opt = make_optimizer(
        OptimizerConfig(lr=args.lr),
        cosine_schedule(args.lr, warmup=20, total=args.steps))
    step_fn = make_train_step(cfg, opt, microbatches=args.microbatches)

    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = opt.init(params)
    start_step = 0

    p_sh = o_sh = None
    if mesh is not None:
        rules = rules_for(cfg, mesh, global_batch=args.batch)
        p_sh = shd.tree_shardings(params, mesh, rules)
        o_sh = shd.tree_shardings(opt_state, mesh, rules)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None, None),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        ctx = shd.use_sharding(mesh, rules)
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        import contextlib
        ctx = contextlib.nullcontext()

    # ---- auto-resume ----------------------------------------------------
    if args.ckpt_dir:
        got = ckpt.restore_latest(
            args.ckpt_dir, {"params": params, "opt": opt_state},
            {"params": p_sh, "opt": o_sh} if p_sh is not None else None)
        if got is not None:
            start_step, tree, _ = got
            params, opt_state = tree["params"], tree["opt"]
            print(f"[train] resumed from step {start_step}")

    dcfg = DataConfig()
    wd = Watchdog()
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)
                   if hasattr(p, "shape"))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    with ctx:
        t_start = time.time()
        for step in range(start_step, args.steps):
            batch_np = synth_batch(cfg, dcfg, step, args.batch, args.seq)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            t0 = time.time()
            params, opt_state, metrics = jitted(
                params, opt_state, jnp.asarray(step, jnp.int32), batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            if not np.isfinite(loss):
                raise RuntimeError(
                    f"non-finite loss at step {step}; restart resumes "
                    f"from the last committed checkpoint")
            if wd.observe(step, dt):
                print(f"[watchdog] step {step} straggled: {dt:.2f}s")
            if step % args.log_every == 0 or step == args.steps - 1:
                tput = args.batch * args.seq / dt
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"{dt * 1e3:6.0f} ms  {tput:9.0f} tok/s")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, step + 1,
                          {"params": params, "opt": opt_state},
                          extra={"arch": cfg.name})
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, args.steps,
                      {"params": params, "opt": opt_state},
                      extra={"arch": cfg.name})
    total = time.time() - t_start
    print(f"[train] done: {args.steps - start_step} steps in {total:.0f}s;"
          f" {len(wd.flagged)} straggler flags")
    return params


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
