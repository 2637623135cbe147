"""End-to-end training driver with fault tolerance.

CPU-scale by default (reduced configs); the same loop drives the
production mesh on real hardware.  Features exercised here and asserted in
tests/examples:

* deterministic data keyed by (seed, step, shard) -> exact resume;
* checkpoint/restart: async atomic checkpoints + retention; on start,
  auto-resume from the newest checkpoint;
* straggler watchdog + heartbeat monitor wired into the step loop
  (simulated hosts on CPU);
* before-execute-time AT: layout plan + microbatching chosen by
  tuning/static.py before the first step (the paper's phase ordering:
  install -> static -> run).

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --steps 50 --reduced --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from .. import checkpoint
from ..configs import get_arch
from ..data import DataConfig, batch_for_step
from ..distributed.fault_tolerance import (HeartbeatMonitor,
                                           StragglerWatchdog)
from ..models import LayoutPlan, build_model
from ..optim import adamw
from . import enable_compile_cache
from .steps import build_train_step


def train(arch: str = "deepseek-7b", steps: int = 20, reduced: bool = True,
          ckpt_dir: str | None = None, ckpt_every: int = 10,
          seq_len: int = 64, batch: int = 8, lr: float = 3e-4,
          remat: str = "none", num_microbatches: int = 1,
          log_every: int = 5, seed: int = 0,
          run_steps: int | None = None, autotune: bool = False,
          tune_shape: str = "train_4k") -> dict:
    """``steps`` fixes the schedule horizon; ``run_steps`` optionally stops
    this invocation early (simulated preemption for restart tests).

    ``autotune=True`` runs before-execute-time AT (paper phase ordering:
    install -> static -> run) through a ``repro.at`` session before the
    first step: the production-mesh layout plan for ``(arch, tune_shape)``
    is selected on the roofline estimate and persisted in the session
    record store under ``ckpt_dir`` (or cwd), so later launches of the
    same cell skip the selection.
    """
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    tuned_plan = None
    if autotune:
        from .. import at
        from ..tuning import tune_layout
        session = at.AutoTuner(ckpt_dir or ".")
        tuned_plan = tune_layout(session, arch, tune_shape)
        print(f"[train] static AT: layout plan for ({arch}, {tune_shape}) "
              f"-> {tuned_plan!r}")
    plan = LayoutPlan(name="host", remat=remat,
                      num_microbatches=num_microbatches)
    opt_cfg = adamw.AdamWConfig(lr=lr, warmup_steps=max(2, steps // 10),
                                total_steps=steps)
    step_fn = jax.jit(build_train_step(model, plan, opt_cfg))

    dcfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len, global_batch=batch,
        seed=seed,
        frontend_seq=cfg.frontend_seq if cfg.frontend != "none"
        or cfg.is_encoder_decoder else 0,
        d_model=cfg.d_model)

    start_step = 0
    params = None
    opt_state = None
    ckptr = checkpoint.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    if ckpt_dir and checkpoint.latest_step(ckpt_dir) is not None:
        abstract = jax.eval_shape(
            lambda: {"params": model.init(jax.random.PRNGKey(seed)),
                     "opt": adamw.init(model.init(
                         jax.random.PRNGKey(seed)))})
        restored, meta = checkpoint.restore(ckpt_dir, abstract)
        params, opt_state = restored["params"], restored["opt"]
        start_step = int(meta["step"]) + 1
        print(f"[train] resumed from step {meta['step']}")
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
        opt_state = adamw.init(params)

    hb = HeartbeatMonitor(n_hosts=1, timeout_s=600)
    watchdog = StragglerWatchdog(n_hosts=1)
    losses = []
    t_start = time.time()
    end_step = steps if run_steps is None else min(steps,
                                                   start_step + run_steps)
    for step in range(start_step, end_step):
        t0 = time.time()
        batch_data = batch_for_step(dcfg, step)
        params, opt_state, metrics = step_fn(params, opt_state, batch_data)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.time() - t0
        hb.beat(0)
        watchdog.observe(0, dt)
        if step % log_every == 0 or step == end_step - 1:
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):7.3f} "
                  f"lr {float(metrics['lr']):.2e} {dt:5.2f}s")
        if ckptr and (step + 1) % ckpt_every == 0:
            ckptr.save(step, {"params": params, "opt": opt_state},
                       extra={"arch": cfg.name})
    if ckptr:
        ckptr.save(end_step - 1, {"params": params, "opt": opt_state},
                   extra={"arch": cfg.name})
        ckptr.wait()
    wall = time.time() - t_start
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "steps": end_step - start_step, "wall_s": wall,
            "params": params, "opt_state": opt_state,
            "tuned_plan": tuned_plan}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--autotune", action="store_true",
                    help="static-AT layout selection before step 0")
    ap.add_argument("--tune-shape", default="train_4k")
    args = ap.parse_args()
    enable_compile_cache()
    out = train(arch=args.arch, steps=args.steps, reduced=args.reduced,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                seq_len=args.seq_len, batch=args.batch, lr=args.lr,
                remat=args.remat, num_microbatches=args.microbatches,
                autotune=args.autotune, tune_shape=args.tune_shape)
    print(f"[train] done: {out['steps']} steps, final loss "
          f"{out['final_loss']:.4f}, {out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
