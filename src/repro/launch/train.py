"""Training driver: data pipeline -> jitted train step -> checkpoints,
with energy accounting (the paper's technique) and fault tolerance.

CPU-scale by default (smoke config); the full configs run through the same
code path on a real mesh.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \\
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.cluster.workload import TrainWorkload
from repro.config import ARCH_IDS, ShapeConfig, TrainConfig, get_arch
from repro.data import make_batch_iterator
from repro.distributed.fault import FaultPolicy, FaultTolerantLoop
from repro.models import init_params
from repro.optim import adamw_init
from repro.power.trace import TraceRecorder
from repro.runtime.steps import make_train_step


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    entry = get_arch(args.arch)
    cfg = entry.smoke() if args.smoke else entry.full()
    shape = ShapeConfig("custom", args.seq, args.batch, "train")
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1), remat="none")

    key = jax.random.PRNGKey(tc.seed)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    step_fn = jax.jit(make_train_step(cfg, tc))
    data = make_batch_iterator(cfg, shape)
    ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name)
    loop = FaultTolerantLoop(FaultPolicy(checkpoint_every=args.ckpt_every))

    # energy plan for this step shape (paper C5): roofline-coupled clock,
    # built through the unified Workload adapter (repro.cluster) so the
    # driver and the cluster scheduler share one definition
    workload = TrainWorkload(arch=args.arch, steps=args.steps,
                             batch=args.batch, seq=args.seq,
                             smoke=args.smoke)
    plan, ac = workload.energy_plan()
    print(f"[energy] dominant={plan.dominant} freq={plan.freq_scale:.2f} "
          f"power={plan.power_w:.0f}W perf_loss={plan.perf_loss:.3%}")

    # telemetry: each step emits a chip-power sample into the shared bus
    # (energy comes from integrating the trace, not a private W×s product)
    recorder = TraceRecorder(source="launch.train")
    recorder.emit(0.0, {"chip": plan.power_w}, flops_rate=0.0,
                  freq_scale=plan.freq_scale)
    t_run = 0.0
    last_good = None
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        t0 = time.time()
        new_params, new_opt, metrics = step_fn(params, opt, batch)
        loss = float(metrics["loss"])
        wall = time.time() - t0
        h = loop.observe(step, wall, loss)
        t_run += wall
        recorder.emit(t_run, {"chip": plan.power_w},
                      flops_rate=ac.flops / max(wall, 1e-9) / 1e9,
                      freq_scale=plan.freq_scale)
        if not h.ok and loop.should_rollback(h):
            print(f"[fault] step {step}: {h.reason}; rolling back")
            if last_good is not None:
                params, opt = last_good
            continue
        params, opt = new_params, new_opt
        if step % args.ckpt_every == 0:
            ckpt.save(step, params)
            last_good = (params, opt)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"wall {wall*1e3:7.1f}ms gnorm "
                  f"{float(metrics['grad_norm']):.3f}")
    ckpt.wait()
    trace = recorder.trace()
    print(f"[energy] total {trace.energy_j()/3600:.4f} Wh over "
          f"{args.steps} steps, avg {trace.avg_power():.0f}W "
          f"({loop.straggler_report()})")


if __name__ == "__main__":
    main()
