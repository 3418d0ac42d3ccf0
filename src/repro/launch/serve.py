"""Serving driver: prefill + batched greedy decode with energy accounting,
plus recorded-trace replay through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \\
      --batch 4 --prompt-len 64 --gen 32

Replay a recorded (or synthesized) request trace instead:

  PYTHONPATH=src python -m repro.launch.serve --make-demo-trace /tmp/day.npz
  PYTHONPATH=src python -m repro.launch.serve --replay /tmp/day.npz
  PYTHONPATH=src python -m repro.launch.serve --replay /tmp/day.npz --executed
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.workload import ServeWorkload
from repro.config import ARCH_IDS, get_arch
from repro.models.frontend import enc_len_for
from repro.power.trace import TraceRecorder
from repro.runtime.steps import (grow_decode_cache, make_decode_step,
                                 make_prefill_step)


def _replay(args) -> None:
    """--replay: feed a RequestTrace through the analytic
    continuous-batching engine (optionally with executed token
    generation) and print the per-request serve report."""
    from repro.serve import (ContinuousBatchingEngine, ExecutedGroupRuntime,
                             RequestTrace, ServeCostModel)
    trace = RequestTrace.load(args.replay)
    print(f"[replay] {trace.n_requests} requests over "
          f"{trace.duration_s:.3g}s ({trace.meta.get('generator', '?')})")
    cost = ServeCostModel(args.arch, max_batch=args.batch,
                          prompt_len=args.prompt_len, gen=args.gen,
                          smoke=args.smoke, kv_int8=args.kv_int8)
    runtime = None
    if args.executed:
        runtime = ExecutedGroupRuntime(args.arch, smoke=args.smoke,
                                       kv_int8=args.kv_int8)
    engine = ContinuousBatchingEngine(cost, runtime=runtime)
    res = engine.replay(trace, slo_s=args.slo_s)
    print(f"[energy] decode dominant={res.plan.dominant} "
          f"freq={res.plan.freq_scale:.2f} power={res.plan.power_w:.0f}W")
    print("[replay]", res.stats.summary())
    done = [r for r in res.records if r.done_s is not None]
    if done:
        r = done[0]
        print(f"[replay] request {r.idx}: wait {r.wait_s:.3g}s "
              f"ttft {r.ttft_s:.3g}s latency {r.latency_s:.3g}s "
              f"{res.request_energy_j(r.idx):.3g} J")
        if r.tokens is not None:
            print("sample:", np.asarray(r.tokens)[:16])


def _make_demo_trace(args) -> None:
    """--make-demo-trace: write a seeded diurnal day scaled to this
    serve shape's analytic capacity."""
    from repro.serve import ServeCostModel, diurnal_trace
    cost = ServeCostModel(args.arch, max_batch=args.batch,
                          prompt_len=args.prompt_len, gen=args.gen,
                          smoke=args.smoke, kv_int8=args.kv_int8)
    plan, _, _ = cost.plan()
    t_pre, _ = cost.prefill_cost(args.prompt_len, args.batch)
    service_s = t_pre + args.gen * plan.step_time_s
    cap_rps = args.batch / service_s
    day = 512.0 * service_s
    tr = diurnal_trace(day, rate_peak_per_s=0.6 * cap_rps,
                       rate_floor_per_s=0.05 * cap_rps,
                       prompt_lens=(args.prompt_len,),
                       gen_lens=(args.gen,), seed=0)
    tr.save(args.make_demo_trace)
    print(f"[trace] wrote {tr.n_requests} requests over {day:.3g}s "
          f"to {args.make_demo_trace}")


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--replay", metavar="PATH", default=None,
                    help="replay a RequestTrace npz through the "
                         "continuous-batching engine instead of one "
                         "batched generation")
    ap.add_argument("--executed", action="store_true",
                    help="with --replay: run real jitted prefill/decode "
                         "per admitted group (tokens become real; timing "
                         "stays analytic)")
    ap.add_argument("--slo-s", type=float, default=None,
                    help="with --replay: p99 latency SLO for the "
                         "compliance report")
    ap.add_argument("--make-demo-trace", metavar="PATH", default=None,
                    help="write a seeded diurnal demo trace npz sized to "
                         "this serve shape, then exit")
    args = ap.parse_args()

    if args.make_demo_trace:
        _make_demo_trace(args)
        return
    if args.replay:
        _replay(args)
        return

    entry = get_arch(args.arch)
    cfg = entry.smoke() if args.smoke else entry.full()
    B, S = args.batch, args.prompt_len
    total = S + args.gen

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    if cfg.family == "vlm":
        n_p = cfg.n_patches
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, n_p, cfg.d_model)), jnp.bfloat16)
    elif cfg.family == "encdec":
        batch["frame_embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, enc_len_for(cfg, S), cfg.d_model)),
            jnp.bfloat16)

    from repro.models import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))

    prefill = jax.jit(make_prefill_step(
        cfg, quantize_kv_cache=args.kv_int8))
    decode = jax.jit(make_decode_step(cfg))

    # energy plan (decode is memory-bound -> deep clock derate, paper C5),
    # built through the unified Workload adapter (repro.cluster) so the
    # driver and the cluster scheduler share one definition; ac is the
    # per-decode-step cost, ac_prefill the prefill-shape cost
    workload = ServeWorkload(arch=args.arch, batch=B, prompt_len=S,
                             gen=args.gen, smoke=args.smoke,
                             kv_int8=args.kv_int8)
    plan, ac_prefill, ac = workload.energy_plan()
    print(f"[energy] decode dominant={plan.dominant} "
          f"freq={plan.freq_scale:.2f} power={plan.power_w:.0f}W")
    # telemetry bus: prefill + every decoded token emit chip samples
    recorder = TraceRecorder(source="launch.serve")
    recorder.emit(0.0, {"chip": plan.power_w}, flops_rate=0.0,
                  freq_scale=plan.freq_scale)

    t0 = time.time()
    logits, cache = prefill(params, batch)
    # grow the cache to the full generation length
    cache = grow_decode_cache(cfg, cache, B, total,
                              quantize_kv_cache=args.kv_int8)
    t_prefill = time.time() - t0
    recorder.emit(t_prefill, {"chip": plan.power_w},
                  flops_rate=ac_prefill.flops / max(t_prefill, 1e-9) / 1e9,
                  freq_scale=plan.freq_scale)
    print(f"prefill {S} tokens x {B}: {t_prefill:.2f}s")

    out_tokens = []
    tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)[:, None]
    t0 = time.time()
    for _ in range(args.gen):
        out_tokens.append(np.asarray(tok))
        logits, cache = decode(params, tok.astype(jnp.int32), cache)
        tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)[:, None]
    jax.block_until_ready(logits)
    dt = time.time() - t0
    recorder.emit(t_prefill + dt, {"chip": plan.power_w},
                  flops_rate=ac.flops * args.gen / max(dt, 1e-9) / 1e9,
                  freq_scale=plan.freq_scale)
    gen = np.concatenate(out_tokens, axis=1)
    trace = recorder.trace()
    print(f"decoded {args.gen} tokens x {B} in {dt:.2f}s "
          f"({args.gen*B/dt:.1f} tok/s)")
    # split the bus energy at the prefill/decode boundary and divide by
    # the tokens each phase actually processed (B·S prompt tokens through
    # prefill, B·gen generated tokens through decode) — the old print
    # billed everything to generated tokens only
    e_pre = trace.energy_j(0.0, t_prefill)
    e_dec = trace.energy_j(t_prefill, t_prefill + dt)
    n_pre = B * S
    n_dec = B * args.gen
    print(f"[energy] prefill {e_pre:.1f} J / {n_pre} prompt tokens "
          f"= {e_pre / max(n_pre, 1):.3f} J/token")
    print(f"[energy] decode  {e_dec:.1f} J / {n_dec} generated tokens "
          f"= {e_dec / max(n_dec, 1):.3f} J/token")
    print(f"[energy] total   {trace.energy_j():.1f} J over "
          f"{trace.duration:.2f}s "
          f"({trace.energy_j() / max(n_pre + n_dec, 1):.3f} J/token over "
          f"all processed tokens)")
    print("sample:", gen[0][:16])


if __name__ == "__main__":
    main()
