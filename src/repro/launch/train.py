"""Training driver: ``python -m repro.launch.train --arch qwen3-0.6b ...``

Runs real training on whatever devices exist: virtual CPU devices for
tests, or the TPU chips of one host (``chip_smoke.py`` drives this path on
a v5e).  The compile cache follows ``launch.devices.enable_compile_cache``.

Strategy selection goes through the unified API (``repro.strategy``):

  --strategy auto        planner picks the best executable strategy for
                         (arch, topology, batch) with the calibrated cost
                         model (throughput objective by default)
  --strategy hsdp_tp4    explicit spec string, lowered directly

On a CPU host, ``--host_devices`` (default 8) forces that many fake XLA
host devices so multi-axis strategies exercise the real SPMD path; it is a
no-op on real accelerators.
"""
from __future__ import annotations

import argparse
import os
import sys

from repro.launch.devices import enable_compile_cache, force_host_device_count


def _force_host_devices(argv):
    """Set XLA host device count BEFORE jax import (CPU-only effect)."""
    n = "8"
    for i, a in enumerate(argv):
        if a == "--host_devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif a.startswith("--host_devices="):
            n = a.split("=", 1)[1]
    try:
        count = int(n)
    except ValueError:
        return                    # let argparse report the bad value
    if count > 0:
        force_host_device_count(count)


if __name__ == "__main__":          # before jax import below
    _force_host_devices(sys.argv)

import jax
import jax.numpy as jnp

from repro import strategy as strategy_lib
from repro.configs import ShapeConfig, get_config, reduced
from repro.core import parallel as par
from repro.data import Batcher, BinTokenSource, SyntheticSource
from repro.optim import AdamWConfig
from repro.train.trainer import TrainConfig, train_loop


def runtime_overrides(kernels: str, seq_len: int) -> dict:
    """The Runtime knobs this entry point trains with, on top of what the
    plan's ``make_runtime`` derives (dtypes, constraints, pipeline)."""
    return dict(remat=False, rwkv_chunk=32, mamba_chunk=64,
                attn_impl=kernels, norm_impl=kernels,
                attn_min_chunked_len=max(2048, seq_len + 1)
                if seq_len <= 2048 else 2048)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq_len", type=int, default=512)
    ap.add_argument("--global_batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad_accum", type=int, default=0,
                    help="0 -> take it from the strategy spec (ga<k>)")
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' or a path to a flat uint16 token file")
    ap.add_argument("--ckpt_dir", default="")
    ap.add_argument("--ckpt_every", type=int, default=0)
    ap.add_argument("--log_every", type=int, default=10)
    ap.add_argument("--topology", "--mesh", dest="topology", default="host",
                    help="host | pod | multipod[<k>] (pod meshes need real "
                         "chips)")
    ap.add_argument("--strategy", default="auto",
                    help="'auto' (planner) or a spec string like hsdp_tp4 / "
                         "fsdp_cp2 / fsdp_pp2_mb8_1f1b / fsdp_pp2_ep2_mb2 / "
                         "ddp")
    ap.add_argument("--objective", default="wps",
                    choices=sorted(strategy_lib.OBJECTIVES))
    ap.add_argument("--host_devices", type=int, default=8,
                    help="fake XLA host devices on CPU (0 = leave alone)")
    ap.add_argument("--kernels", default="jnp", choices=["jnp", "pallas"],
                    help="attention/norm impl: 'pallas' runs the fwd+bwd "
                         "Pallas kernels (interpret mode off-TPU)")
    ap.add_argument("--seed", type=int, default=0)
    # resilience: supervised restarts, fault injection, async checkpointing
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint in ckpt_dir")
    ap.add_argument("--async_ckpt", action="store_true",
                    help="snapshot on-thread, write checkpoints in background")
    ap.add_argument("--ckpt_keep", type=int, default=0,
                    help="gc all but the newest N checkpoints (0 = keep all)")
    ap.add_argument("--max_restarts", type=int, default=0,
                    help="supervise the run: restart up to N times on "
                         "failure, restoring from the latest valid ckpt")
    ap.add_argument("--fault_plan", default="",
                    help="inject faults: 'crash@<step>[,..]' or a FaultPlan "
                         "JSON path")
    ap.add_argument("--event_log", default="",
                    help="write the supervisor's structured event log here")
    # observability: spans + metrics to pluggable sinks (see README
    # "Observability"); all three default off and cost nothing when off
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run's "
                         "spans here (open at ui.perfetto.dev)")
    ap.add_argument("--metrics_jsonl", default="",
                    help="stream every telemetry event (spans, counters, "
                         "gauges, histograms) as JSONL here")
    ap.add_argument("--drift_report", default="",
                    help="write per-window predicted-vs-measured step-time "
                         "drift (cost model vs telemetry spans) here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    topo = strategy_lib.get_topology(args.topology)
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    strat, planned = strategy_lib.resolve(args.strategy, cfg, topo, shape,
                                          objective=args.objective)
    plan = strat.to_plan(cfg, topo, shape)
    if planned is not None:
        r = planned.report
        print(f"[planner] chose {strat.format()} on {topo.name} "
              f"({topo.n_devices}x {topo.hardware}): predicted "
              f"{r.wps:,.0f} tok/s, mfu {r.mfu:.3f}, "
              f"{r.memory_per_device / 2**30:.2f} GiB/dev")
    else:
        print(f"[strategy] {strat.format()} on {topo.name} "
              f"(mesh {dict(plan.mesh.shape)})")

    # dtypes come from the strategy's precision policy (plan.policy): the
    # default/_f32 spec keeps pure f32, a _bf16 spec trains bf16 with f32
    # master params, _fp8 additionally quantizes the ZeRO gather wire
    rt_overrides = runtime_overrides(args.kernels, args.seq_len)
    rt = par.make_runtime(cfg, plan, shape, **rt_overrides)

    def make_batches():
        # fresh per attempt: sources are stateful; a resumed attempt
        # replays the stream and skips to the restored position
        if args.data == "synthetic":
            src = SyntheticSource(cfg.vocab_size, seed=args.seed)
        else:
            src = BinTokenSource(args.data)
        return Batcher(src, args.seq_len, args.global_batch)

    grad_accum = args.grad_accum or strat.grad_accum
    tc = TrainConfig(steps=args.steps, warmup=max(args.steps // 20, 1),
                     log_every=args.log_every, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir or os.path.join("results", "ckpt",
                                                            cfg.name),
                     grad_accum=grad_accum,
                     opt=AdamWConfig(lr=args.lr),
                     ckpt_async=args.async_ckpt, ckpt_keep=args.ckpt_keep,
                     resume=args.resume)

    fault_plan = None
    if args.fault_plan:
        from repro.resilience import load_fault_plan
        fault_plan = load_fault_plan(args.fault_plan)

    from repro import telemetry as tel
    recorder = tel.NULL
    if args.trace or args.metrics_jsonl or args.drift_report:
        recorder = tel.Recorder()
        if args.metrics_jsonl:
            recorder.add_sink(tel.JsonlSink(args.metrics_jsonl))
        if args.trace:
            recorder.add_sink(tel.ChromeTraceSink(
                args.trace, process_name=f"train {cfg.name}"))
    drift = None
    if args.drift_report:
        # predicted side: the cost model's decomposition for the resolved
        # strategy; measured side arrives from train_loop's log windows
        report = planned.report if planned is not None else \
            strategy_lib.evaluate(cfg, strat, topo, shape)
        hw = topo.hw
        drift = tel.DriftMonitor(
            report.decomposition(), telemetry=recorder,
            meta={"spec": strat.format(), "topology": topo.name,
                  "hardware": topo.hardware, "arch": cfg.name,
                  "seq_len": args.seq_len,
                  "global_batch": args.global_batch,
                  # invert mfu = model_flops / (t_step * n * peak) so the
                  # trainer can gauge measured MFU without re-deriving
                  "model_flops_per_step":
                      report.mfu * report.t_step
                      * topo.n_devices * hw.flops_bf16,
                  "cluster_peak_flops":
                      topo.n_devices * hw.flops_bf16})

    if args.max_restarts > 0:
        from repro.resilience.supervisor import (SupervisorConfig,
                                                 supervise_training)
        params, opt_state, history, sup = supervise_training(
            cfg, strat, topo, shape, tc, make_batches,
            rt_overrides=rt_overrides, key=jax.random.PRNGKey(args.seed),
            fault_plan=fault_plan,
            sup_cfg=SupervisorConfig(max_restarts=args.max_restarts,
                                     event_log_path=args.event_log),
            telemetry=recorder, drift=drift)
        n_failures = sum(e["kind"] == "failure" for e in sup.events)
        if n_failures:
            print(f"[supervisor] recovered from {n_failures} failure(s)"
                  + (f"; event log: {args.event_log}" if args.event_log
                     else ""))
    else:
        params, opt_state, history = train_loop(
            cfg, plan, rt, tc, make_batches(),
            key=jax.random.PRNGKey(args.seed), fault_plan=fault_plan,
            telemetry=recorder, drift=drift)
    recorder.close()
    if args.trace:
        print(f"[telemetry] trace written to {args.trace}")
    if args.drift_report and drift is not None:
        drift.write(args.drift_report)
        mean = drift.summary()["mean_predicted_over_measured"]
        terms = ", ".join(f"{t}={r:.3g}" for t, r in mean.items())
        print(f"[telemetry] drift report -> {args.drift_report}"
              + (f" (predicted/measured: {terms})" if terms else ""))
    losses = [h["loss"] for h in history]
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {args.steps} steps")
    return history


if __name__ == "__main__":
    main()
