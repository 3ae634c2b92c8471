"""Serving driver: batched generation with prefill + KV-cache decode.

``python -m repro.launch.serve --arch qwen3-0.6b --reduced --n_new 32``

``--strategy`` routes through the unified strategy API: 'auto' asks the
planner (decode shape, throughput objective), a spec string such as
``fsdp_tp2`` lowers directly, and '' (default) keeps the single-device
path.  Sharded serving places params per the plan and wires the Runtime's
activation constraints, exactly like the dry-run's decode lowering.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import strategy as strategy_lib
from repro.configs import ShapeConfig, get_config, reduced
from repro.core import parallel as par
from repro.launch.devices import enable_compile_cache
from repro.models import Runtime, init_params
from repro.serve import ServeEngine


def single_device_runtime(kernels: str) -> Runtime:
    """The Runtime of the single-device serving path: 'auto' picks the
    dense MoE oracle for small token counts and the dropping dispatch
    above the threshold; ``kernels='pallas'`` runs flash-decode."""
    return Runtime(rwkv_chunk=16, mamba_chunk=32, moe_impl="auto",
                   attn_impl=kernels, norm_impl=kernels)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt_len", type=int, default=32)
    ap.add_argument("--n_new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--strategy", default="",
                    help="'' = single-device; 'auto' = planner; else a spec "
                         "string like fsdp_tp2")
    ap.add_argument("--topology", default="host",
                    help="host | pod | multipod[<k>]")
    ap.add_argument("--kernels", default="jnp", choices=["jnp", "pallas"],
                    help="attention/norm impl; with 'pallas' the paged "
                         "engine's decode segments run the flash-decode "
                         "kernel over the block pool")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "paged", "static"],
                    help="auto routes through the paged continuous-batching "
                         "path when it applies; static forces the dense-"
                         "cache per-token loop")
    ap.add_argument("--n_slots", type=int, default=8,
                    help="in-flight batch bound of the paged engine")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace/Perfetto JSON of engine "
                         "ticks/prefill/decode spans here")
    ap.add_argument("--metrics_jsonl", default="",
                    help="stream every telemetry event as JSONL here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    max_len = args.prompt_len + args.n_new
    key = jax.random.PRNGKey(args.seed)

    plan = None
    if args.strategy:
        topo = strategy_lib.get_topology(args.topology)
        shape = ShapeConfig("serve", max_len, args.batch, "decode")
        strat, planned = strategy_lib.resolve(args.strategy, cfg, topo, shape)
        plan = strat.to_plan(cfg, topo, shape)
        print(f"[strategy] {strat.format()} on {topo.name} "
              f"(mesh {dict(plan.mesh.shape)}, attn={plan.attn})")
        # moe_impl / moe_groups come from the resolved plan (make_runtime:
        # 'ep' when the plan has an expert axis, 'dropping' otherwise) —
        # the served model must run the same dispatch the plan shards for
        rt = par.make_runtime(cfg, plan, shape, remat=False,
                              rwkv_chunk=16, mamba_chunk=32,
                              attn_impl=args.kernels, norm_impl=args.kernels)
        params = init_params(cfg, key)
        pshard = par.param_shardings(
            cfg, plan, jax.eval_shape(lambda: params))
        params = jax.device_put(params, pshard)
    else:
        rt = single_device_runtime(args.kernels)
        params = init_params(cfg, key)
    from repro import telemetry as tel
    recorder = tel.NULL
    if args.trace or args.metrics_jsonl:
        recorder = tel.Recorder()
        if args.metrics_jsonl:
            recorder.add_sink(tel.JsonlSink(args.metrics_jsonl))
        if args.trace:
            recorder.add_sink(tel.ChromeTraceSink(
                args.trace, process_name=f"serve {cfg.name}"))
    engine = ServeEngine(cfg, params, rt, max_len=max_len, plan=plan,
                         seed=args.seed, n_slots=args.n_slots,
                         telemetry=recorder)
    if args.engine == "paged" and not engine.paged_ok:
        raise SystemExit("--engine paged needs a single-device plan and an "
                         "attention-only stack")
    use_paged = engine.paged_ok and args.engine != "static"

    prompts = jax.random.randint(key, (args.batch, args.prompt_len),
                                 0, cfg.vocab_size)
    t0 = time.time()
    if use_paged:
        out = engine.generate(prompts, args.n_new,
                              temperature=args.temperature, key=key)
    else:
        out = engine.generate_static(prompts, args.n_new,
                                     temperature=args.temperature, key=key)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.n_new} engine={'paged' if use_paged else 'static'}")
    print(f"generated {args.batch * args.n_new} tokens in {dt:.2f}s "
          f"({args.batch * args.n_new / dt:.1f} tok/s on "
          f"{jax.default_backend()})")
    print("first sequence tail:", out[0, -min(16, args.n_new):].tolist())
    if recorder is not tel.NULL:
        snap = recorder.metrics.snapshot()
        lat = snap.get("serve/token_latency_s")
        if lat and lat.get("count"):
            print(f"[telemetry] token latency p50 {lat['p50'] * 1e3:.2f}ms "
                  f"p99 {lat['p99'] * 1e3:.2f}ms over {lat['count']} tokens")
        ttft = snap.get("serve/ttft_s")
        if ttft and ttft.get("count"):
            print(f"[telemetry] ttft p50 {ttft['p50'] * 1e3:.2f}ms "
                  f"p99 {ttft['p99'] * 1e3:.2f}ms")
        recorder.close()
        if args.trace:
            print(f"[telemetry] trace written to {args.trace}")
    assert out.shape == (args.batch, args.prompt_len + args.n_new)


if __name__ == "__main__":
    main()
