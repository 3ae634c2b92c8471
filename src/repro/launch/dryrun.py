import os
import sys

from repro.launch.devices import enable_compile_cache, force_host_device_count


def _force_fake_devices(argv):
    """Set the XLA host device count BEFORE the jax import below.

    Pod meshes need 512 fake devices (override=True: the appended flag
    wins over any smaller env default); '--topology host' keeps a small
    live mesh (8, or whatever the environment already set) so compiled
    steps can also be *executed*.  CLI-only: importing this module as a
    library leaves the caller's device count alone.
    """
    topo = ""
    for i, a in enumerate(argv):
        if a == "--topology" and i + 1 < len(argv):
            topo = argv[i + 1]
        elif a.startswith("--topology="):
            topo = a.split("=", 1)[1]
    if topo == "host":
        force_host_device_count(8)
    else:
        force_host_device_count(512, override=True)


if __name__ == "__main__":          # before the jax import below
    _force_fake_devices(sys.argv)

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and record memory / FLOP / collective statistics.

This is the proof that the distribution config is coherent without real
hardware: a sharding mismatch, compile-time OOM, or unsupported collective
fails here.  Results feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi_pod]
  python -m repro.launch.dryrun ... --out results/dryrun
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import strategy as strategy_lib
from repro.configs import SHAPES, get_config, list_archs, supports_shape
from repro.core import parallel as par
from repro.launch import specs as specs_lib
from repro.models import transformer as tfm
from repro.optim import init_opt_state
from repro.perf import flops as flops_lib
from repro.perf.hlo import collective_stats
from repro.serve.engine import make_prefill, make_serve_step
from repro.train.trainer import TrainConfig, make_train_step

SDS = jax.ShapeDtypeStruct


def _to_dtype_sds(shapes, shardings, float_dtype):
    def one(s, sh):
        dt = float_dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype
        return SDS(s.shape, dt, sharding=sh)
    return jax.tree.map(one, shapes, shardings)


def _attach(shapes, shardings):
    return jax.tree.map(lambda s, sh: SDS(s.shape, s.dtype, sharding=sh),
                        shapes, shardings)


def resolve_strategy(cfg, shape, topo, strategy: str, dp_mode: str = "hsdp",
                     attn_override=None, seq_parallel: bool = True):
    """Map (--strategy, legacy flags) to a Strategy descriptor.

    '' (default) keeps the paper's pod layout — model axis 16 — with the
    legacy dp_mode/attn/sp flags folded in; 'auto' asks the planner;
    anything else is a spec string (legacy flags still apply on top unless
    the spec sets them itself).
    """
    if strategy == "auto":
        s, _ = strategy_lib.resolve("auto", cfg, topo, shape)
    elif not strategy:
        s = strategy_lib.Strategy(
            dp_mode="fsdp" if dp_mode == "fsdp2d" else "hsdp", tp=16)
    else:
        s = strategy_lib.parse(strategy)
    if attn_override and s.attn is None:
        s = dataclasses.replace(s, attn=attn_override)
    if not seq_parallel:
        s = dataclasses.replace(s, seq_parallel=False)
    if dp_mode == "fsdp2d" and s.dp_mode == "hsdp":
        s = dataclasses.replace(s, dp_mode="fsdp")
    return s


def _topology(name: str, multi_pod: bool):
    """'' keeps the legacy pod/multipod selection; 'host' is a live mesh."""
    if name:
        return strategy_lib.get_topology(name)
    return strategy_lib.pod_topology(pods=2 if multi_pod else 1)


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              dp_mode: str = "hsdp", attn_override=None, rt_overrides=None,
              donate: bool = False, seq_parallel: bool = True,
              grad_accum: int = 1, strategy: str = "",
              topology: str = "", use_reduced: bool = False):
    from repro.configs import reduced
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    shape = SHAPES[shape_name]
    topo = _topology(topology, multi_pod)
    strat = resolve_strategy(cfg, shape, topo, strategy, dp_mode,
                             attn_override, seq_parallel)
    plan = strat.to_plan(cfg, topo, shape)
    mesh = plan.mesh
    rt = par.make_runtime(cfg, plan, shape, **(rt_overrides or {}))

    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(functools.partial(tfm.init_params, cfg), key)
    pshard = par.param_shardings(cfg, plan, pshapes)
    # lower with the storage dtype the strategy's precision policy
    # actually trains with (previously hard-coded bf16 while train_loop
    # ran f32 — the compiled memory/collective stats described a program
    # nothing executed)
    params_sds = _to_dtype_sds(pshapes, pshard, rt.param_dtype)

    with par.use_mesh(mesh):
        if shape.mode == "train":
            batch = specs_lib.train_batch_specs(cfg, shape)
            bshard = par.batch_specs(cfg, plan, batch)
            batch_sds = _attach(batch, bshard)
            oshapes = jax.eval_shape(init_opt_state, params_sds)
            oshard = {"m": pshard, "v": pshard,
                      "step": par.fitted(plan, par.P(), ())}
            opt_sds = _attach(oshapes, oshard)
            # the ga<k> spec token wins unless --grad_accum was set explicitly
            # (train.py applies the same precedence)
            ga = grad_accum if grad_accum > 1 else strat.grad_accum
            step = make_train_step(cfg, rt, TrainConfig(grad_accum=ga))
            lowered = jax.jit(step, out_shardings=(pshard, oshard, None),
                              donate_argnums=(0, 1) if donate else ()) \
                .lower(params_sds, opt_sds, batch_sds)
        elif shape.mode == "prefill":
            batch = specs_lib.prefill_batch_specs(cfg, shape)
            bshard = par.batch_specs(cfg, plan, batch)
            batch_sds = _attach(batch, bshard)
            fn = make_prefill(cfg, rt, max_len=shape.seq_len)
            cshapes = jax.eval_shape(
                lambda: tfm.init_cache(cfg, shape.global_batch, shape.seq_len,
                                       rt.compute_dtype, par.make_runtime(
                                           cfg, plan, shape, constrain=None)))
            cshard = par.cache_shardings(cfg, plan, cshapes)
            lowered = jax.jit(fn, out_shardings=(None, cshard)) \
                .lower(params_sds, batch_sds)
        else:  # decode
            rt_nc = par.make_runtime(cfg, plan, shape, constrain=None)
            cshapes = jax.eval_shape(
                lambda: tfm.init_cache(cfg, shape.global_batch, shape.seq_len,
                                       rt.compute_dtype, rt_nc))
            cshard = par.cache_shardings(cfg, plan, cshapes)
            cache_sds = _attach(cshapes, cshard)
            tokens, pos = specs_lib.decode_token_specs(cfg, shape)
            tok_sds = SDS(tokens.shape, tokens.dtype,
                          sharding=par.fitted(plan, par.P(plan.dp, None),
                                              tokens.shape))
            pos_sds = SDS((), jnp.int32,
                          sharding=par.fitted(plan, par.P(), ()))
            step = make_serve_step(cfg, rt)
            lowered = jax.jit(step, out_shardings=(None, cshard)) \
                .lower(params_sds, cache_sds, tok_sds, pos_sds)
    return cfg, shape, strat, plan, lowered


def run_label(arch: str, shape_name: str, multi_pod: bool,
              strategy: str = "", tag: str = "", topology: str = ""):
    """(mesh_name, label) naming one sweep point — also its artifact path,
    so main()'s skip-if-existing check and run_one()'s writer must agree."""
    if topology:
        mesh_name = topology
    else:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if strategy:
        mesh_name += f"_{strategy}"
    label = f"{arch}_{shape_name}_{mesh_name}" + (f"_{tag}" if tag else "")
    return mesh_name, label


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            dp_mode: str = "hsdp", attn_override=None, tag: str = "",
            rt_overrides=None, donate: bool = False,
            seq_parallel: bool = True, grad_accum: int = 1,
            strategy: str = "", topology: str = "",
            use_reduced: bool = False, telemetry=None):
    from repro import telemetry as tel
    telemetry = telemetry if telemetry is not None else tel.NULL
    mesh_name, label = run_label(arch, shape_name, multi_pod, strategy, tag,
                                 topology)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not supports_shape(cfg, shape):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped",
               "reason": "requires sub-quadratic attention (DESIGN.md §4)"}
        _write(out_dir, label, rec)
        print(f"[dryrun] {label}: SKIP (full attention, long context)")
        return rec

    t0 = time.time()
    try:
        from repro.core.expert import dispatch_stats_snapshot
        stats0 = dispatch_stats_snapshot()
        with telemetry.span("dryrun/lower", label=label):
            cfg, shape, strat, plan, lowered = lower_one(
                arch, shape_name, multi_pod, dp_mode, attn_override,
                rt_overrides, donate, seq_parallel, grad_accum, strategy,
                topology, use_reduced)
        t_lower = time.time() - t0
        t0 = time.time()
        with telemetry.span("dryrun/compile", label=label):
            compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # trip-count-scaled: while bodies multiplied by known_trip_count
        coll = collective_stats(compiled.as_text())
        n_dev = plan.mesh.devices.size          # chips in THIS mesh
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "ok", "strategy": strat.format(),
            "strategy_arg": strategy or "legacy-default",
            "precision": strat.precision,
            "plan": {
                "attn": plan.attn, "kv_tp": plan.kv_tp, "dp": list(plan.dp),
                "fsdp": list(plan.fsdp), "expert": plan.expert,
                "mesh": {k: int(v) for k, v in plan.mesh.shape.items()},
                "decode_cache_axes": list(plan.decode_cache_axes)},
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "n_devices": n_dev,
            "flops_hlo_per_device_raw": cost.get("flops", 0.0),
            "bytes_accessed_per_device_raw": cost.get("bytes accessed", 0.0),
            "flops_compiled_analytic": flops_lib.compiled_flops(
                cfg, shape, remat=(shape.mode == "train")),
            "flops_forward_analytic": flops_lib.forward_flops(cfg, shape),
            "flops_model_6nd": flops_lib.model_flops(cfg, shape),
            "memory": {
                "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes_per_device": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
            },
            "collectives": coll,
            "collective_bytes_total": int(sum(v["bytes"] for v in coll.values())),
            "params_total": cfg.param_count(),
            "params_active": cfg.active_param_count(),
            "rt_overrides": {k: bool(v) if isinstance(v, bool) else v
                             for k, v in (rt_overrides or {}).items()
                             if not callable(v)},
            "donate": donate,
        }
        # resilience prediction: what the goodput model says failures cost
        # this exact (strategy, topology) point — system MTBF, the
        # strategy-aware checkpoint write time (distinct-writer
        # parallelism), the Young/Daly interval, and the effective
        # throughput fraction left after checkpoint stalls + lost work +
        # restarts
        from repro.core import costmodel as cm
        topo_res = _topology(topology, multi_pod)
        cost_strat = strat.to_cost_strategy(cfg, topo_res)
        hw = topo_res.hw
        t_ck = cm.checkpoint_write_time(cfg, hw, cost_strat)
        mtbf_sys = cm.system_mtbf(hw, cost_strat.n_devices)
        g = cm.goodput(t_ck, mtbf_sys,
                       t_restart=cm.restart_time(cfg, hw, cost_strat))
        rec["resilience"] = {
            "mtbf_device_s": hw.mtbf,
            "mtbf_system_s": round(mtbf_sys, 1),
            "ckpt_bytes": cm.checkpoint_bytes(cfg),
            "distinct_writers": cm.distinct_writers(cost_strat),
            "t_ckpt_s": round(t_ck, 4),
            "young_daly_interval_s": round(
                cm.young_daly_interval(t_ck, mtbf_sys), 1),
            "goodput": round(g, 5),
        }
        if cfg.moe.n_experts:
            # which EP entry this lowering's apply_moe calls actually took
            # (trace-time deltas): 'ep_padded_calls' means small token
            # counts ran the padded all-to-all, 'ep_fallback_calls' means
            # the plan's dispatch was NOT what lowered (GSPMD dropping)
            stats1 = dispatch_stats_snapshot()
            rec["moe_dispatch"] = {k: stats1[k] - stats0[k] for k in stats1}
        if strat.pp > 1:
            # pipeline section: the analytic per-schedule bubble,
            # in-flight activation count and sub-tick census
            from repro.core.pipeline import (bubble_fraction,
                                             inflight_microbatches,
                                             op_tick_counts,
                                             virtual_stages)
            rec["pipeline"] = {
                "pp": strat.pp, "microbatches": strat.microbatches,
                "sched": strat.sched,
                "virtual_stages": virtual_stages(strat.sched),
                "overlap": strat.overlap,
                "bubble_predicted": bubble_fraction(
                    strat.pp, strat.microbatches, strat.sched),
                "inflight_microbatches": inflight_microbatches(
                    strat.pp, strat.microbatches, strat.sched),
                # sub-tick census of the executed table (zb splits each
                # backward into dgrad 'B' + wgrad 'W' sub-ticks)
                "op_tick_counts": op_tick_counts(
                    strat.sched, strat.pp, strat.microbatches),
            }
        print(f"[dryrun] {label}: OK  compile {t_compile:.0f}s  "
              f"flops {rec['flops_compiled_analytic']:.3e}  "
              f"coll {rec['collective_bytes_total']:.3e}B  "
              f"temp/dev {rec['memory']['temp_bytes_per_device']/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "error", "error": repr(e),
               "traceback": traceback.format_exc()[-4000:]}
        print(f"[dryrun] {label}: FAIL {e!r}")
    _write(out_dir, label, rec)
    return rec


def _write(out_dir, label, rec):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, label + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi_pod", action="store_true")
    ap.add_argument("--both_meshes", action="store_true")
    ap.add_argument("--topology", default="",
                    help="'' = pod/multipod (512 fake devices); 'host' = "
                         "small live mesh (compiled steps can execute)")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of each arch")
    ap.add_argument("--strategy", default="",
                    help="'' = legacy pod layout (model axis 16), 'auto' = "
                         "planner, else a spec string like hsdp_tp4 / "
                         "fsdp_cp8")
    ap.add_argument("--dp_mode", default="hsdp", choices=["hsdp", "fsdp2d"])
    ap.add_argument("--attn", default=None, choices=[None, "head_tp", "context"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip_existing", action="store_true")
    # perf-iteration knobs (§Perf): each maps to a Runtime override
    ap.add_argument("--donate", action="store_true",
                    help="donate params+opt buffers to the step")
    ap.add_argument("--remat_inner", action="store_true",
                    help="checkpoint each layer inside scanned blocks")
    ap.add_argument("--gather_per_block", action="store_true",
                    help="force per-layer FSDP all-gather inside the scan")
    ap.add_argument("--mamba_chunk", type=int, default=0)
    ap.add_argument("--rwkv_chunk", type=int, default=0)
    ap.add_argument("--attn_kv_chunk", type=int, default=0)
    ap.add_argument("--attn_q_chunk", type=int, default=0)
    ap.add_argument("--no_sp", action="store_true",
                    help="disable sequence-parallel residual stream")
    ap.add_argument("--grad_accum", type=int, default=1)
    ap.add_argument("--kernels", default="", choices=["", "jnp", "pallas"],
                    help="attention/norm impl override ('' keeps Runtime "
                         "defaults)")
    ap.add_argument("--trace", default="",
                    help="write per-config lower/compile spans as a "
                         "Chrome-trace/Perfetto JSON here")
    args = ap.parse_args()
    enable_compile_cache()
    rt_overrides = {}
    if args.kernels:
        rt_overrides["attn_impl"] = args.kernels
        rt_overrides["norm_impl"] = args.kernels
    if args.remat_inner:
        rt_overrides["remat_inner"] = True
    if args.gather_per_block:
        rt_overrides["fsdp_gather_per_block"] = True
    for k in ("mamba_chunk", "rwkv_chunk", "attn_kv_chunk", "attn_q_chunk"):
        if getattr(args, k):
            rt_overrides[k] = getattr(args, k)

    archs = list_archs(assigned_only=True) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    if args.topology:
        # an explicit topology overrides the pod/multipod pair entirely —
        # looping both meshes would run the identical config twice
        meshes = [False]
    elif args.both_meshes:
        meshes = [False, True]
    else:
        meshes = [args.multi_pod]

    from repro import telemetry as tel
    recorder = tel.NULL
    if args.trace:
        recorder = tel.Recorder()
        recorder.add_sink(tel.ChromeTraceSink(args.trace,
                                              process_name="dryrun"))

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                _, label = run_label(arch, shape, mp, args.strategy,
                                     args.tag, args.topology)
                path = os.path.join(args.out, label + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            print(f"[dryrun] {label}: cached")
                            continue
                rec = run_one(arch, shape, mp, args.out, args.dp_mode,
                              args.attn, args.tag, rt_overrides, args.donate,
                              not args.no_sp, args.grad_accum, args.strategy,
                              args.topology, args.reduced,
                              telemetry=recorder)
                n_fail += rec["status"] == "error"
    recorder.close()
    if args.trace:
        print(f"[telemetry] trace written to {args.trace}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
