"""Benchmark harness: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines per benchmark (the
harness contract), writes full per-figure CSVs to results/benchmarks/, and
validates the paper-claim anchors at the end.

Also includes microbenchmarks of the real compute paths (blocked attention,
WKV chunked scan, MoE dispatch) on CPU — wall-time there is a correctness/
regression signal, not a TPU performance claim.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import time

import numpy as np


def _figure_benchmarks():
    from benchmarks.figures import ALL
    os.makedirs("results/benchmarks", exist_ok=True)
    summary = []
    for name, fn in ALL.items():
        t0 = time.perf_counter()
        header, rows = fn()
        dt_us = (time.perf_counter() - t0) * 1e6
        path = f"results/benchmarks/{name}.csv"
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(header)
            w.writerows(rows)
        summary.append((name, dt_us, f"{len(rows)}rows:{path}"))
    return summary


def _micro_benchmarks():
    import jax
    import jax.numpy as jnp
    from repro.models.attention import _attend_blocked
    from repro.models.rwkv6 import wkv_chunked
    from repro.models.layers import Runtime
    key = jax.random.PRNGKey(0)
    out = []

    def timeit(name, fn, *args, n=3, derived=""):
        fn(*args)                      # compile
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        out.append((name, (time.perf_counter() - t0) / n * 1e6, derived))

    # independent keys per tensor: correlated q/k/v make softmax rows
    # degenerate (one dominant logit) and flatter the timings
    kq, kk_, kv_, kr, kw, ku, kx = jax.random.split(key, 7)
    q = jax.random.normal(kq, (2, 1024, 4, 64))
    k = jax.random.normal(kk_, (2, 1024, 2, 64))
    v = jax.random.normal(kv_, (2, 1024, 2, 64))
    f = jax.jit(lambda q, k, v: _attend_blocked(q, k, v, 0, 0.125, 256, 256))
    timeit("micro_blocked_attention_1k", f, q, k, v,
           derived="B2S1024H4GQA2D64_cpu")

    r = jax.random.normal(kr, (2, 512, 4, 64)) * 0.5
    kk = jax.random.normal(kk_, (2, 512, 4, 64)) * 0.5
    vv = jax.random.normal(kv_, (2, 512, 4, 64)) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(kw, (2, 512, 4, 64)) - 2.5))
    u = jax.random.normal(ku, (4, 64)) * 0.3
    s0 = jnp.zeros((2, 4, 64, 64))
    g = jax.jit(lambda *a: wkv_chunked(*a, 64))
    timeit("micro_wkv6_chunked_512", g, r, kk, vv, w, u, s0,
           derived="B2T512H4N64_cpu")

    from repro.models import moe as moe_lib
    from repro.configs import get_config, reduced
    cfg = reduced(get_config("deepseek-moe-16b"))
    p = moe_lib.init_moe(cfg, key)
    x = jax.random.normal(kx, (4, 128, cfg.d_model))
    rt = Runtime(moe_impl="dropping", moe_groups=4)
    h = jax.jit(lambda x: moe_lib.apply_moe(cfg, p, x, rt)[0])
    timeit("micro_moe_dispatch", h, x, derived="T512E4k2_cpu")
    return out


# (name, dims) sweep for the kernel fwd / fwd+bwd microbenchmarks: varies
# sequence length, head dim, GQA ratio, and sliding window
KERNEL_SHAPES = [
    ("mha_s256_d64", dict(B=1, S=256, H=4, Kv=4, D=64, window=0)),
    ("gqa4_s512_d64", dict(B=1, S=512, H=8, Kv=2, D=64, window=0)),
    ("mha_s256_d128", dict(B=1, S=256, H=4, Kv=4, D=128, window=0)),
    ("swa128_s512_d64", dict(B=1, S=512, H=4, Kv=2, D=64, window=128)),
]
NORM_SHAPES = [
    ("rows2048_d256", (2048, 256)),
    ("rows4096_d1024", (4096, 1024)),
]


def _kernel_microbenchmarks(out_path: str = "results/benchmarks/BENCH_kernels.json",
                            n_iter: int = 3):
    """Time fwd and fwd+bwd of the attention/rmsnorm hot path for both impls
    (pure-jnp fallback vs Pallas kernels; interpret mode off-TPU) and write
    the perf-trajectory artifact BENCH_kernels.json."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kernel_ops
    from repro.kernels import ref
    from repro.models.attention import _attend_blocked

    def bench(fn, *args):
        fn(*args)                                  # compile / first trace
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n_iter):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / n_iter * 1e6

    records, summary = [], []
    for idx, (name, sh) in enumerate(KERNEL_SHAPES):
        B, S, H, Kv, D, w = (sh[k] for k in ("B", "S", "H", "Kv", "D",
                                             "window"))
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(idx), 3)
        q = jax.random.normal(kq, (B, S, H, D))
        k = jax.random.normal(kk, (B, S, Kv, D))
        v = jax.random.normal(kv, (B, S, Kv, D))
        impls = {
            "jnp": jax.jit(lambda q, k, v, w=w, D=D: _attend_blocked(
                q, k, v, w, D ** -0.5, 128, 128)),
            "pallas": jax.jit(lambda q, k, v, w=w: kernel_ops.attention(
                q, k, v, window=w, block_q=128, block_kv=128)),
        }
        for impl, fwd in impls.items():
            fwd_bwd = jax.jit(jax.grad(
                lambda q, k, v, fwd=fwd: jnp.sum(fwd(q, k, v)),
                argnums=(0, 1, 2)))
            t_fwd = bench(fwd, q, k, v)
            t_bwd = bench(fwd_bwd, q, k, v)
            records.append({"kernel": "attention", "shape": name, **sh,
                            "impl": impl, "fwd_us": round(t_fwd, 1),
                            "fwd_bwd_us": round(t_bwd, 1)})
            summary.append((f"kern_attn_{name}_{impl}", t_fwd,
                            f"fwdbwd{t_bwd:.0f}us"))
    for idx, (name, (n, d)) in enumerate(NORM_SHAPES):
        kx, ks = jax.random.split(jax.random.PRNGKey(100 + idx))
        x = jax.random.normal(kx, (n, d))
        scale = jax.random.normal(ks, (d,))
        impls = {
            "jnp": jax.jit(ref.rmsnorm_ref),
            "pallas": jax.jit(lambda x, s: kernel_ops.rmsnorm(x, s)),
        }
        for impl, fwd in impls.items():
            fwd_bwd = jax.jit(jax.grad(
                lambda x, s, fwd=fwd: jnp.sum(fwd(x, s)), argnums=(0, 1)))
            t_fwd = bench(fwd, x, scale)
            t_bwd = bench(fwd_bwd, x, scale)
            records.append({"kernel": "rmsnorm", "shape": name,
                            "rows": n, "d": d, "impl": impl,
                            "fwd_us": round(t_fwd, 1),
                            "fwd_bwd_us": round(t_bwd, 1)})
            summary.append((f"kern_rmsnorm_{name}_{impl}", t_fwd,
                            f"fwdbwd{t_bwd:.0f}us"))

    out_dir = os.path.dirname(out_path)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"backend": jax.default_backend(),
                   "interpret_mode": jax.default_backend() != "tpu",
                   "n_iter": n_iter, "rows": records}, f, indent=1)
    print(f"[bench] wrote {out_path} ({len(records)} rows)")
    return summary


def _measure_strategy_step(cfg, spec: str, shape, n_iter: int = 3,
                           topo=None):
    """Shared sweep harness: lower ``spec`` for (cfg, host topology),
    execute one compiled train step best-of-``n_iter``, and return
    (strat, report, plan, rt, row) where ``row`` carries the common
    predicted/measured fields — the pp/ep sweeps add their own columns.
    ``topo`` overrides the default all-host-devices topology (the drift
    report measures a 1-device baseline)."""
    import jax
    from repro import strategy as strategy_lib
    from repro.core import parallel as par
    from repro.launch.specs import concrete_train_batch
    from repro.models import transformer as tfm
    from repro.optim import init_opt_state
    from repro.train.trainer import (TrainConfig, make_train_step,
                                     place_train_state)

    topo = topo if topo is not None else strategy_lib.host_topology()
    key = jax.random.PRNGKey(0)
    strat = strategy_lib.parse(spec)
    report = strategy_lib.evaluate(cfg, strat, topo, shape)
    plan = strat.to_plan(cfg, topo, shape)
    # dtypes follow the spec's precision policy (f32 default, _bf16/_fp8
    # opt in) so precision-suffixed specs measure what they claim
    rt = par.make_runtime(cfg, plan, shape, remat=False,
                          attn_min_chunked_len=256)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, shape.global_batch, shape.seq_len, key)
    with par.use_mesh(plan.mesh):
        params_s, opt_s, batch_s, pshard, _ = place_train_state(
            cfg, plan, params, init_opt_state(params), batch)
        step = jax.jit(make_train_step(cfg, rt, TrainConfig()),
                       out_shardings=(pshard, None, None))
        # AOT-compile once: the executable both runs the timing loop and
        # reports the backend's memory analysis (measured peak memory)
        compiled = step.lower(params_s, opt_s, batch_s).compile()
        jax.block_until_ready(compiled(params_s, opt_s, batch_s))  # warm-up
        t_best = float("inf")
        for _ in range(n_iter):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(params_s, opt_s, batch_s))
            t_best = min(t_best, time.perf_counter() - t0)
        mem = _compiled_memory(compiled)
    row = {
        "spec": spec,
        "mesh": {k: int(v) for k, v in plan.mesh.shape.items()},
        "precision": strat.precision,
        "predicted_hw": topo.hardware,
        "predicted_t_step_s": report.t_step,
        "measured_t_step_s": round(t_best, 4),
        "measured_backend": jax.default_backend(),
        # compiled-executable memory analysis (None where the backend
        # does not report one): temp = activations/workspace — the term
        # pipeline schedules actually move; args = params + opt state
        "measured_temp_bytes": mem.get("temp"),
        "measured_arg_bytes": mem.get("args"),
    }
    return strat, report, plan, rt, row


def _compiled_memory(compiled) -> dict:
    """Per-device memory analysis of a compiled executable ({} / None
    fields when the backend can't say)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:                      # noqa: BLE001 — best-effort probe
        return {}
    if ma is None:
        return {}
    def _get(attr):
        v = getattr(ma, attr, None)
        return int(v) if v is not None else None
    return {"temp": _get("temp_size_in_bytes"),
            "args": _get("argument_size_in_bytes")}


def _write_bench(out_path: str, payload: dict, n_rows: int):
    out_dir = os.path.dirname(out_path)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"[bench] wrote {out_path} ({n_rows} rows)")


def _pp_sweep(out_path: str = "results/benchmarks/BENCH_pipeline.json",
              pps=(1, 2, 4), scheds=("gpipe", "1f1b", "1f1b_i2", "zb"),
              ovls=(False, True), n_iter: int = 3):
    """Pipeline sweep over pp in {1,2,4} x schedule in {gpipe, 1f1b,
    1f1b_i2, zb} x ZeRO gather overlap {off, on} on 8 virtual CPU devices
    -> BENCH_pipeline.json (CI artifact).

    The schedule-comparable columns are analytic: the per-schedule
    pipeline bubble fraction ((P-1)/(M+P-1) for gpipe/1f1b, (P-1)/(vM+P-1)
    interleaved, 2(P-1)/(3M+2P-2) zero-bubble), the in-flight activation
    count, the table's sub-tick census, and the cost model's peak memory,
    next to the compiled executable's temp bytes where the backend
    reports them.  The CPU step time is a regression signal only.  The
    `_ovl` variants flip the double-buffered ZeRO gather prefetch.
    """
    from repro.launch.devices import force_host_device_count
    force_host_device_count(8)
    import jax
    from repro.configs import ShapeConfig, get_config, reduced

    cfg = reduced(get_config("qwen3-0.6b"), n_layers=8)
    shape = ShapeConfig("pp-sweep", 128, 16, "train")
    rows, summary = [], []
    for pp in pps:
        for sched in (scheds if pp > 1 else ("gpipe",)):
            for ovl in ovls:
                rows.append(_pp_sweep_point(cfg, shape, pp, sched, ovl,
                                            n_iter, summary))
    _write_bench(out_path, {
        "backend": jax.default_backend(), "n_iter": n_iter,
        "arch": cfg.name, "shape": {"seq_len": shape.seq_len,
                                    "global_batch": shape.global_batch},
        "rows": rows}, len(rows))
    return summary


def _pp_sweep_point(cfg, shape, pp, sched, ovl, n_iter, summary):
    """One (pp, sched, ovl) row of the pipeline sweep."""
    from repro.core.pipeline import (bubble_fraction, inflight_microbatches,
                                     op_tick_counts, virtual_stages)
    if pp == 1:
        spec = "fsdp" + ("_ovl" if ovl else "")
    else:
        spec = f"fsdp_pp{pp}_mb8" \
            + ("" if sched == "gpipe" else f"_{sched}") \
            + ("_ovl" if ovl else "")
    strat, report, _plan, _rt, row = _measure_strategy_step(
        cfg, spec, shape, n_iter)
    t_best = row["measured_t_step_s"]
    row.update(pp=pp, microbatches=strat.microbatches, sched=sched,
               overlap=ovl, virtual_stages=virtual_stages(sched),
               predicted_wps=report.wps,
               predicted_peak_memory_bytes=report.memory_per_device,
               bubble_predicted=bubble_fraction(pp, strat.microbatches,
                                                sched))
    if pp > 1:
        row["inflight_microbatches"] = inflight_microbatches(
            pp, strat.microbatches, sched)
        row["op_tick_counts"] = op_tick_counts(
            sched, pp, strat.microbatches)
    summary.append((f"pp_sweep_{spec}", t_best * 1e6,
                    f"pred{row['bubble_predicted']:.3f}"
                    f"_mem{row['predicted_peak_memory_bytes']/2**20:.0f}MiB"))
    return row


def _ep_sweep(out_path: str = "results/benchmarks/BENCH_moe.json",
              eps=(1, 2, 4, 8), n_iter: int = 3):
    """Predicted vs measured MoE step time across ep in {1,2,4,8} on 8
    virtual CPU devices -> BENCH_moe.json (CI artifact).

    Records the analytic step time and the exposed `moe_a2a` fraction per
    ep degree next to the executed wall time of the EP shard_map dispatch.
    Wall time on CPU is a regression signal, not a TPU claim; the
    comparable trend is the a2a fraction trading against the shrinking
    expert-param gathers as ep grows.
    """
    import dataclasses
    from repro.launch.devices import force_host_device_count
    force_host_device_count(8)
    import jax
    from repro.configs import ShapeConfig, get_config, reduced

    # 8 routed experts so every ep in the sweep divides the expert count
    cfg = reduced(get_config("deepseek-moe-16b"), max_experts=8)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, moe_start_layer=0))
    shape = ShapeConfig("ep-sweep", 128, 16, "train")
    rows, summary = [], []
    for ep in eps:
        spec = "fsdp" if ep == 1 else f"fsdp_ep{ep}"
        _strat, report, _plan, rt, row = _measure_strategy_step(
            cfg, spec, shape, n_iter)
        a2a = report.comm_breakdown["moe_a2a"]
        row.update(
            ep=ep, moe_impl=rt.moe_impl,
            predicted_moe_a2a_s=a2a,
            predicted_exposed_a2a_frac=0.5 * a2a / report.t_step,
            predicted_fsdp_ag_s=report.comm_breakdown["fsdp_ag"])
        rows.append(row)
        summary.append((f"ep_sweep_{spec}", row["measured_t_step_s"] * 1e6,
                        f"a2afrac{row['predicted_exposed_a2a_frac']:.3f}"
                        f"_impl{rt.moe_impl}"))
    _write_bench(out_path, {
        "backend": jax.default_backend(), "n_iter": n_iter,
        "arch": cfg.name, "n_experts": cfg.moe.n_experts,
        "shape": {"seq_len": shape.seq_len,
                  "global_batch": shape.global_batch},
        "rows": rows}, len(rows))
    return summary


def _serve_sweep(out_path: str = "results/benchmarks/BENCH_serve.json",
                 batches=(1, 2, 4, 8), prompt_len: int = 16,
                 n_new: int = 64, n_iter: int = 3):
    """Serving-engine sweep: continuous-batching paged engine vs the
    static dense-cache baseline across offered batch sizes ->
    BENCH_serve.json (CI artifact).

    Per batch size it records, for both engines, end-to-end tokens/s and
    the p50/p99 *effective per-token latency* (continuous: each token's
    share of the wall time of the tick that delivered it; static: the
    wall time of each synchronized host step).  It also isolates the
    decode inner-loop dispatch comparison the paged engine is built
    around: the same jitted paged decode kernel run as one on-device
    ``lax.fori_loop`` segment of ``steps`` iterations vs ``steps``
    single-step host dispatches over identical mid-flight state.  CPU
    wall time is a regression signal, not a TPU claim; the dispatch
    ratio is the comparable trend.
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.models import transformer as tfm
    from repro.models.layers import Runtime
    from repro.serve import ServeEngine

    # small enough that per-step dispatch overhead is visible next to
    # compute — the regime where the on-device segment loop matters
    cfg = reduced(get_config("qwen3-0.6b"), n_layers=2, d_model=128)
    rt = Runtime()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    steps = 32
    rows, summary = [], []
    for B in batches:
        eng = ServeEngine(cfg, params, rt, max_len=prompt_len + n_new + 8,
                          n_slots=B, block_size=16, prefill_chunk=prompt_len,
                          steps_per_tick=steps)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (B, prompt_len), 0, cfg.vocab_size)
        pnp = np.asarray(prompts)
        eng.generate(prompts, n_new)             # compile the paged path
        eng.generate_static(prompts, n_new)      # compile the dense path

        # -- continuous: timed tick loop over the paged engine ----------
        def run_continuous():
            for i in range(B):
                eng.submit(pnp[i], n_new, stream=i)
            base = eng._base_key(None)
            sched = eng._sched
            lat, n_ticks = [], 0
            t0 = time.perf_counter()
            while sched.has_work():
                gen0 = {r.rid: len(r.generated)
                        for r in sched.running.values()}
                t1 = time.perf_counter()
                eng._tick(base)
                wall = time.perf_counter() - t1
                n_ticks += 1
                for r in list(sched.running.values()) + \
                        list(sched.finished.values()):
                    g = len(r.generated) - gen0.get(r.rid, 0)
                    if g:
                        lat += [wall / g] * g
            t_total = time.perf_counter() - t0
            sched.finished.clear()
            return t_total, lat, n_ticks

        t_cont, lat, n_ticks = min(
            (run_continuous() for _ in range(n_iter)), key=lambda r: r[0])
        cont = {"batch": B, "mode": "continuous",
                "tokens_per_s": round(B * n_new / t_cont, 1),
                "p50_token_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_token_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "total_s": round(t_cont, 4), "n_ticks": n_ticks}

        # -- decode dispatch: on-device segment vs per-step host loop ---
        # identical mid-flight paged state (all B slots decode-active
        # after one tick), identical kernel, identical token count
        for i in range(B):
            eng.submit(pnp[i], n_new, stream=i)
        eng._tick(eng._base_key(None))
        cache = eng._cache_dict()
        last = jnp.asarray(eng._last)
        streams = jnp.asarray(eng._streams)
        temps = jnp.asarray(eng._temps)
        kseg = jax.random.PRNGKey(7)

        def seg(c, l, rem, n):
            return eng._segment_fn(eng.params, c, l,
                                   jnp.full((B,), rem, jnp.int32),
                                   streams, temps, kseg, steps=n)

        jax.block_until_ready(seg(cache, last, 1, 1)[1])   # compile steps=1
        t_dev = t_host = float("inf")
        for _ in range(n_iter):
            t1 = time.perf_counter()
            jax.block_until_ready(seg(cache, last, steps, steps)[1])
            t_dev = min(t_dev, time.perf_counter() - t1)
            t1 = time.perf_counter()
            c, l = cache, last
            for _ in range(steps):
                c, out = seg(c, l, 1, 1)
                l = out[:, 0]
            jax.block_until_ready(l)
            t_host = min(t_host, time.perf_counter() - t1)
        eng.run_until_drained()                  # leave the engine clean
        cont.update(
            decode_on_device_ms_per_step=round(t_dev / steps * 1e3, 4),
            decode_host_dispatch_ms_per_step=round(t_host / steps * 1e3, 4),
            decode_dispatch_speedup=round(t_host / t_dev, 3))

        # -- static baseline: batch prefill + one host step per token ---
        t_stat = float("inf")
        for _ in range(n_iter):
            t0 = time.perf_counter()
            jax.block_until_ready(eng.generate_static(prompts, n_new))
            t_stat = min(t_stat, time.perf_counter() - t0)
        # per-token latency needs per-step walls -> synchronized replay
        logits, cache = eng._prefill(eng.params, {"tokens": prompts})
        last = jax.block_until_ready(logits[:, -1])
        walls = []
        for t in range(n_new):
            t1 = time.perf_counter()
            nxt = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
            logits, cache = eng._step(eng.params, cache, nxt,
                                      jnp.asarray(prompt_len + t, jnp.int32))
            last = jax.block_until_ready(logits[:, 0])
            walls.append(time.perf_counter() - t1)
        stat = {"batch": B, "mode": "static",
                "tokens_per_s": round(B * n_new / t_stat, 1),
                "p50_token_ms": round(float(np.percentile(walls, 50)) * 1e3, 3),
                "p99_token_ms": round(float(np.percentile(walls, 99)) * 1e3, 3),
                "total_s": round(t_stat, 4)}
        rows += [cont, stat]
        summary.append((f"serve_b{B}_continuous", t_cont * 1e6,
                        f"tok/s{cont['tokens_per_s']:.0f}"
                        f"_p50{cont['p50_token_ms']:.2f}ms"
                        f"_devstep{cont['decode_on_device_ms_per_step']:.2f}ms"
                        f"_hoststep{cont['decode_host_dispatch_ms_per_step']:.2f}ms"))
        summary.append((f"serve_b{B}_static", t_stat * 1e6,
                        f"tok/s{stat['tokens_per_s']:.0f}"
                        f"_p50{stat['p50_token_ms']:.2f}ms"))
        if B >= 4 and t_host <= t_dev:
            print(f"[bench] warn: B={B} on-device segment "
                  f"({t_dev/steps*1e3:.3f}ms/step) did not beat host "
                  f"dispatch ({t_host/steps*1e3:.3f}ms/step) — noisy host?")

    import jax as _jax
    _write_bench(out_path, {
        "backend": _jax.default_backend(), "arch": cfg.name,
        "prompt_len": prompt_len, "n_new": n_new,
        "steps_per_tick": steps, "block_size": 16,
        "prefill_chunk": prompt_len, "n_iter": n_iter,
        "rows": rows}, len(rows))
    return summary


def _goodput_sweep(out_path: str = "results/benchmarks/BENCH_goodput.json",
                   n_devices=(256, 512, 1024, 2048, 4096, 8192),
                   mtbfs=(0.0, 1.8e8, 3e6, 1e6)):
    """Failure-aware diminishing returns -> BENCH_goodput.json (CI artifact).

    Two halves:

    * **analytic**: effective tokens/s vs device count for llama2-7b on
      H100 islands, with and without failures at swept per-device MTBFs
      (0.0 = no failures).  At each point the planner picks its best
      strategy under both 'wps' and 'effective_wps' over the
      {hsdp, fsdp} dp-mode sweep — where the picks differ, the goodput
      objective changed the sharding decision (few checkpoint writers
      vs many), the paper's diminishing-returns curve bending further
      down once failures are priced.
    * **measured**: per-step checkpoint stall of the sync writer vs the
      AsyncCheckpointer (snapshot-only stall) for a real train state on
      the host devices — the number that justifies ``--async_ckpt``.
    """
    import dataclasses as _dc
    import shutil
    import tempfile

    from repro.launch.devices import force_host_device_count
    force_host_device_count(8)
    import jax
    from repro import strategy as strategy_lib
    from repro.configs import ShapeConfig, get_config
    from repro.core import costmodel as cm

    cfg = get_config("llama2-7b")
    shape = ShapeConfig("goodput-sweep", 4096, 1024, "train")
    modes = ("hsdp", "fsdp")
    rows, summary = [], []
    n_flips = 0
    for mtbf in mtbfs:
        for n in n_devices:
            hw = cm.HARDWARE["H100"]
            if mtbf:
                hw = _dc.replace(hw, mtbf=mtbf)
            topo = strategy_lib.Topology("goodput", n, hw.island,
                                         hardware="H100", hbm=80e9,
                                         hw_obj=hw if mtbf else None)
            a = strategy_lib.best(cfg, topo, shape, objective="wps",
                                  dp_modes=modes)
            b = strategy_lib.best(cfg, topo, shape,
                                  objective="effective_wps", dp_modes=modes)
            if a is None or b is None:
                continue
            r = b.report
            eff = r.wps * (r.goodput_frac if mtbf else 1.0)
            flip = a.spec != b.spec
            n_flips += flip
            rows.append({
                "mtbf_device_s": mtbf or None,   # None = failure-free
                "n_devices": n,
                "wps_pick": a.spec, "effective_pick": b.spec,
                "objectives_disagree": flip,
                "wps": a.report.wps,
                "effective_wps": eff,
                "goodput": r.goodput_frac if mtbf else 1.0,
                "t_ckpt_s": r.t_ckpt,
                "young_daly_interval_s": r.ckpt_interval,
                "distinct_writers": cm.distinct_writers(
                    b.strategy.to_cost_strategy(cfg, topo)),
            })
    # measured: sync full-write stall vs async snapshot-only stall
    from repro import checkpointing as ckpt_lib
    key = jax.random.PRNGKey(0)
    state = {"params": {f"w{i}": jax.random.normal(
        jax.random.fold_in(key, i), (256, 256)) for i in range(8)}}
    tmp = tempfile.mkdtemp(prefix="goodput-bench-")
    try:
        t0 = time.perf_counter()
        ckpt_lib.save_checkpoint(os.path.join(tmp, "sync"), 1, state)
        t_sync = time.perf_counter() - t0
        with ckpt_lib.AsyncCheckpointer(os.path.join(tmp, "async")) as ck:
            t_async = ck.save(1, state)
            ck.wait()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    measured = {
        "state_bytes": int(sum(a.size * a.dtype.itemsize for a in
                               jax.tree.leaves(state))),
        "sync_save_stall_s": round(t_sync, 5),
        "async_save_stall_s": round(t_async, 5),
        "async_stall_fraction": round(t_async / max(t_sync, 1e-9), 4),
    }
    _write_bench(out_path, {
        "backend": jax.default_backend(), "arch": cfg.name,
        "shape": {"seq_len": shape.seq_len,
                  "global_batch": shape.global_batch},
        "hardware": "H100", "dp_modes": list(modes),
        "objective_flips": n_flips,
        "rows": rows, "checkpoint_stall": measured}, len(rows))
    summary.append(("goodput_sweep_flips", float(n_flips),
                    f"{len(rows)}pts_async_stall"
                    f"{measured['async_stall_fraction']:.3f}x_sync"))
    return summary


def _precision_sweep(out_path: str = "results/benchmarks/BENCH_precision.json",
                     n_iter: int = 3):
    """Mixed-precision sweep -> BENCH_precision.json (CI artifact).

    Three sections:

    * **measured**: the same FSDP mesh executed under the _f32 / _bf16 /
      _fp8 precision policies on 8 virtual host devices.  CPU wall time
      is a regression signal, not a TPU claim — what the section proves
      is that each policy lowers and runs end-to-end (bf16 compute with
      f32 master params; fp8 additionally quantizing the per-layer ZeRO
      gather wire) and that the loss stays finite.
    * **kernels**: Pallas flash-attention / rmsnorm fwd at f32 vs bf16
      inputs with the dtype-resolved block defaults (bf16 doubles the
      tile: same VMEM footprint, half the grid steps).
    * **analytic**: the dtype-aware cost model pricing llama2-7b on a
      TPU v5e pod per precision — the byte terms that move the paper's
      EP/PP/FSDP crossovers when precision changes — plus the spec the
      planner picks once precision is a swept degree (bf16 dominates f32
      on any fixed mesh: half the wire bytes, double the matmul rate).
    """
    import dataclasses as _dc

    from repro.launch.devices import force_host_device_count
    force_host_device_count(8)
    import jax
    import jax.numpy as jnp
    from repro import strategy as strategy_lib
    from repro.configs import ShapeConfig, get_config, reduced
    from repro.core import costmodel as cm
    from repro.kernels import ops as kernel_ops

    rows, summary = [], []

    # -- measured: one mesh, three policies -----------------------------
    cfg = reduced(get_config("qwen3-0.6b"), n_layers=4)
    shape = ShapeConfig("precision-sweep", 128, 16, "train")
    for spec in ("fsdp", "fsdp_bf16", "fsdp_fp8"):
        strat, report, plan, rt, row = _measure_strategy_step(
            cfg, spec, shape, n_iter)
        row.update(section="measured",
                   compute_dtype=str(rt.compute_dtype),
                   comm_dtype=plan.policy.comm_dtype
                   or str(jnp.dtype(rt.param_dtype)),
                   predicted_wps=report.wps)
        rows.append(row)
        summary.append((f"precision_step_{spec}",
                        row["measured_t_step_s"] * 1e6,
                        f"compute{row['compute_dtype']}"
                        f"_comm{row['comm_dtype']}"))

    # -- kernels: dtype-resolved block defaults -------------------------
    def bench(fn, *args):
        fn(*args)                                  # compile / first trace
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n_iter):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / n_iter * 1e6

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    for dt in (jnp.float32, jnp.bfloat16):
        name = str(jnp.dtype(dt))
        q = jax.random.normal(kq, (1, 512, 4, 64), dt)
        k = jax.random.normal(kk, (1, 512, 2, 64), dt)
        v = jax.random.normal(kv, (1, 512, 2, 64), dt)
        t_attn = bench(jax.jit(
            lambda q, k, v: kernel_ops.attention(q, k, v)), q, k, v)
        x = jax.random.normal(kq, (2048, 256), dt)
        s = jax.random.normal(kk, (256,), dt)
        t_norm = bench(jax.jit(
            lambda x, s: kernel_ops.rmsnorm(x, s)), x, s)
        rows.append({"section": "kernels", "dtype": name,
                     "block_q": kernel_ops._dtype_blocks(dt, 128),
                     "block_kv": kernel_ops._dtype_blocks(dt, 256),
                     "block_rows": kernel_ops._dtype_blocks(dt, 256),
                     "attn_fwd_us": round(t_attn, 1),
                     "rmsnorm_fwd_us": round(t_norm, 1)})
        summary.append((f"precision_kern_{name}", t_attn,
                        f"rms{t_norm:.0f}us"
                        f"_bq{kernel_ops._dtype_blocks(dt, 128)}"))

    # -- analytic: dtype-aware byte terms + planner pick -----------------
    cfg7 = get_config("llama2-7b")
    hw = cm.HARDWARE["TPUv5e"]
    for prec in ("f32", "bf16", "fp8"):
        s = _dc.replace(cm.Strategy(256, zero_stage=3), precision=prec)
        r = cm.step_time(cfg7, hw, s, 1024, 2048)
        rows.append({"section": "analytic", "precision": prec,
                     "arch": cfg7.name, "hardware": hw.name,
                     "n_devices": 256, "zero_stage": 3,
                     "t_step_s": r.t_step, "mfu": r.mfu,
                     "fsdp_ag_s": r.comm_breakdown["fsdp_ag"],
                     "fsdp_rs_s": r.comm_breakdown["fsdp_rs"],
                     "memory_per_device": r.memory_per_device})
        summary.append((f"precision_analytic_{prec}", r.t_step * 1e6,
                        f"mfu{r.mfu:.3f}"
                        f"_ag{rows[-1]['fsdp_ag_s'] * 1e3:.1f}ms"))
    topo = strategy_lib.Topology("v5e-pod", 256, hw.island,
                                 hardware=hw.name, hbm=16e9)
    shape7 = ShapeConfig("precision-analytic", 2048, 1024, "train")
    pick = strategy_lib.best(cfg7, topo, shape7)
    if pick is not None:
        rows.append({"section": "planner", "pick": pick.spec,
                     "precision": pick.strategy.precision,
                     "wps": pick.report.wps, "mfu": pick.report.mfu})
        summary.append(("precision_planner_pick", pick.report.t_step * 1e6,
                        pick.spec))

    _write_bench(out_path, {
        "backend": jax.default_backend(), "n_iter": n_iter,
        "measured_arch": cfg.name, "analytic_arch": cfg7.name,
        "rows": rows}, len(rows))
    return summary


def _strategy_benchmark(spec: str, hw_name: str, gpus: int, global_batch: int,
                        seq_len: int):
    """Price one spec (or the planner's 'auto' pick) via the unified API."""
    from repro import strategy as strategy_lib
    from repro.configs.base import ShapeConfig
    from repro.configs.llama2 import LLAMA2_7B
    from repro.core import costmodel as cm
    hw = cm.HARDWARE[hw_name]
    topo = strategy_lib.Topology(hw.name, gpus, island=hw.island,
                                 hardware=hw.name, hbm=80e9)
    shape = ShapeConfig("bench", seq_len, global_batch, "train")
    t0 = time.perf_counter()
    strat, planned = strategy_lib.resolve(spec, LLAMA2_7B, topo, shape)
    r = (planned.report if planned is not None
         else strategy_lib.evaluate(LLAMA2_7B, strat, topo, shape))
    dt_us = (time.perf_counter() - t0) * 1e6
    return [("strategy_" + strat.format(), dt_us,
             f"{hw_name}x{gpus}_wps{r.wps:.0f}_mfu{r.mfu:.3f}")]


def _drift_report(out_path: str = "results/benchmarks/BENCH_drift.json",
                  tel_dir: str = "results/telemetry",
                  specs=("fsdp", "fsdp_tp2"), n_iter: int = 3):
    """Predicted-vs-measured drift per cost-model term — the measured
    half of the measure<->model calibration loop (ROADMAP item).

    Differential probe on 8 virtual CPU devices: the same reduced model
    runs one optimizer step (a) on a **single** device (no collectives —
    its wall time stands in for the measured compute term) and (b) under
    each sharded spec.  measured collective ~= t_spec - t_single, the
    same two-point logic as the pipeline bubble probe.  Each spec's
    :class:`repro.telemetry.DriftMonitor` diffs that against
    ``StepReport.decomposition()`` and the per-term
    ``predicted_over_measured`` ratios land in BENCH_drift.json plus
    per-spec reports, a JSONL event stream, and a Perfetto trace under
    ``results/telemetry/`` (CI schema-checks and uploads them).

    On CPU hosts the *ratios* are apples-to-oranges against the H100
    profile (that gap is exactly what hardware-profile calibration will
    fit); what must hold structurally is that both compute and
    collective terms get a measured value and a ratio.
    """
    from repro.launch.devices import force_host_device_count
    force_host_device_count(8)
    import jax
    from repro import strategy as strategy_lib
    from repro import telemetry as tel
    from repro.configs import ShapeConfig, get_config, reduced

    cfg = reduced(get_config("qwen3-0.6b"), n_layers=4)
    shape = ShapeConfig("drift", 128, 16, "train")
    os.makedirs(tel_dir, exist_ok=True)
    recorder = tel.Recorder()
    recorder.add_sink(tel.JsonlSink(
        os.path.join(tel_dir, "drift_events.jsonl")))
    recorder.add_sink(tel.ChromeTraceSink(
        os.path.join(tel_dir, "drift_trace.json"),
        process_name="drift-report"))

    topo1 = strategy_lib.host_topology(n_devices=1)
    with recorder.span("drift/baseline_1dev"):
        _, _, _, _, row1 = _measure_strategy_step(cfg, "ddp", shape,
                                                  n_iter, topo=topo1)
    t_single = row1["measured_t_step_s"]
    recorder.gauge("drift/measured_compute_s", t_single)

    rows, summary = [], []
    for spec in specs:
        with recorder.span("drift/measure", spec=spec):
            strat, report, plan, rt, row = _measure_strategy_step(
                cfg, spec, shape, n_iter)
        t_spec = row["measured_t_step_s"]
        coll_raw = t_spec - t_single
        measured = {
            "step": t_spec,
            "compute": t_single,
            # floored so the collective term always yields a ratio; the
            # raw (possibly ~0) delta is recorded alongside
            "collective": max(coll_raw, 1e-6),
        }
        monitor = tel.DriftMonitor(
            report.decomposition(), telemetry=recorder,
            meta={"spec": spec, "arch": cfg.name,
                  "predicted_hw": row["predicted_hw"],
                  "measured_backend": row["measured_backend"],
                  "probe": "differential-1dev-baseline",
                  "n_iter": n_iter})
        window = monitor.observe(measured, n_steps=n_iter)
        monitor.write(os.path.join(tel_dir, f"drift_{spec}.json"))
        ratios = window["predicted_over_measured"]
        row.update(measured_compute_s=t_single,
                   measured_collective_raw_s=round(coll_raw, 6),
                   predicted=report.decomposition(),
                   measured=measured,
                   predicted_over_measured=ratios)
        rows.append(row)
        summary.append((
            f"drift_{spec}", t_spec * 1e6,
            "pred/meas:" + ";".join(
                f"{t}={ratios[t]:.3g}" for t in
                ("step", "compute", "collective") if ratios.get(t))))
    recorder.close()
    _write_bench(out_path, {
        "backend": jax.default_backend(),
        "baseline_spec": "ddp@1dev",
        "baseline_t_step_s": t_single,
        "telemetry_dir": tel_dir,
        "rows": rows}, len(rows))
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="auto",
                    help="'auto' (planner pick) or a spec string like "
                         "hsdp_tp4 / fsdp_cp8 to price on --hw x --gpus")
    ap.add_argument("--hw", default="H100")
    ap.add_argument("--gpus", type=int, default=2048)
    ap.add_argument("--global_batch", type=int, default=4096)
    ap.add_argument("--seq_len", type=int, default=4096)
    ap.add_argument("--micro-kernels", dest="micro_kernels",
                    action="store_true",
                    help="only run the fwd/fwd+bwd kernel microbenchmarks "
                         "(jnp vs pallas) and write BENCH_kernels.json")
    ap.add_argument("--kernel_json",
                    default="results/benchmarks/BENCH_kernels.json")
    ap.add_argument("--pp-sweep", dest="pp_sweep", action="store_true",
                    help="only run the pipeline-parallel sweep "
                         "(analytic bubble, in-flight count and sub-tick "
                         "census, predicted and compiled peak memory for "
                         "pp in {1,2,4} x {gpipe,1f1b,1f1b_i2,zb} x "
                         "overlap {off,on} on 8 virtual devices) and "
                         "write BENCH_pipeline.json")
    ap.add_argument("--pipeline_json",
                    default="results/benchmarks/BENCH_pipeline.json")
    ap.add_argument("--ep-sweep", dest="ep_sweep", action="store_true",
                    help="only run the expert-parallel sweep (predicted "
                         "vs measured step time + exposed moe_a2a "
                         "fraction for ep in {1,2,4,8} on 8 virtual "
                         "devices) and write BENCH_moe.json")
    ap.add_argument("--moe_json",
                    default="results/benchmarks/BENCH_moe.json")
    ap.add_argument("--serve-sweep", dest="serve_sweep", action="store_true",
                    help="only run the serving-engine sweep (continuous-"
                         "batching paged engine vs static dense baseline: "
                         "tokens/s, p50/p99 per-token latency, and the "
                         "on-device decode segment vs per-step host "
                         "dispatch comparison) and write BENCH_serve.json")
    ap.add_argument("--serve_json",
                    default="results/benchmarks/BENCH_serve.json")
    ap.add_argument("--goodput-sweep", dest="goodput_sweep",
                    action="store_true",
                    help="only run the failure-aware goodput sweep "
                         "(analytic effective tokens/s vs device count "
                         "w/wo failures at swept MTBFs, planner picks "
                         "under wps vs effective_wps, and the measured "
                         "async-vs-sync checkpoint stall) and write "
                         "BENCH_goodput.json")
    ap.add_argument("--goodput_json",
                    default="results/benchmarks/BENCH_goodput.json")
    ap.add_argument("--precision-sweep", dest="precision_sweep",
                    action="store_true",
                    help="only run the mixed-precision sweep (f32/bf16/fp8 "
                         "train-step execution on one mesh, dtype-tuned "
                         "kernel blocks, and the dtype-aware cost-model "
                         "column with the planner's precision pick) and "
                         "write BENCH_precision.json")
    ap.add_argument("--precision_json",
                    default="results/benchmarks/BENCH_precision.json")
    ap.add_argument("--drift-report", dest="drift_report",
                    action="store_true",
                    help="only run the predicted-vs-measured drift probe "
                         "(cost-model step/compute/collective terms vs a "
                         "differential 1-device-baseline measurement on 8 "
                         "virtual devices) and write BENCH_drift.json + "
                         "results/telemetry/ artifacts")
    ap.add_argument("--drift_json",
                    default="results/benchmarks/BENCH_drift.json")
    ap.add_argument("--telemetry_dir", default="results/telemetry")
    args = ap.parse_args()

    if args.micro_kernels:
        rows = _kernel_microbenchmarks(args.kernel_json)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    if args.pp_sweep:
        rows = _pp_sweep(args.pipeline_json)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    if args.ep_sweep:
        rows = _ep_sweep(args.moe_json)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    if args.serve_sweep:
        rows = _serve_sweep(args.serve_json)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    if args.goodput_sweep:
        rows = _goodput_sweep(args.goodput_json)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    if args.precision_sweep:
        rows = _precision_sweep(args.precision_json)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    if args.drift_report:
        rows = _drift_report(args.drift_json, args.telemetry_dir)
        print("name,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        return

    rows = _figure_benchmarks()
    rows += _micro_benchmarks()
    rows += _strategy_benchmark(args.strategy, args.hw, args.gpus,
                                args.global_batch, args.seq_len)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    # paper-claim anchor validation (same checks as tests/test_costmodel.py)
    from repro.configs.llama2 import LLAMA2_7B
    from repro.core import costmodel as cm
    r128 = cm.step_time(LLAMA2_7B, cm.H100, cm.Strategy(128, zero_stage=2),
                        256, 4096)
    r2048 = cm.step_time(LLAMA2_7B, cm.H100, cm.Strategy(2048, zero_stage=2),
                         4096, 4096)
    drop = 1 - r2048.tflops_per_device / r128.tflops_per_device
    pdrop = 1 - r2048.power_per_device / r128.power_per_device
    base = cm.step_time(LLAMA2_7B, cm.H100, cm.Strategy(2048, zero_stage=2),
                        4096, 4096)
    tpgain = max(cm.step_time(LLAMA2_7B, cm.H100,
                              cm.Strategy(2048, tp=tp, zero_stage=2),
                              4096, 4096).wps for tp in (2, 4)) / base.wps - 1
    print(f"claim_weak_scaling_drop,{drop:.4f},paper=0.3722")
    print(f"claim_power_drop,{pdrop:.4f},paper=0.0587")
    print(f"claim_tp_gain_2048,{tpgain:.4f},paper=0.5260")


if __name__ == "__main__":
    main()
